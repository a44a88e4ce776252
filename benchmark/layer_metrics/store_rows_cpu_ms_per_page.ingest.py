"""CPU milliseconds per page the store spends placing rows
(`store.ingest_rows`: normalise, the per-row loop, the append into the host
blocks, ids and payloads), under its lock and before the WAL."""
from _stages import stage_cpu_ms_per_page


def read(ctx):
    return stage_cpu_ms_per_page(ctx, "store.ingest_rows")
