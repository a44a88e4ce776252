"""CPU milliseconds per page the store spends encoding WAL records
(`store.wal_encode`: base64 of a row's float32 bytes and `json.dumps` of
its record, a row at a time, then the join)."""
from _stages import stage_cpu_ms_per_page


def read(ctx):
    return stage_cpu_ms_per_page(ctx, "store.wal_encode")
