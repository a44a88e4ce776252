"""Wall milliseconds per store flush in `store.wal_sync` (`write`, `flush`,
`os.fsync` of the WAL): the part of `store_flush_ms.ingest` that does not
hold the interpreter."""
from _common import histogram_mean_delta


def read(ctx):
    return histogram_mean_delta(ctx, "span.store.wal_sync.ms")
