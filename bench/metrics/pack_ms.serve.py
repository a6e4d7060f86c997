"""Mean milliseconds per batch of the window in packing into the pooled
buffer set, with the bucket bookkeeping (span `nai.serve.pack`, `pack_s`
of the engine's `"serve.batch"` records)."""
from yardstick.spans import mean, serve_batches


def read(rec):
    v = mean(serve_batches(rec), "pack_s")
    return None if v is None else 1e3 * v
