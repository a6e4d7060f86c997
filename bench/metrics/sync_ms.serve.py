"""Mean milliseconds per batch of the window in the result sync: waiting for
the device, copying and checking the answers (span `nai.serve.sync`,
`sync_s` of the engine's `"serve.batch"` records)."""
from yardstick.spans import mean, serve_batches


def read(rec):
    v = mean(serve_batches(rec), "sync_s")
    return None if v is None else 1e3 * v
