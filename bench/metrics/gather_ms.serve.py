"""Mean milliseconds per batch of the window in the store's feature gather
and the stationary state built from it (span `nai.serve.gather`,
`gather_s` of the engine's `"serve.batch"` records)."""
from yardstick.spans import mean, serve_batches


def read(rec):
    v = mean(serve_batches(rec), "gather_s")
    return None if v is None else 1e3 * v
