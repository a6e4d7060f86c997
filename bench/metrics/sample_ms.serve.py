"""Mean milliseconds per batch of the window in the support sampler
(`sample_support`) (span `nai.serve.sample`, `sample_s` of the engine's
`"serve.batch"` records)."""
from yardstick.spans import mean, serve_batches


def read(rec):
    v = mean(serve_batches(rec), "sample_s")
    return None if v is None else 1e3 * v
