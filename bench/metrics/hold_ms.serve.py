"""Mean milliseconds per batch of the window in the pipeline hold, from the
end of the batch's dispatch to the start of its sync (`hold_s` of the
engine's `"serve.batch"` records)."""
from yardstick.spans import mean, serve_batches


def read(rec):
    v = mean(serve_batches(rec), "hold_s")
    return None if v is None else 1e3 * v
