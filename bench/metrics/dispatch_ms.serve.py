"""Mean milliseconds per batch of the window in operand transfer and the
runner's asynchronous dispatch (span `nai.serve.dispatch`, `dispatch_s` of
the engine's `"serve.batch"` records)."""
from yardstick.spans import mean, serve_batches


def read(rec):
    v = mean(serve_batches(rec), "dispatch_s")
    return None if v is None else 1e3 * v
