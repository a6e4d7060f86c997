"""Mean milliseconds a request of the window waited in the front-end
queue: from its intended arrival to the start of its batch's host stage
(`queue_wait_s` over `n` of the engine's ``"serve.batch"`` records)."""
from yardstick.spans import ratio, serve_batches


def read(rec):
    v = ratio(serve_batches(rec), "queue_wait_s", "n")
    return None if v is None else 1e3 * v
