"""Share of the window's batches whose answers the engine's completion
waiter delivered before the engine thread reached the batch's finalize
(`early` of the engine's ``"serve.batch"`` records; a program without the
waiter has no such counter, and the metric is left out)."""
from yardstick.spans import mean, serve_batches


def read(rec):
    return mean(serve_batches(rec), "early")
