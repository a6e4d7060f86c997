"""Share of the packed edge slots of the window's batches that hold real
support edges: `edges_real` over `edges_pad` of the engine's
``"serve.batch"`` records."""
from yardstick.spans import ratio, serve_batches


def read(rec):
    return ratio(serve_batches(rec), "edges_real", "edges_pad")
