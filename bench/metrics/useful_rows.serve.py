"""Share of the packed support rows of the window's batches that are real
support nodes: `rows_real` over `rows_pad` (the bucket's padded row count)
of the engine's ``"serve.batch"`` records."""
from yardstick.spans import ratio, serve_batches


def read(rec):
    return ratio(serve_batches(rec), "rows_real", "rows_pad")
