"""Median, over every request offered in the window, of completion time
minus intended arrival; a request shed or failed counts as infinite."""
from yardstick.measure import percentile

Q = 50


def read(rec):
    if "latency_s" not in rec:
        return None
    v, _ = percentile(rec["latency_s"], Q)
    if v == float("inf"):
        raise ValueError(f"the p{Q} latency falls on a request that was "
                         f"never answered")
    return 1e3 * v
