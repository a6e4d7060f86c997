"""Share of the traced window of whole-graph jobs in which no operation
ran on the device: 1 - busy / window."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or "jobs" not in rec:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
