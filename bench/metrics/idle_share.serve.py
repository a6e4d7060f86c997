"""Share of the traced serving window in which no operation ran on the
device: 1 - busy / window."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or "deadline_hits" not in rec:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
