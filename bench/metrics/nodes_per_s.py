"""Nodes classified by whole jobs over the wall time of those jobs,
packing, checkpoints and classification included."""


def read(rec):
    if "jobs" not in rec:
        return None
    return rec["nodes"] * rec["jobs"] / rec["wall_s"]
