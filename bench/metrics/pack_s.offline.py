"""Seconds per job spent packing the whole graph (`stats["pack_s"]` of
`run_full_graph_infer`)."""


def read(rec):
    if not rec.get("pack_s"):
        return None
    return sum(rec["pack_s"]) / len(rec["pack_s"])
