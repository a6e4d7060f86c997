"""Seconds per job spent writing fsynced checkpoints (`stats["ckpt_s"]` of
`run_full_graph_infer`, one save per superstep and one of the input)."""


def read(rec):
    if not rec.get("ckpt_s"):
        return None
    return sum(rec["ckpt_s"]) / len(rec["ckpt_s"])
