"""Mean batch fill of the window's engine batches: requests served over
batches over the batch size (`EngineStats`)."""


def read(rec):
    if not rec.get("batches"):
        return None
    return rec["served"] / rec["batches"] / rec["batch_size"]
