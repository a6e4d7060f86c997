"""Mean host stage per batch (sampler, feature gather, packing), from the
engine's `batch_timings` host spans."""


def read(rec):
    if not rec.get("host_s"):
        return None
    return 1e3 * sum(rec["host_s"]) / len(rec["host_s"])
