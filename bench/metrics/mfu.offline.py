"""Share of the chip's peak of the whole jobs: the counted operations of
the window's jobs (propagation, distances and classification of every
node), over the window's length times the peak, in percent."""
from yardstick.readers import mean_flops, mfu_pct


def read(rec):
    if "jobs" not in rec:
        return None
    return mfu_pct(rec, mean_flops(rec) * rec["jobs"])
