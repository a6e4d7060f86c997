"""Share of the chip's peak of the whole serving step: the counted
operations of the batches the runner executed in the traced window
(propagation, distances and classification), over the window's length
times the peak, in percent."""
from yardstick.readers import mean_flops, mfu_pct, per_call_s

MODULE = "jit_run"


def read(rec):
    _, count = per_call_s(rec, MODULE)
    return mfu_pct(rec, mean_flops(rec) * count)
