"""Roofline share of the serving runner (`jit_run`): the least time of a
batch's counted work (propagation over the support's real edges and rows,
exit distances, classification; `yardstick/work.py`) at the chip's peaks,
over the runner's mean device time per batch, in percent."""
from yardstick.readers import roofline_pct

MODULE = "jit_run"


def read(rec):
    return roofline_pct(rec, MODULE, ("prop", "dist", "cls"))
