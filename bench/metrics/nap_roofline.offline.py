"""Roofline share of the superstep (`jit_step_single`): the least time of
one propagation step over the whole graph's real edges and rows, with its
exit distances (`yardstick/work.py`), over the superstep's mean device
time, in percent."""
from yardstick.readers import roofline_pct

MODULE = "jit_step_single"


def read(rec):
    work = rec.get("work")
    if not work:
        return None
    return roofline_pct(rec, MODULE, ("prop", "dist"), per_call=work[0]["steps"])
