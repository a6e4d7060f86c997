"""Arithmetic the per-layer metric readers share: device time of a
jitted program in the trace, its roofline share and the whole window's
share of the chip's peak."""
from __future__ import annotations

from .work import least_time, total


def per_call_s(rec, module):
    """Mean device seconds per execution of `module` in the traced window,
    and how many executions the window holds (None, 0 if none)."""
    m = ((rec.get("trace") or {}).get("modules") or {}).get(module)
    if not m or not m["count"]:
        return None, 0
    return m["total_s"] / m["count"], m["count"]


def roofline_pct(rec, module, parts, per_call=1):
    """Least time of the counted work of one execution of `module` over its
    mean device time, in percent. Each execution does 1/`per_call` of a
    counted unit of work (a batch, or a job's supersteps)."""
    t, _ = per_call_s(rec, module)
    work = rec.get("work")
    if t is None or not work or "peak" not in rec:
        return None
    least = [least_time(*total(w, parts), rec["peak"])[0] for w in work]
    return 100.0 * sum(least) / len(least) / per_call / t


def mfu_pct(rec, flops_in_window):
    """Counted operations of the window over the window's length times the
    peak, in percent."""
    tr = rec.get("trace")
    if tr is None or "peak" not in rec or not flops_in_window:
        return None
    return 100.0 * flops_in_window / (tr["window_s"] * rec["peak"]["flops_per_s"])


def mean_flops(rec):
    """Mean counted operations of one batch or job of the record."""
    work = rec.get("work") or []
    if not work:
        return 0.0
    return sum(total(w)[0] for w in work) / len(work)
