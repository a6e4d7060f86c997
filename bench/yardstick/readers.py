"""Arithmetic the per-layer metric readers share: device time of a
jitted program in the trace, its roofline share and the whole window's
share of the chips' peak.

Device times are per chip (`trace.reduce_events`), and the counted work
is the whole job's or batch's, so both shares divide it by the record's
``chips`` times one chip's peak."""
from __future__ import annotations

from .work import least_time, total


def per_call_s(rec, module):
    """Mean device seconds of one execution of `module` on one chip in the
    traced window, and how many executions a chip ran (None, 0 if none)."""
    m = ((rec.get("trace") or {}).get("modules") or {}).get(module)
    if not m or not m["count"]:
        return None, 0
    return m["total_s"] / m["count"], m["count"]


def _peak(rec):
    """The peaks of all the record's chips together."""
    chips = rec["chips"]
    return {k: chips * rec["peak"][k] for k in ("flops_per_s",
                                                 "hbm_bytes_per_s")}


def roofline_pct(rec, module, parts, per_call=1):
    """Least time of the counted work of one execution of `module` on all
    the record's chips over its mean device time on one, in percent. Each
    execution does 1/`per_call` of a counted unit of work (a batch, or a
    job's supersteps)."""
    t, _ = per_call_s(rec, module)
    work = rec.get("work")
    if t is None or not work or "peak" not in rec:
        return None
    least = [least_time(*total(w, parts), _peak(rec))[0] for w in work]
    return 100.0 * sum(least) / len(least) / per_call / t


def mfu_pct(rec, flops_in_window):
    """Counted operations of the window over the window's length times the
    peak of the record's chips, in percent."""
    tr = rec.get("trace")
    if tr is None or "peak" not in rec or not flops_in_window:
        return None
    return 100.0 * flops_in_window / (tr["window_s"]
                                      * _peak(rec)["flops_per_s"])


def mean_flops(rec):
    """Mean counted operations of one batch or job of the record."""
    work = rec.get("work") or []
    if not work:
        return 0.0
    return sum(total(w)[0] for w in work) / len(work)
