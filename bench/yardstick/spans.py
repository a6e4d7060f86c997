"""The program's own per-batch and per-job records (`repro.obs`), selected
for the measured window.

The serving engine publishes one ``"serve.batch"`` record per completed
batch and the offline driver one ``"offline.job"`` record per finished
job, in the benchmark's own process. The window's records are the last
``rec["batches"]`` (window and drain, the population ``host_s`` holds) or
the last ``rec["jobs"]``. A selection that does not match the run's record
(request counts, packing seconds), or a program without `repro.obs`,
gives None: the metric is then left out, never read from other work.
"""
from __future__ import annotations

from typing import List, Optional


def _log(kind: str) -> List[dict]:
    try:
        from repro import obs
    except ImportError:
        return []
    return obs.records(kind)


def serve_batches(rec) -> Optional[List[dict]]:
    """The window's engine batch records, oldest first, or None."""
    n = rec.get("batches")
    if not n or "served" not in rec:
        return None
    sel = _log("serve.batch")[-n:]
    if len(sel) != n or sum(r["n"] for r in sel) != rec["served"]:
        return None
    return sel


def offline_jobs(rec) -> Optional[List[dict]]:
    """The window's offline job records, oldest first, or None."""
    n = rec.get("jobs")
    if not n or "pack_s" not in rec:
        return None
    sel = _log("offline.job")[-n:]
    if len(sel) != n or [r.get("pack_s") for r in sel] != list(rec["pack_s"]):
        return None
    return sel


def mean(sel, key: str) -> Optional[float]:
    """Mean of `key` over the selected records (None if any lacks it)."""
    if not sel or any(key not in r for r in sel):
        return None
    return sum(r[key] for r in sel) / len(sel)


def ratio(sel, num: str, den: str) -> Optional[float]:
    """Sum of `num` over sum of `den` across the records (None if any
    lacks either, or the denominator is 0)."""
    if not sel or any(num not in r or den not in r for r in sel):
        return None
    d = sum(r[den] for r in sel)
    return sum(r[num] for r in sel) / d if d else None
