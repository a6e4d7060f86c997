"""Reduction of a JAX profiler trace (`.xplane.pb`) to device metrics.

* Every time is per device: summed over the device planes that ran
  anything and divided by their number, so on four chips a program's
  time per execution is one chip's, not four chips' together.
* Device busy time is the union of the intervals of the operations on a
  device plane's ``XLA Ops`` line, clipped to the measured window.
* Device time is attributed to programs by the ``XLA Modules`` line,
  whose events are named ``<module>(<fingerprint>)``: a jitted Python
  function ``run`` is the module ``jit_run``.
* Collective time is the union of the collective ops (all-to-all,
  all-gather, collective-permute, all-reduce, reduce-scatter, by op
  name); its exposed part is what no other op on that device overlaps.
* Each idle gap of a device inside the window is attributed to the
  benchmark's own host span (a `jax.profiler.TraceAnnotation` whose name
  starts with ``bench.``) that overlaps it most: what the host was doing
  while the device waited.

The window is the host span named ``bench.window`` when the trace has
one, else the stretch from the first to the last device operation.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"all[-_]to[-_]all|all[-_]gather|collective[-_]permute"
                        r"|all[-_]reduce|reduce[-_]scatter")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of `intervals`."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that `busy` (merged) leaves uncovered."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _module_name(event_name: str) -> str:
    """``jit_run(5402...)`` -> ``jit_run``."""
    return event_name.split("(", 1)[0]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def _span_over(gap: Interval, host, starts) -> str:
    """Name of the host span (sorted by start) that overlaps `gap` most."""
    best, most = "no bench span", 0.0
    i = max(bisect.bisect_right(starts, gap[0]) - 1, 0)
    # nested spans may start earlier and still cover the gap
    while i > 0 and host[i - 1][1] > gap[0]:
        i -= 1
    for a, b, name in host[i:]:
        if a >= gap[1]:
            break
        o = _overlap(gap, (a, b))
        if o > most:
            best, most = name, o
    return best


def read_events(path: str):
    """(device planes, host spans) of a trace: per device plane a dict
    with its ``ops`` [(start, end, name)] and ``modules`` [(start, end,
    name)] in nanoseconds; host spans [(start, end, name)]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                name_of = _op_name if key == "ops" else _module_name
                for ev in line.events:
                    dev[key].append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     name_of(ev.name)))
            if dev["ops"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name))
    return devices, spans


def reduce_trace(path: str, *, top: int = 10,
                 window: Optional[Interval] = None) -> Dict:
    """`reduce_events` of the trace at `path`."""
    devices, spans = read_events(path)
    if not devices:
        raise ValueError(f"{path}: no device plane ran an operation")
    return reduce_events(devices, spans, top=top, window=window)


def is_collective(op: str) -> bool:
    """An exchange between devices, by its XLA op name (``all-to-all.3``,
    ``all-gather-start``, ``collective-permute-done.1``, ...)."""
    return COLLECTIVE.search(op) is not None


def _length(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def uncovered(cover: List[Interval], other: List[Interval]) -> float:
    """Length of the merged `cover` that the merged `other` leaves open,
    in one pass over both."""
    total, j = 0.0, 0
    for a, b in cover:
        while j < len(other) and other[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(other) and other[k][0] < b:
            if other[k][0] > t:
                total += other[k][0] - t
            t = max(t, other[k][1])
            k += 1
        if b > t:
            total += b - t
    return total


def reduce_events(devices: List[Dict], spans: List[Tuple[float, float, str]],
                  *, top: int = 10, window: Optional[Interval] = None
                  ) -> Dict:
    """Busy and window seconds, per-module device time, time in
    collectives, the device operations that took most time and the longest
    idle gaps, each gap named by the host span it falls in. `devices` and
    `spans` are as `read_events` gives them.

    Every time is per device, the mean over the device planes that ran
    anything: a module's ``count`` is its executions on one device and its
    ``total_s`` their seconds there, so ``total_s / count`` is one
    execution's time on one device. ``collective_s`` is the time covered
    by collective ops, ``exposed_collective_s`` the part of it in which no
    other op runs on that device."""
    if window is None:
        marks = [s for s in spans if s[2] == WINDOW_SPAN]
        if marks:
            window = (marks[0][0], marks[0][1])
        else:
            window = (min(o[0] for d in devices for o in d["ops"]),
                      max(o[1] for d in devices for o in d["ops"]))
    lo, hi = window
    host = sorted(s for s in spans if s[2] != WINDOW_SPAN)
    host_starts = [s[0] for s in host]
    busy_total = coll_total = exposed_total = 0.0
    modules: Dict[str, Dict[str, float]] = {}
    op_time: Dict[str, float] = collections.Counter()
    idle: List[Tuple[str, float]] = []
    for dev in devices:
        busy = union(clip([(a, b) for a, b, _ in dev["ops"]], lo, hi))
        busy_total += _length(busy)
        split = {True: [], False: []}
        for a, b, name in dev["ops"]:
            split[is_collective(name)].append((a, b))
        coll = union(clip(split[True], lo, hi))
        coll_total += _length(coll)
        exposed_total += uncovered(coll, union(clip(split[False], lo, hi)))
        mods = sorted(dev["modules"])
        for a, b, name in mods:
            if not lo <= (a + b) / 2 < hi:
                continue
            m = modules.setdefault(name, {"count": 0, "total_s": 0.0})
            m["count"] += 1
            m["total_s"] += (b - a) * 1e-9
        starts = [s for s, _, _ in mods]
        for a, b, name in dev["ops"]:
            seg = (max(a, lo), min(b, hi))
            if seg[1] <= seg[0]:
                continue
            i = bisect.bisect_right(starts, a) - 1
            owner = mods[i][2] if i >= 0 and a < mods[i][1] else "other"
            op_time[f"{owner}:{name}"] += (seg[1] - seg[0]) * 1e-9
        for g in gaps(busy, lo, hi):
            idle.append((_span_over(g, host, host_starts),
                         (g[1] - g[0]) * 1e-9))
    n_dev = len(devices)
    idle.sort(key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / n_dev,
        "collective_s": coll_total * 1e-9 / n_dev,
        "exposed_collective_s": exposed_total * 1e-9 / n_dev,
        "devices": n_dev,
        "modules": {k: {"count": v["count"] / n_dev,
                        "total_s": v["total_s"] / n_dev}
                    for k, v in modules.items()},
        "device_ops": [[k, v / n_dev] for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v] for k, v in idle[:top]],
    }
