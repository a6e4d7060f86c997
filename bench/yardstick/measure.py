"""Small pieces every driver uses: host spans, the profiler window, the
device's memory peak and percentiles over every request."""
from __future__ import annotations

import contextlib
import math
import shutil
from pathlib import Path
from typing import Optional, Sequence, Tuple

from .trace import find_xplane, reduce_trace


def span(name: str, on: bool):
    """A host span in the profiler's trace while tracing, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """The profiler around the measured window, reduced once it stops."""

    def __init__(self, out_dir: Path, on: bool):
        self.dir, self.on = Path(out_dir) / "trace", on

    def start(self) -> None:
        if self.on:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))

    def stop(self) -> None:
        if self.on:
            import jax
            jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        """The trace's reduction (None when not tracing); the trace itself
        is deleted."""
        if not self.on:
            return None
        result = reduce_trace(find_xplane(str(self.dir)))
        shutil.rmtree(self.dir, ignore_errors=True)
        return result


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def percentile(values: Sequence[float], q: float) -> Tuple[float, int]:
    """Nearest-rank q-th percentile of `values` (infinities included) and
    how many values lie beyond it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = max(math.ceil(q / 100.0 * len(xs)), 1)
    return xs[rank - 1], len(xs) - rank
