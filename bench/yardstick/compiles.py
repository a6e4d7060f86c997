"""Counts of JAX traces, XLA compiles and persistent-cache loads, read
from `jax.monitoring` events, so a run can show what compiled inside its
measured window."""
from __future__ import annotations

import contextlib
from typing import Dict

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"   # compile or cache load
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        import jax.monitoring as mon
        self.n = {"traces": 0, "backend": 0, "cache_hits": 0}

        def on_duration(event, duration, **kw):
            if event == _TRACE:
                self.n["traces"] += 1
            elif event == _BACKEND:
                self.n["backend"] += 1

        def on_event(event, **kw):
            if event == _CACHE_HIT:
                self.n["cache_hits"] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, int]:
        return {"traces": self.n["traces"],
                "compiles": self.n["backend"] - self.n["cache_hits"],
                "cache_loads": self.n["cache_hits"]}

    @contextlib.contextmanager
    def window(self, out: Dict[str, int]):
        """Fill `out` with what happened inside the block."""
        before = self.snapshot()
        try:
            yield out
        finally:
            after = self.snapshot()
            out.update({k: after[k] - before[k] for k in after})


_COUNTER = None


def counter() -> CompileCounter:
    """The process's one counter (listeners are registered once)."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER
