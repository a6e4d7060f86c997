"""Spans and records of the program's own work.

One tracing system for serving and offline inference. A unit of work (a
serving batch, an offline job) keeps one plain dict, its *record*; a
`span` around one phase of that work both

* writes a host span named ``nai.<name>`` into the JAX profiler's trace
  (`jax.profiler.TraceAnnotation`), on the same clock as the device
  events, carrying its keyword arguments (the batch's sequence number,
  the superstep) as event metadata, and
* adds its wall seconds to ``rec["<last component of name>_s"]``:
  ``span(rec, "serve.sample", batch=7)`` adds to ``rec["sample_s"]``.

A finished record is `publish`ed under its kind (``"serve.batch"``,
``"offline.job"``) into a bounded, process-wide log that an operator's
exporter or a metric reader takes from with `records`.

Spans are per batch, per job or per superstep: never per request, row
or edge, and never inside a jitted function (device-side phases are
`jax.named_scope`s: ``nap.propagate``, ``nap.exit``, ``nap.classify``).
With the profiler off a span costs a few microseconds, so there is no
switch.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Deque, Dict, List

import jax

PREFIX = "nai."
MAX_RECORDS = 1024     # per kind, like the engine's `batch_timings`

_LOG: Dict[str, Deque[dict]] = collections.defaultdict(
    lambda: collections.deque(maxlen=MAX_RECORDS))


def key_of(name: str) -> str:
    """The record key a span named `name` adds to: ``serve.sample`` ->
    ``sample_s``."""
    return name.rsplit(".", 1)[-1] + "_s"


@contextlib.contextmanager
def span(rec: dict, name: str, **args):
    """Trace ``nai.<name>`` and add its elapsed seconds to
    ``rec[key_of(name)]`` (also when the body raises)."""
    key = key_of(name)
    with jax.profiler.TraceAnnotation(PREFIX + name, **args):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            rec[key] = rec.get(key, 0.0) + (time.perf_counter() - t0)


def publish(kind: str, rec: dict) -> None:
    """Append a finished record to the `kind` log (the oldest drop out
    beyond `MAX_RECORDS`)."""
    _LOG[kind].append(rec)


def records(kind: str) -> List[dict]:
    """The `kind` log's records, oldest first (empty if none)."""
    return list(_LOG.get(kind, ()))
