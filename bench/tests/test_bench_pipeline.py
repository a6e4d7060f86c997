"""The pipeline's per-layer metric `early_share.serve`, read from the
engine's completion-waiter counter: after a tiny serving run with the
trace off it is present, finite and read from the window's records; a
record that does not match the log, the offline record, or records
without the counter (a program without the waiter) read nothing."""
import math

import pytest

from test_bench_spans import _reader, runs  # noqa: F401 — the fixture

NAME = "early_share.serve"


def test_early_share_reads_the_windows_counters(runs):
    from yardstick import spans
    rec, metrics = runs[1]["tiny.serve"]
    assert NAME in metrics, sorted(metrics)
    v = metrics[NAME]["value"]
    assert math.isfinite(v)
    sel = spans.serve_batches(rec)
    assert v == pytest.approx(sum(r["early"] for r in sel) / len(sel))
    # a quiet tick finalizes only batches the waiter has delivered
    assert 0.0 < v <= 1.0
    assert all(r["early"] in (0, 1) for r in sel)
    assert all(r["lead_s"] == 0.0 for r in sel if not r["early"])


def test_early_share_reads_nothing_without_its_records(runs, monkeypatch):
    from yardstick import spans
    root, out = runs
    reader = _reader(root, NAME)
    rec = dict(out["tiny.serve"][0])
    assert reader.read(rec) is not None
    assert reader.read(dict(rec, batches=rec["batches"] + 1)) is None
    assert reader.read(out["tiny.offline"][0]) is None
    bare = [{k: v for k, v in r.items() if k != "early"}
            for r in spans.serve_batches(rec)]
    monkeypatch.setattr(spans, "_log", lambda kind: bare)
    assert reader.read(rec) is None
