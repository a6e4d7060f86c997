"""The trace reduction on a small trace recorded on a TPU v5e: three
steps of a jitted ``run`` (three segment-sum propagation steps) and a
jitted ``classify``, each inside a ``bench.step`` host span, with a
``bench.sleep`` span of 20 ms between steps."""
from pathlib import Path

import pytest

from yardstick import trace

SMALL = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert busy == [(0, 3), (5, 9), (10, 11)]
    assert trace.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 10), (11, 12)]
    assert trace.clip(busy, 2, 6) == [(2, 3), (5, 6)]


def test_busy_is_the_union_of_device_ops():
    devices, spans = trace.read_events(str(SMALL))
    assert len(devices) == 1 and devices[0]["name"] == "/device:TPU:0"
    ops = [(a, b) for a, b, _ in devices[0]["ops"]]
    lo, hi = min(a for a, _ in ops), max(b for _, b in ops)
    # busy by brute force over a 1 ns grid of the merged intervals
    covered = sum(b - a for a, b in trace.union(ops))
    r = trace.reduce_trace(str(SMALL))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(covered * 1e-9)
    # the three propagation steps dominate: ~2 ms each run, three runs
    assert 0.005 < r["busy_s"] < 0.007
    assert 0.1 < r["busy_s"] / r["window_s"] < 0.2


def test_device_time_is_attributed_to_modules():
    r = trace.reduce_trace(str(SMALL))
    assert r["modules"]["jit_run"]["count"] == 3
    assert r["modules"]["jit_classify"]["count"] == 3
    assert r["modules"]["jit_run"]["total_s"] == pytest.approx(
        3 * 1.983e-3, rel=0.01)
    names = [n for n, _ in r["device_ops"]]
    assert names[0].startswith("jit_run:fusion.")
    assert any(n.startswith("jit_classify:") for n in names)
    assert len(r["device_ops"]) <= 10


def test_idle_gaps_are_named_by_host_spans():
    r = trace.reduce_trace(str(SMALL))
    (first, s1), (second, s2) = r["idle_gaps"][:2]
    # between steps the host slept: the device's two long gaps fall there
    assert first == second == "bench.sleep"
    assert s1 > 0.02 and s2 > 0.02
    assert all(s <= s2 for _, s in r["idle_gaps"][2:])
    assert len(r["idle_gaps"]) <= 10


def test_explicit_window_clips():
    devices, _ = trace.read_events(str(SMALL))
    a, b, _ = sorted(devices[0]["modules"])[0]
    r = trace.reduce_trace(str(SMALL), window=(a, b))
    assert r["modules"]["jit_run"]["count"] == 1
    assert r["busy_s"] <= r["window_s"]
