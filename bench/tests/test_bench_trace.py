"""The trace reduction on a small trace recorded on a TPU v5e: three
steps of a jitted ``run`` (three segment-sum propagation steps) and a
jitted ``classify``, each inside a ``bench.step`` host span, with a
``bench.sleep`` span of 20 ms between steps; and synthetic event lists
of one and four devices, read per device."""
from pathlib import Path

import numpy as np
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


MS = 1_000_000   # ns


def _device(i, shift=0.0):
    """One device's events, in ms: two executions of `jit_step`, 10 ms
    apart, each 4 ms of ops, with an exchange that compute overlaps by
    0.5 ms and one that nothing overlaps."""
    ops, mods = [], []
    for t0 in (0.0, 10.0):
        t0 += shift
        for a, b, name in ((0.0, 2.0, "fusion.1"),
                           (1.5, 3.0, "collective-permute-start.1"),
                           (3.0, 3.5, "fusion.2"),
                           (3.5, 4.0, "all-reduce.2")):
            ops.append(((t0 + a) * MS, (t0 + b) * MS, name))
        mods.append((t0 * MS, (t0 + 4.0) * MS, "jit_step"))
    return {"name": f"/device:TPU:{i}", "ops": ops, "modules": mods}


SPANS = [(0.0, 20 * MS, trace.WINDOW_SPAN), (4 * MS, 10 * MS, "bench.sleep")]


@pytest.mark.parametrize("chips", [1, 4])
def test_every_time_is_per_device(chips):
    r = trace.reduce_events([_device(i) for i in range(chips)], SPANS)
    assert r["devices"] == chips
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.008)
    m = r["modules"]["jit_step"]
    assert m["count"] == 2 and m["total_s"] == pytest.approx(0.008)
    # the collective ops cover 1.5 + 0.5 ms an execution; compute covers
    # 0.5 ms of the first, so 1.0 + 0.5 ms are exposed
    assert r["collective_s"] == pytest.approx(0.004)
    assert r["exposed_collective_s"] == pytest.approx(0.003)
    ops = dict(r["device_ops"])
    assert ops["jit_step:fusion.1"] == pytest.approx(0.004)
    assert r["idle_gaps"][0][0] == "bench.sleep"


def test_module_time_is_one_execution_on_one_device():
    from yardstick.readers import per_call_s
    # devices that start apart still run each execution for 4 ms
    r = trace.reduce_events([_device(i, shift=0.3 * i) for i in range(4)],
                            SPANS)
    t, count = per_call_s({"trace": r}, "jit_step")
    assert t == pytest.approx(0.004) and count == 2


def test_roofline_and_mfu_take_every_chip_s_peak():
    from yardstick import readers, work
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    w = work.nai_work(rows=1000, edges=20000, width=64, steps=2,
                      orders=np.full(10, 2), t_min=1, classes=4)
    flops, nbytes = work.total(w)
    least = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    for chips in (1, 4):
        r = trace.reduce_events([_device(i) for i in range(chips)], SPANS)
        rec = {"trace": r, "work": [w], "peak": peak, "chips": chips}
        assert readers.roofline_pct(rec, "jit_step", ("prop", "dist", "cls")
                                    ) == pytest.approx(
            100 * least / chips / 0.004)
        assert readers.mfu_pct(rec, 2 * flops) == pytest.approx(
            100 * 2 * flops / (0.020 * chips * peak["flops_per_s"]))


def test_exposed_time_by_hand():
    assert trace.uncovered([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]
                           ) == 2 + 4 + 3 + 4
    assert trace.uncovered([(0, 10)], []) == 10
    assert trace.uncovered([], [(0, 5)]) == 0
    assert trace.is_collective("all-to-all.3")
    assert trace.is_collective("all-gather-done")
    assert trace.is_collective("reduce-scatter.1")
    assert not trace.is_collective("fusion.12")


def test_one_chip_trace_reads_as_before():
    r = trace.reduce_trace(str(SMALL))
    devices, spans = trace.read_events(str(SMALL))
    assert r == trace.reduce_events(devices, spans)
    assert r["collective_s"] == 0.0 and r["exposed_collective_s"] == 0.0
    assert r["modules"]["jit_run"]["count"] == 3
