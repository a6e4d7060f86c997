"""Pipelined serving (PR 3): the two-stage engine pipeline must be a pure
latency optimization — identical predictions and exit orders to serial
serving on the same request stream, zero steady-state jit compiles (the
batch-row series carry must not add a shape axis that defeats bucketing),
zero steady-state bucket-sized pack allocations, and bounded stats."""
import dataclasses
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.gnn import GNNConfig, init_classifiers, load_dataset
from repro.gnn.nai import NAIConfig, _needed_mask
from repro.gnn.sampler import sample_support
from repro.serving import NAIServingEngine, Request
from repro.serving import engine as engine_mod
from repro.serving.engine import EngineStats, LatencyRing
from repro.gnn.store import as_store


@pytest.fixture(scope="module")
def setup():
    g = load_dataset("pubmed-like", scale=0.02, seed=4)
    # one FB feature block keeps interpret-mode Pallas test-sized
    g = dataclasses.replace(
        g, features=np.ascontiguousarray(g.features[:, :64]))
    cfg = GNNConfig("sgc", 64, g.num_classes, k=2, hidden=32, mlp_layers=2)
    params = {"cls": init_classifiers(cfg, jax.random.PRNGKey(0))}
    nai = NAIConfig(t_s=6.0, t_min=1, t_max=2, batch_size=32)
    return g, cfg, params, nai


@pytest.fixture(scope="module")
def stream(setup):
    """One shared request stream with ragged batch sizes (same bucket)."""
    g = setup[0]
    rng = np.random.default_rng(0)
    return [rng.choice(g.test_idx, size=s, replace=False)
            for s in (32, 30, 32, 28)]


def _serve_stream(engine, stream):
    done = []
    for nodes in stream:
        engine.submit(nodes)
        done += engine.step()
    done += engine.flush()
    return (np.array([r.node_id for r in done]),
            np.array([r.prediction for r in done]),
            np.array([r.exit_order for r in done]))


@pytest.mark.parametrize("impl", ["segment", "block_ell", "fused"])
def test_pipelined_matches_serial(setup, stream, impl):
    """Same stream through a serial (depth-1) and a pipelined (depth-2)
    engine: identical completion order, predictions, and exit orders."""
    g, cfg, params, nai = setup
    serial = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                              mode="compiled", spmm_impl=impl)
    piped = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                             mode="compiled", spmm_impl=impl,
                             pipeline_depth=2)
    ns, ps, os_ = _serve_stream(serial, stream)
    np_, pp, op = _serve_stream(piped, stream)
    np.testing.assert_array_equal(np_, ns)   # FIFO completion preserved
    np.testing.assert_array_equal(pp, ps)
    np.testing.assert_array_equal(op, os_)
    assert piped.stats.served == serial.stats.served == \
        sum(len(b) for b in stream)
    # the pipeline really ran deferred: some step() returned a previous
    # batch, and flush() drained the in-flight tail
    assert not piped._inflight


def test_pipelined_steady_state_zero_compiles(setup, stream):
    """Ragged batch sizes landing in already-seen buckets must be jit
    cache hits AND pooled pack-buffer reuses — the batch-row series carry
    must not introduce a new shape axis that defeats bucketing."""
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode="compiled", spmm_impl="segment",
                           pipeline_depth=2)
    # warm: pass 1 grows the high-water marks (compiles + allocations);
    # pass 2 lets every rotating pool slot converge to the final bucket
    # shapes (a slot allocated before the HWM peaked is replaced once)
    _serve_stream(eng, stream)
    _serve_stream(eng, stream)
    compiles0 = eng.jit_stats["compiles"]
    allocs0 = eng.pack_stats["allocs"]
    _serve_stream(eng, stream)           # steady state
    assert eng.jit_stats["compiles"] == compiles0
    assert eng.jit_stats["hits"] >= len(stream)
    assert eng.pack_stats["allocs"] == allocs0
    assert eng.jit_cache_size() == compiles0


def test_pipeline_depth_validation(setup):
    g, cfg, params, nai = setup
    with pytest.raises(ValueError):
        NAIServingEngine(cfg, nai, params, g, pipeline_depth=0)
    with pytest.raises(ValueError):
        NAIServingEngine(cfg, nai, params, g, mode="host",
                         pipeline_depth=2)


def test_step_on_empty_queue_keeps_pipeline(setup, stream):
    """An empty queue must NOT drain the pipeline: a momentarily empty
    queue under bursty arrivals is exactly when host/device overlap
    matters, and the old `return self.flush()` was a sync barrier that
    silently degraded depth-2 to serial. Batches within the pipeline
    depth stay in flight across empty-queue steps; `flush()` remains the
    explicit drain."""
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode="compiled", spmm_impl="segment",
                           pipeline_depth=2)
    eng.submit(stream[0])
    assert eng.step() == []              # pipe filling
    assert len(eng._inflight) == 1
    assert eng.step() == []              # empty queue: pipeline kept
    assert len(eng._inflight) == 1       # still in flight, no barrier
    eng.submit(stream[1])
    done = eng.step()                    # next batch pushes depth to 2
    assert len(done) == len(stream[0])   # -> oldest finalized (FIFO)
    assert len(eng._inflight) == 1
    done = eng.flush()                   # explicit drain
    assert len(done) == len(stream[1])
    assert not eng._inflight


def _requests(nodes, now=0.0):
    return [Request(int(n), now) for n in nodes]


def test_waiter_delivers_before_the_next_dispatch(setup, stream,
                                                  monkeypatch):
    """At depth 2 batch 0's answers are on its requests while batch 1's
    host stage still runs; `step()` returns them only at batch 1's
    finalize. Batch 1's host stage is held until batch 0's delivery
    future has resolved, and looks at batch 0's requests from inside."""
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode="compiled", spmm_impl="segment",
                           pipeline_depth=2)
    seen = {}

    def slow_sample(*args, **kwargs):
        if eng._inflight:               # batch 1's host stage
            eng._inflight[0].delivery.result(timeout=60)
            seen["t"] = time.perf_counter()
            seen["b0"] = [(r.status, r.done_s, r.batch_id) for r in b0]
        return sample_support(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "sample_support", slow_sample)
    b0, b1 = _requests(stream[0]), _requests(stream[1])
    for r in b0:
        eng.submit_request(r)
    assert eng.step() == []              # batch 0 in flight
    for r in b1:
        eng.submit_request(r)
    done = eng.step()                    # batch 1 dispatched: 0 finalized
    assert done == b0
    # inside batch 1's host stage batch 0 was answered, not yet finalized
    assert [st for st, _, _ in seen["b0"]] == ["completed"] * len(b0)
    assert [bid for _, _, bid in seen["b0"]] == [-1] * len(b0)
    assert [r.done_s for r in b0] == [t for _, t, _ in seen["b0"]]
    assert all(0.0 < r.done_s <= seen["t"] for r in b0)
    assert all(r.prediction >= 0 and r.batch_id == 0 for r in b0)
    rec0 = eng.batch_timings[0]
    assert rec0["early"] == 1 and rec0["lead_s"] > 0.0
    eng._inflight[0].delivery.result(timeout=60)
    assert eng.step() == []              # delivered, not yet finalized
    assert all(r.status == "completed" and r.batch_id == -1 for r in b1)
    assert eng.flush() == b1
    assert all(r.batch_id == 1 for r in b1)


def test_poll_finalizes_a_delivered_batch(setup, stream):
    """On a quiet tick `poll` takes a batch out of the pipeline once the
    waiter has delivered it; its record counts it as early."""
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode="compiled", spmm_impl="segment",
                           pipeline_depth=2)
    b0 = _requests(stream[0])
    for r in b0:
        eng.submit_request(r)
    assert eng.poll(now=100.0) == []     # aged: batch 0 dispatched
    eng._inflight[0].delivery.result(timeout=60)
    assert eng.poll(now=100.0) == b0     # empty queue: opportunistic
    rec = eng.batch_timings[-1]
    assert rec["early"] == 1 and rec["lead_s"] > 0.0
    assert not eng._inflight
    eng.close()


def test_pipelined_matches_serial_under_thread_switching(setup):
    """Depth 2 (with the completion waiter) against depth 1 on one long
    stream, the interpreter switching threads every microsecond:
    bit-identical answers, the same batch grouping and FIFO order;
    serial, no batch is ever delivered ahead of its finalize."""
    g, cfg, params, nai = setup
    rng = np.random.default_rng(11)
    stream = [rng.choice(g.test_idx, size=s, replace=False)
              for s in rng.integers(20, 33, size=12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        out = {}
        for depth in (1, 2):
            eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                                   mode="compiled", spmm_impl="segment",
                                   pipeline_depth=depth)
            done = []
            for nodes in stream:
                eng.submit(nodes)
                done += eng.step()
            done += eng.flush()
            out[depth] = (done, list(eng.batch_timings))
            assert (eng._waiter is None) == (depth == 1)
            eng.close()
    finally:
        sys.setswitchinterval(interval)
    (serial, rs), (piped, rp) = out[1], out[2]
    for key in ("node_id", "prediction", "exit_order", "batch_id"):
        assert ([getattr(r, key) for r in piped]
                == [getattr(r, key) for r in serial]), key
    assert [r.batch_id for r in piped] == sorted(r.batch_id for r in piped)
    assert all(r.status == "completed" for r in piped)
    assert [r["batch"] for r in rp] == [r["batch"] for r in rs]
    assert all(r["early"] == 0 and r["lead_s"] == 0.0 for r in rs)
    assert all(r["early"] in (0, 1) and r["lead_s"] >= 0.0 for r in rp)
    assert all(r["lead_s"] == 0.0 for r in rp if not r["early"])


def test_on_done_hands_answers_over_on_the_waiter(setup, stream,
                                                  monkeypatch):
    """The delivery contract when pipelined: each request's `on_done`
    runs once, on the completion waiter, at `done_s` — while batch 1's
    host stage still holds the engine thread, before `step()` returns
    the request."""
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode="compiled", spmm_impl="segment",
                           pipeline_depth=2)
    calls, seen = [], {}

    def on_done(r):
        calls.append((r, threading.current_thread().name, r.status,
                      r.done_s, time.perf_counter()))

    def slow_sample(*args, **kwargs):
        if eng._inflight:               # batch 1's host stage
            eng._inflight[0].delivery.result(timeout=60)
            seen["calls"] = list(calls)
        return sample_support(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "sample_support", slow_sample)
    b0 = [Request(int(n), 0.0, on_done=on_done) for n in stream[0]]
    for r in b0:
        eng.submit_request(r)
    assert eng.step() == []
    eng.submit(stream[1])
    assert eng.step() == b0
    assert [c[0] for c in seen["calls"]] == b0   # all before step returned
    assert calls == seen["calls"]                # and only once each
    for r, thread, status, done_s, t in calls:
        assert thread.startswith("nai-waiter")
        assert status == "completed" and done_s == r.done_s <= t
    eng.close()


@pytest.mark.parametrize("mode", ["host", "compiled"])
def test_on_done_runs_on_the_caller_when_serial(setup, stream, mode):
    """Serial (depth 1, either mode), the delivery happens inside the
    finalize on the thread that calls `step()`: each `on_done` runs once,
    after `done_s` and before the batch is counted."""
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode=mode, spmm_impl="segment",
                           pipeline_depth=1)
    calls = []

    def on_done(r):
        calls.append((r, threading.current_thread(), r.status,
                      r.batch_id, r.done_s))

    b0 = [Request(int(n), 0.0, on_done=on_done) for n in stream[0]]
    for r in b0:
        eng.submit_request(r)
    assert eng.step() == b0
    assert [c[0] for c in calls] == b0
    for r, thread, status, bid, done_s in calls:
        assert thread is threading.current_thread()
        assert (status, bid, done_s) == ("completed", -1, r.done_s)
        assert r.batch_id == 0
    assert eng._waiter is None
    eng.close()


def test_watchdog_polls_back_off(setup, monkeypatch):
    """Armed, the watchdog's readiness polls start at 100 us and double up
    to `_WATCH_POLL_MAX_S`, so a long device stage wakes the waiter a few
    hundred times a second at most."""
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode="compiled", spmm_impl="segment",
                           pipeline_depth=2, watchdog_s=60.0)

    class ReadyAfter:
        def __init__(self, n):
            self.n = n

        def is_ready(self):
            self.n -= 1
            return self.n < 0

    pauses = []
    monkeypatch.setattr(engine_mod.time, "sleep", pauses.append)
    fl = engine_mod._Inflight([], np.zeros(0, np.int64), 0, ReadyAfter(10),
                              ReadyAfter(0), {},
                              t_submit=time.perf_counter())
    eng._watchdog_sync(fl)
    cap = engine_mod._WATCH_POLL_MAX_S
    assert pauses == pytest.approx(
        [min(1e-4 * 2 ** i, cap) for i in range(10)])
    assert pauses[-1] == cap


def test_armed_watchdog_waiter_matches_unarmed(setup, stream):
    """With the watchdog armed the waiter waits by polling instead of
    blocking: same answers and batches as unarmed, still delivered ahead
    of the finalize."""
    g, cfg, params, nai = setup
    out = {}
    for wd in (None, 60.0):
        eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                               mode="compiled", spmm_impl="segment",
                               pipeline_depth=2, watchdog_s=wd)
        done = []
        for nodes in stream:
            eng.submit(nodes)
            done += eng.step()
            eng._inflight[-1].delivery.result(timeout=60)
        done += eng.flush()
        assert all(r["early"] == 1 for r in eng.batch_timings)
        out[wd] = [(r.node_id, r.prediction, r.exit_order, r.batch_id)
                   for r in done]
        eng.close()
    assert out[60.0] == out[None]


def test_close_stops_the_waiter(setup, stream):
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode="compiled", spmm_impl="segment",
                           pipeline_depth=2)
    assert eng._waiter is None           # started by the first batch
    eng.submit(stream[0])
    eng.step()
    threads = list(eng._waiter._threads)
    assert len(threads) == 1 and threads[0].is_alive()
    eng.close()
    assert eng._waiter is None and not eng._inflight
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    eng.close()                          # idempotent


def test_donation_gating(setup):
    """On CPU (this suite's backend) donation is auto-disabled — XLA CPU
    does not implement buffer donation; an explicit donate=True still
    threads the argnums through for accelerator backends."""
    g, cfg, params, nai = setup
    from repro.gnn.nai import make_compiled_infer
    auto = NAIServingEngine(cfg, nai, params, g, mode="compiled",
                            spmm_impl="segment")
    expected = () if jax.default_backend() == "cpu" else (1, 2, 3)
    assert auto.donate_argnums == expected
    forced = make_compiled_infer(cfg, nai, spmm_impl="segment",
                                 donate=True)
    assert forced._donate_argnums == (1, 2, 3)


# ------------------------------------------------------------ satellites
def test_latency_ring_is_bounded():
    ring = LatencyRing(capacity=100)
    for i in range(1000):
        ring.append(float(i))
    assert len(ring) == 100
    assert ring.total_appended == 1000
    # window holds exactly the most recent 100 samples
    assert sorted(ring.values()) == [float(v) for v in range(900, 1000)]


def test_latency_ring_short_run_matches_list():
    """Below capacity the ring is indistinguishable from the old
    unbounded list: same samples, same percentiles, same summary."""
    rng = np.random.default_rng(3)
    lat = rng.random(50).tolist()
    stats = EngineStats()
    for v in lat:
        stats.latencies.append(v)
    for q in (50, 95, 99):
        assert stats.percentile(q) == pytest.approx(
            float(np.percentile(lat, q)))
    assert stats.summary()["p50_ms"] == pytest.approx(
        1e3 * float(np.percentile(lat, 50)))


def test_engine_stats_served_unaffected_by_ring(setup, stream):
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                           mode="compiled", spmm_impl="segment",
                           latency_window=8)
    _serve_stream(eng, stream)
    total = sum(len(b) for b in stream)
    assert eng.stats.served == total
    assert len(eng.stats.latencies) == 8          # bounded window
    assert eng.stats.latencies.total_appended == total
    assert eng.stats.summary()["p99_ms"] >= 0.0


def _needed_mask_isin_reference(sup, active_batch, remaining_hops):
    """The pre-PR-3 np.isin implementation, kept as the oracle."""
    S = len(sup)
    dist = np.full(S, np.iinfo(np.int32).max, np.int32)
    dist[:sup.n_batch][active_batch] = 0
    frontier = np.flatnonzero(dist == 0)
    for h in range(1, remaining_hops + 1):
        if len(frontier) == 0:
            break
        m = np.isin(sup.dst, frontier)
        cand = sup.src[m]
        new = cand[dist[cand] > h]
        dist[new] = h
        frontier = np.unique(new)
    return dist <= remaining_hops


def test_needed_mask_matches_isin_reference(setup):
    """The O(E) boolean-lookup frontier filter must reproduce the
    np.isin scan bit-for-bit across hop budgets and active patterns."""
    g, cfg, _, nai = setup
    rng = np.random.default_rng(7)
    nodes = rng.choice(g.test_idx, size=32, replace=False)
    sup = sample_support(as_store(g), nodes, 3, cfg.r)
    for frac in (1.0, 0.5, 0.1, 0.0):
        active = rng.random(sup.n_batch) < frac
        for hops in (0, 1, 2, 3):
            got = _needed_mask(sup, active, hops)
            want = _needed_mask_isin_reference(sup, active, hops)
            np.testing.assert_array_equal(got, want, err_msg=f"{frac}/{hops}")
