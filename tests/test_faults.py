"""Fault injection + failure-domain isolation (PR 8): deterministic
`FaultPlan` schedules, per-batch failure isolation in the engine (host /
device / NaN-guard / watchdog), the graceful-degradation retry on the
reference host path, typed store errors (checksum corruption, bounded
short-read retry), store close()/context-manager lifecycle, and the
front-end circuit breaker's state machine — plus the invariant that
wiring all of it up with an EMPTY plan stays bit-identical to the
pre-fault engine."""
import dataclasses
import os
import threading

import jax
import numpy as np
import pytest

from repro.gnn import GNNConfig, init_classifiers, load_dataset
from repro.gnn.nai import NAIConfig
from repro.gnn.store import (MmapStore, StoreCorruption, StoreIOError,
                             save_graph_store)
from repro.serving import (BreakerConfig, CircuitBreaker, EngineConfig,
                           FaultPlan, FaultSpec, FaultyStore,
                           NAIServingEngine, Request, ServingFrontend,
                           SLOClass)
from repro.serving.faults import NaNGuardError

IMPL = "segment"     # CPU-cheap reference backend for fault tests


@pytest.fixture(scope="module")
def setup():
    g = load_dataset("pubmed-like", scale=0.02, seed=4)
    g = dataclasses.replace(
        g, features=np.ascontiguousarray(g.features[:, :64]))
    cfg = GNNConfig("sgc", 64, g.num_classes, k=2, hidden=32, mlp_layers=2)
    params = {"cls": init_classifiers(cfg, jax.random.PRNGKey(0))}
    nai = NAIConfig(t_s=6.0, t_min=1, t_max=2, batch_size=8)
    return g, cfg, params, nai


def _engine(setup, **over):
    g, cfg, params, nai = setup
    ec = EngineConfig(**{"mode": "compiled", "spmm_impl": IMPL,
                         "pipeline_depth": 2, **over})
    return NAIServingEngine(cfg, nai, params, g, config=ec)


def _serve(eng, nids, bs=8):
    done = []
    for i in range(0, len(nids), bs):
        eng.submit(nids[i:i + bs])
        done += eng.step()
    done += eng.flush()
    return done


def _nodes(setup, n=40, seed=0):
    # unique ids: each node appears in exactly one batch, so clean and
    # faulted runs (same batching) are comparable keyed by node id even
    # though NAI results depend on batch-support composition
    g = setup[0]
    rng = np.random.default_rng(seed)
    return rng.choice(g.test_idx, size=n, replace=False)


# ------------------------------------------------------------ fault plan
def test_fault_plan_deterministic_and_seed_sensitive():
    plan = FaultPlan([FaultSpec("host", rate=0.3),
                      FaultSpec("device", at=(2, 5))], seed=9)
    a, b = plan.injector(), plan.injector()
    hits_a = [(a.fire("host") is not None, a.fire("device") is not None)
              for _ in range(50)]
    hits_b = [(b.fire("host") is not None, b.fire("device") is not None)
              for _ in range(50)]
    assert hits_a == hits_b                      # same plan => same run
    assert any(h for h, _ in hits_a)             # rate spec fired
    assert [d for _, d in hits_a[:7]] == [False, False, True, False,
                                          False, True, False]
    c = FaultPlan([FaultSpec("host", rate=0.3)], seed=10).injector()
    hits_c = [c.fire("host") is not None for _ in range(50)]
    assert hits_c != [h for h, _ in hits_a]      # different seed differs


def test_fault_plan_max_fires_and_validation():
    inj = FaultPlan([FaultSpec("host", rate=1.0, max_fires=2)]).injector()
    assert [inj.fire("host") is not None for _ in range(4)] == \
        [True, True, False, False]
    with pytest.raises(ValueError, match="unknown fault stage"):
        FaultSpec("warp_core", rate=0.1)
    with pytest.raises(ValueError, match="rate"):
        FaultSpec("host", rate=1.5)


# --------------------------------------------- engine batch isolation
def test_host_fault_fails_only_its_batch(setup):
    nids = _nodes(setup)
    clean = _serve(_engine(setup), nids)
    eng = _engine(setup, faults=FaultPlan([FaultSpec("host", at=(1,))]))
    done = _serve(eng, nids)
    assert len(done) == len(nids)
    failed = [r for r in done if r.status == "failed"]
    ok = [r for r in done if r.status == "completed"]
    assert len(failed) == 8 and eng.stats.failed == 8
    assert all("InjectedFault" in r.error for r in failed)
    assert all(r.prediction == -1 for r in failed)
    # the surviving batches match the clean run bit-for-bit (inference
    # is deterministic per node, so node id keys the comparison)
    by_clean = {r.node_id: (r.prediction, r.exit_order) for r in clean}
    for r in ok:
        assert (r.prediction, r.exit_order) == by_clean[r.node_id]


def test_device_fault_fails_only_its_batch(setup):
    eng = _engine(setup, faults=FaultPlan([FaultSpec("device", at=(0,))]))
    done = _serve(eng, _nodes(setup))
    sts = [r.status for r in done]
    assert sts.count("failed") == 8 and sts.count("completed") == 32
    assert eng._inflight == type(eng._inflight)()   # pipeline clean


def test_nan_guard_never_completes_poisoned_batch(setup):
    eng = _engine(setup, faults=FaultPlan([FaultSpec("nan", at=(0, 2))]))
    done = _serve(eng, _nodes(setup))
    failed = [r for r in done if r.status == "failed"]
    assert len(failed) == 16
    assert all("NaNGuardError" in r.error for r in failed)
    # no completed request carries a poisoned result
    for r in done:
        if r.status == "completed":
            assert 0 <= r.prediction < setup[1].num_classes
            assert 1 <= r.exit_order <= setup[3].t_max


def test_guard_tripped_batch_gets_nothing_delivered(setup):
    """Pipelined, the completion waiter hands out no answer of a batch
    whose guards trip: its requests stay untouched until the engine
    thread fails them, and the batches around it complete."""
    eng = _engine(setup, faults=FaultPlan([FaultSpec("nan", at=(1,))]))
    nids = _nodes(setup, n=24)
    batches = [[Request(int(n), 0.0) for n in nids[i:i + 8]]
               for i in (0, 8, 16)]
    done = []
    for b in batches[:2]:
        for r in b:
            eng.submit_request(r)
        done += eng.step()
    assert done == batches[0]            # batch 1 is the one in flight
    with pytest.raises(NaNGuardError):
        eng._inflight[0].delivery.result(timeout=60)
    for r in batches[1]:
        assert (r.status, r.prediction, r.exit_order, r.done_s) == \
            ("pending", -1, -1, -1.0)
    for r in batches[2]:
        eng.submit_request(r)
    done += eng.step()
    done += eng.flush()
    assert done == batches[0] + batches[1] + batches[2]
    assert all(r.status == "failed" and "NaNGuardError" in r.error
               and r.prediction == -1 for r in batches[1])
    assert all(r.status == "completed"
               for r in batches[0] + batches[2])
    assert eng.stats.failed == 8 and eng.stats.served == 16
    assert len(eng.batch_timings) == 2   # a failed batch publishes no record


def test_failed_batch_reaches_on_done_at_finalize(setup):
    """A guard-tripped batch's requests get their `on_done` once, as
    failed, on the engine thread when it fails them — never from the
    waiter; the batches around it are handed over as completed."""
    eng = _engine(setup, faults=FaultPlan([FaultSpec("nan", at=(1,))]))
    calls = []

    def on_done(r):
        calls.append((r, r.status, threading.current_thread().name))

    nids = _nodes(setup, n=24)
    batches = [[Request(int(n), 0.0, on_done=on_done)
                for n in nids[i:i + 8]] for i in (0, 8, 16)]
    done = []
    for b in batches:
        for r in b:
            eng.submit_request(r)
        done += eng.step()
    done += eng.flush()
    assert done == batches[0] + batches[1] + batches[2]
    assert sorted(id(c[0]) for c in calls) == sorted(id(r) for r in done)
    main = threading.current_thread().name
    for r, status, thread in calls:
        if r in batches[1]:
            assert (status, thread) == ("failed", main)
        else:
            assert status == "completed" and thread.startswith("nai-waiter")


def test_raising_on_done_is_kept_on_its_request(setup):
    """A client callback that raises does not fail the batch: its error is
    kept on its own request, and the other callbacks still run."""
    eng = _engine(setup)
    called = []

    def on_done(r):
        called.append(r)
        if len(called) == 3:
            raise RuntimeError("client bug")

    reqs = [Request(int(n), 0.0, on_done=on_done)
            for n in _nodes(setup, n=8)]
    for r in reqs:
        eng.submit_request(r)
    done = eng.step() + eng.flush()
    assert done == reqs and called == reqs
    assert all(r.status == "completed" for r in reqs)
    assert reqs[2].error == "on_done: RuntimeError: client bug"
    assert all(r.error == "" for r in reqs[:2] + reqs[3:])
    assert eng.stats.served == 8 and eng.stats.failed == 0


def test_failing_cache_fill_still_returns_the_batch(setup, monkeypatch):
    """The cache fill runs after the answers are out: one that raises
    skips the insert and is counted, and the batch is still counted,
    recorded and returned (every accepted request ends exactly once)."""
    eng = _engine(setup, cache_nodes=4096)

    def broken_fill(*args, **kwargs):
        raise MemoryError("cache fill")

    monkeypatch.setattr(eng.cache, "fill", broken_fill)
    nids = _nodes(setup, n=16)
    done = _serve(eng, nids)
    assert [r.node_id for r in done] == [int(n) for n in nids]
    assert all(r.status == "completed" for r in done)
    assert [r.batch_id for r in done] == [0] * 8 + [1] * 8
    assert eng.stats.fill_errors == 2
    assert eng.stats.served == 16 and eng.stats.failed == 0
    assert len(eng.batch_timings) == 2
    assert len(eng.cache) == 0


def test_poll_finalizes_host_materialized_results(setup):
    """Open-loop regression (found by chaos_bench): a batch whose
    in-flight results are plain host arrays (no `is_ready` — e.g. a
    NaN-poisoned batch) must still be finalized by poll() while it sits
    BELOW pipeline_depth; treating missing `is_ready` as not-ready
    parks it there forever and wedges open-loop serving until flush."""
    eng = _engine(setup, retry_failed=True,
                  faults=FaultPlan([FaultSpec("nan", at=(0,))]))
    eng.submit(_nodes(setup, n=8))
    done = eng.poll()                    # dispatches the poisoned batch
    for _ in range(50):
        if done:
            break
        done += eng.poll()               # empty queue: opportunistic path
    assert len(done) == 8, "poll() never finalized the in-flight batch"
    assert all(r.status == "completed" and r.retried for r in done)
    assert not eng._inflight


def test_retry_recovers_on_reference_path_bit_identical(setup):
    nids = _nodes(setup)
    clean = _serve(_engine(setup), nids)
    eng = _engine(setup, retry_failed=True,
                  faults=FaultPlan([FaultSpec("nan", at=(1,)),
                                    FaultSpec("device", at=(3,))]))
    done = _serve(eng, nids)
    assert all(r.status == "completed" for r in done)
    assert eng.stats.retried == 16 and eng.stats.failed == 0
    assert sum(r.retried for r in done) == 16
    # the host reference path gives the same answers as the compiled one
    # (keyed by node: a dispatch-time retry completes ahead of the
    # in-flight batch before it, so terminal order differs)
    by_clean = {r.node_id: (r.prediction, r.exit_order) for r in clean}
    for r in done:
        assert (r.prediction, r.exit_order) == by_clean[r.node_id]


def test_watchdog_fails_hung_batch_and_rearms(setup):
    eng = _engine(setup, watchdog_s=0.2,
                  faults=FaultPlan([FaultSpec("hang", at=(1,))]))
    done = _serve(eng, _nodes(setup))
    failed = [r for r in done if r.status == "failed"]
    assert len(failed) == 8
    assert all("WatchdogTimeout" in r.error for r in failed)
    # the pipeline re-armed: batches AFTER the hung one completed
    assert [r.status for r in done].count("completed") == 32
    assert not eng._inflight


def test_fault_free_wiring_bit_identical(setup):
    """The whole isolation stack armed but idle — empty plan, watchdog,
    NaN guard, retry enabled — must not perturb results or stats."""
    nids = _nodes(setup, n=48, seed=3)
    plain = _engine(setup)
    wired = _engine(setup, faults=FaultPlan(), watchdog_s=5.0,
                    retry_failed=True, nan_guard=True)
    d0, d1 = _serve(plain, nids), _serve(wired, nids)
    assert [r.prediction for r in d1] == [r.prediction for r in d0]
    assert [r.exit_order for r in d1] == [r.exit_order for r in d0]
    assert wired.stats.failed == 0 and wired.stats.retried == 0
    assert all(r.status == "completed" for r in d1)
    assert wired.jit_stats == plain.jit_stats
    assert wired.pack_stats == plain.pack_stats


# ------------------------------------------------- submit validation
def test_submit_rejects_out_of_range_ids_atomically(setup):
    g = setup[0]
    eng = _engine(setup)
    for bad in (-1, g.n, g.n + 7):
        with pytest.raises(ValueError, match="out of range"):
            eng.submit([0, 1, bad])
    assert not eng.queue            # nothing half-submitted
    from repro.serving.engine import Request
    with pytest.raises(ValueError, match="out of range"):
        eng.submit_request(Request(g.n, 0.0))


def test_frontend_submit_rejects_bad_id_without_accounting(setup):
    g, cfg, params, nai = setup
    fe = ServingFrontend(cfg, params, g,
                         [SLOClass("gold", nai, deadline_s=1.0,
                                   max_wait_s=0.01)],
                         mode="host")
    with pytest.raises(ValueError, match="out of range"):
        fe.submit(g.n, "gold", now=0.0)
    assert fe.stats["gold"].offered == 0    # caller error, not shed
    assert fe.submit(int(g.test_idx[0]), "gold", now=0.0) is not None
    fe.flush()
    assert fe.stats["gold"].completed == 1


# ------------------------------------------------------- faulty store
def test_faulty_store_raises_typed_errors_per_plan(setup):
    g = setup[0]
    inj = FaultPlan([FaultSpec("store_read", at=(1,))], seed=2).injector()
    from repro.gnn.store import as_store
    fs = FaultyStore(as_store(g), inj)
    nodes = np.arange(4)
    ok = fs.gather_features(nodes)                    # event 0: clean
    assert np.array_equal(ok, as_store(g).gather_features(nodes))
    with pytest.raises(StoreIOError, match="injected read failure"):
        fs.gather_features(nodes)                     # event 1: fires


def test_store_faults_fail_batches_not_engine(setup):
    g, cfg, params, nai = setup
    from repro.gnn.store import as_store
    plan = FaultPlan([FaultSpec("store_read", at=(1, 4))], seed=6)
    fs = FaultyStore(as_store(g), plan.injector())
    ec = EngineConfig(mode="compiled", spmm_impl=IMPL, pipeline_depth=2)
    eng = NAIServingEngine(cfg, nai, params, fs, config=ec)
    done = _serve(eng, _nodes(setup, n=64, seed=5))
    assert len(done) == 64
    failed = [r for r in done if r.status == "failed"]
    assert failed and all("StoreIOError" in r.error for r in failed)
    assert any(r.status == "completed" for r in done)
    assert eng.stats.failed == len(failed)


# ------------------------------------------- mmap store: io + lifecycle
@pytest.fixture()
def store_dir(setup, tmp_path):
    d = str(tmp_path / "store")
    save_graph_store(setup[0], d)
    return d


def test_checksums_written_and_verified(store_dir):
    with MmapStore(store_dir, verify=True) as ms:
        assert set(ms.verify()) == {"row_ptr", "col_idx", "features",
                                    "degrees", "labels"}


def test_corruption_detected_by_checksum(store_dir):
    p = os.path.join(store_dir, "features.npy")
    size = os.path.getsize(p)
    with open(p, "r+b") as fh:
        fh.seek(size - 5)
        b = fh.read(1)
        fh.seek(size - 5)
        fh.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(StoreCorruption, match="checksum mismatch"):
        MmapStore(store_dir, verify=True)
    ms = MmapStore(store_dir)                  # lazy open still allowed
    with pytest.raises(StoreCorruption):
        ms.verify(("features",))
    ms.close()


def test_truncated_array_detected_by_shape_check(store_dir, setup):
    g = setup[0]
    np.save(os.path.join(store_dir, "degrees.npy"),
            np.asarray(g.degrees)[: g.n // 2])
    ms = MmapStore(store_dir)
    with pytest.raises(StoreCorruption, match="shape"):
        _ = ms.degrees
    ms.close()


def test_short_read_retries_then_raises(store_dir, monkeypatch):
    ms = MmapStore(store_dir, io_retries=2, io_backoff_s=1e-4)
    nodes = np.array([3, 9, 10, 11, 50])
    want = np.load(os.path.join(store_dir, "features.npy"))[nodes]
    real = os.preadv
    calls = {"n": 0}

    def flaky(fd, bufs, off):                  # short once, then real
        calls["n"] += 1
        if calls["n"] % 2 == 1:
            short = [memoryview(bufs[0])[: len(bufs[0]) // 2]]
            return real(fd, short, off)
        return real(fd, bufs, off)

    monkeypatch.setattr(os, "preadv", flaky)
    out = ms.gather_features(nodes)
    assert np.array_equal(out, want)           # retry completed the read

    calls["n"] = 0
    monkeypatch.setattr(
        os, "preadv", lambda fd, bufs, off: 0)  # never progresses
    with pytest.raises(StoreIOError, match="short read"):
        ms.gather_features(nodes)
    monkeypatch.undo()
    ms.close()


def test_mmap_store_close_and_context_manager(store_dir):
    with MmapStore(store_dir) as ms:
        ms.gather_features(np.array([0, 1, 2]))
        assert ms._feat_fd >= 0
        fd = ms._feat_fd
    assert ms._feat_fd == -1
    with pytest.raises(OSError):
        os.fstat(fd)                           # fd really closed
    ms.close()                                 # idempotent
    with pytest.raises(ValueError, match="closed"):
        ms.gather_features(np.array([0]))
    with pytest.raises(ValueError, match="closed"):
        _ = ms.row_ptr


def test_engine_close_releases_store(setup, store_dir):
    g, cfg, params, nai = setup
    ms = MmapStore(store_dir)
    ec = EngineConfig(mode="compiled", spmm_impl=IMPL, pipeline_depth=2)
    eng = NAIServingEngine(cfg, nai, params, ms, config=ec)
    done = _serve(eng, _nodes(setup, n=16, seed=7))
    assert all(r.status == "completed" for r in done)
    eng.close()
    assert ms._feat_fd == -1
    eng.close()                                # idempotent


# -------------------------------------------------- circuit breaker
def test_breaker_state_machine_on_virtual_clock():
    br = CircuitBreaker(BreakerConfig(window=8, trip_frac=0.5,
                                      min_events=4, cooldown_s=1.0,
                                      probes=2))
    t = 0.0
    assert br.route(t) == "native"
    for _ in range(4):                         # sustained failures: trip
        br.on_terminal(True, False, t)
    assert br.state == "open" and br.trips == 1
    assert br.route(t + 0.5) == "reroute"      # still cooling down
    assert br.route(t + 1.1) == "probe"        # half_open: probe 1
    assert br.route(t + 1.1) == "probe"        # probe 2
    assert br.route(t + 1.1) == "reroute"      # probe budget spent
    br.on_terminal(False, True, t + 1.2)       # probe ok
    br.on_terminal(False, True, t + 1.2)       # second ok: close
    assert br.state == "closed"
    # trip again, then a failing probe re-opens with a fresh cooldown
    for _ in range(4):
        br.on_terminal(True, False, t + 2.0)
    assert br.state == "open"
    assert br.route(t + 3.5) == "probe"
    br.on_terminal(True, True, t + 3.6)
    assert br.state == "open" and br.trips == 3
    assert br.route(t + 3.7) == "reroute"      # cooldown restarted
    assert [(a, b) for _, a, b in br.transitions] == [
        ("closed", "open"), ("open", "half_open"),
        ("half_open", "closed"), ("closed", "open"),
        ("open", "half_open"), ("half_open", "open")]


def test_breaker_non_closed_ignores_stale_outcomes():
    br = CircuitBreaker(BreakerConfig(window=8, trip_frac=0.5,
                                      min_events=4, cooldown_s=1.0,
                                      probes=1))
    for _ in range(4):
        br.on_terminal(True, False, 0.0)
    assert br.state == "open"
    # pre-trip traffic draining as failures must not re-trip/extend
    br.on_terminal(True, False, 0.5)
    assert br.trips == 1
    assert br.route(1.5) == "probe"
    br.on_terminal(True, False, 1.6)           # non-probe while half_open
    assert br.state == "half_open"
    br.on_terminal(False, True, 1.7)
    assert br.state == "closed"


def test_frontend_demotes_gold_and_recovers(setup):
    g, cfg, params, nai = setup
    classes = [
        SLOClass("gold", nai, deadline_s=10.0, max_wait_s=0.001,
                 queue_depth=64, demote_to="best_effort",
                 engine=EngineConfig(
                     mode="compiled", spmm_impl=IMPL,
                     faults=FaultPlan([FaultSpec("device",
                                                 at=tuple(range(0, 3)))],
                                      seed=3))),
        SLOClass("best_effort", dataclasses.replace(nai, t_max=nai.t_min),
                 deadline_s=10.0, max_wait_s=0.001, queue_depth=64),
    ]
    br = BreakerConfig(window=8, trip_frac=0.5, min_events=8,
                       cooldown_s=0.05, probes=1, count_misses=False)
    fe = ServingFrontend(cfg, params, g, classes, breaker=br,
                         mode="compiled", spmm_impl=IMPL)
    rng = np.random.default_rng(11)
    import time as _t
    term = []
    for _ in range(40):
        for nid in rng.choice(g.test_idx, size=8, replace=True):
            fe.submit(int(nid), "gold")
        guard = _t.perf_counter() + 1.0
        while fe.pending() and _t.perf_counter() < guard:
            term += fe.step()
        if fe.breakers["gold"].state == "closed" and \
                fe.stats["gold"].degraded:
            break
    term += fe.flush()
    st = fe.stats["gold"]
    brk = fe.breakers["gold"]
    assert brk.trips >= 1
    assert st.degraded > 0                      # demotion happened
    assert brk.state == "closed"                # and it recovered
    assert st.offered == st.accepted + st.rejected
    assert st.accepted == st.completed + st.failed
    assert fe.pending() == 0
    fe.close()
