"""Deadline-aware serving front-end (PR 6): SLO-class routing into
per-class engines, bounded-lane backpressure, the fixed batch former
(close on size OR age, unconditionally), goodput accounting, and the
bit-parity invariants — front-end == direct engine serving, pipelined ==
serial — on a deterministic virtual-clock request stream."""
import dataclasses
import time
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.gnn import GNNConfig, init_classifiers, load_dataset
from repro.gnn.nai import NAIConfig
from repro.serving import (NAIServingEngine, ServingFrontend, SLOClass,
                           default_slo_classes)


@pytest.fixture(scope="module")
def setup():
    g = load_dataset("pubmed-like", scale=0.02, seed=4)
    g = dataclasses.replace(
        g, features=np.ascontiguousarray(g.features[:, :64]))
    cfg = GNNConfig("sgc", 64, g.num_classes, k=2, hidden=32, mlp_layers=2)
    params = {"cls": init_classifiers(cfg, jax.random.PRNGKey(0))}
    nai = NAIConfig(t_s=6.0, t_min=1, t_max=2, batch_size=8)
    return g, cfg, params, nai


def _two_classes(nai, queue_depth=64):
    return [
        SLOClass("gold", nai, deadline_s=10.0, max_wait_s=0.02,
                 queue_depth=queue_depth),
        SLOClass("best_effort", dataclasses.replace(nai, t_max=nai.t_min),
                 deadline_s=10.0, max_wait_s=0.01,
                 queue_depth=queue_depth),
    ]


def _bursty_events(g, nai, n_bursts=5, seed=0):
    """Deterministic virtual-time arrivals: bursts bigger than a batch
    (size closes) separated by lulls longer than max_wait (age closes)."""
    rng = np.random.default_rng(seed)
    events, t = [], 0.0
    for _ in range(n_bursts):
        size = int(rng.integers(3, 2 * nai.batch_size + 1))
        for nid in rng.choice(g.test_idx, size=size, replace=True):
            cls = "gold" if rng.random() < 0.5 else "best_effort"
            events.append((t, cls, int(nid)))
            t += 1e-4
        t += 1.0
    return events


def _replay(fe, events):
    reqs = []
    for t, cls, nid in events:
        r = fe.submit(nid, cls, now=t)
        assert r is not None
        reqs.append(r)
        fe.step(now=t)
    fe.step(now=events[-1][0] + 100.0)   # age out the final stragglers
    fe.flush()
    return reqs


# -------------------------------------------------- NAIConfig validation
def test_nai_config_validation():
    """The front-end builds per-class configs programmatically, so a
    nonsensical combination must fail at construction — not serve -1
    predictions or never-exiting loops in production."""
    NAIConfig(t_s=1.0, t_min=1, t_max=2, batch_size=4)   # valid
    with pytest.raises(ValueError, match="t_min"):
        NAIConfig(t_s=1.0, t_min=0, t_max=2, batch_size=4)
    with pytest.raises(ValueError, match="t_min"):
        NAIConfig(t_s=1.0, t_min=3, t_max=2, batch_size=4)
    with pytest.raises(ValueError, match="t_s"):
        NAIConfig(t_s=-0.5, t_min=1, t_max=2, batch_size=4)
    with pytest.raises(ValueError, match="batch_size"):
        NAIConfig(t_s=1.0, t_min=1, t_max=2, batch_size=0)


def test_slo_class_validation(setup):
    nai = setup[3]
    with pytest.raises(ValueError):
        SLOClass("", nai, deadline_s=1.0, max_wait_s=0.01)
    with pytest.raises(ValueError):
        SLOClass("x", nai, deadline_s=0.0, max_wait_s=0.01)
    with pytest.raises(ValueError):
        SLOClass("x", nai, deadline_s=1.0, max_wait_s=-1.0)
    with pytest.raises(ValueError):
        SLOClass("x", nai, deadline_s=1.0, max_wait_s=0.01, queue_depth=0)


def test_default_slo_classes_tiers(setup):
    nai = setup[3]
    gold, be = default_slo_classes(nai)
    assert gold.nai.t_max == nai.t_max          # accuracy tier
    assert be.nai.t_max == nai.t_min            # cheapest compiled shape
    assert be.deadline_s < gold.deadline_s


# -------------------------------------------------------- batch former
def test_form_batch_waits_young_partial(setup):
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=0.05)
    eng.submit([1, 2, 3], now=100.0)
    assert eng.form_batch(now=100.01) == []      # young partial: wait
    assert len(eng.queue) == 3


def test_form_batch_closes_on_size(setup):
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=1e9)
    eng.submit(np.arange(nai.batch_size + 3), now=100.0)
    batch = eng.form_batch(now=100.0)            # full: close immediately
    assert len(batch) == nai.batch_size
    assert len(eng.queue) == 3


@pytest.mark.parametrize("queued", [1, 2, 3, 5])
def test_form_batch_aged_takes_everything(setup, queued):
    """The deadline-inversion fix: once the oldest request has aged past
    max_wait the batch closes UNCONDITIONALLY with everything queued —
    no minimum-fill guard, no degeneration to size-1 batches (the old
    former required batch_size // 4 post-deadline fill, which held
    batches hostage and collapsed to singletons for batch_size <= 3)."""
    g, cfg, params, nai = setup
    small = dataclasses.replace(nai, batch_size=3)
    eng = NAIServingEngine(cfg, small, params, g, max_wait_s=0.05)
    eng.submit(np.arange(queued), now=100.0)
    if queued < small.batch_size:
        assert eng.form_batch(now=100.01) == []  # young partial: wait
    batch = eng.form_batch(now=100.06)           # aged: close it all
    assert len(batch) == min(queued, small.batch_size)
    assert len(eng.queue) == max(0, queued - small.batch_size)


def test_form_batch_force_and_empty(setup):
    g, cfg, params, nai = setup
    eng = NAIServingEngine(cfg, nai, params, g, max_wait_s=1e9)
    assert eng.form_batch(force=True) == []
    eng.submit([7], now=100.0)
    batch = eng.form_batch(force=True)           # closed-loop path
    assert [r.node_id for r in batch] == [7]


# ------------------------------------------------- routing/backpressure
def test_routing_and_backpressure(setup):
    g, cfg, params, nai = setup
    fe = ServingFrontend(cfg, params, g, _two_classes(nai, queue_depth=5),
                         mode="host")
    for i in range(8):
        fe.submit(int(g.test_idx[i]), "gold", now=0.0)
    st = fe.stats["gold"]
    assert (st.offered, st.accepted, st.rejected) == (8, 5, 3)
    assert len(fe.engines["gold"].queue) == 5
    assert len(fe.engines["best_effort"].queue) == 0
    assert fe.stats["best_effort"].offered == 0
    with pytest.raises(KeyError):
        fe.submit(0, "platinum", now=0.0)
    fe.flush()                                   # free the gold lane
    # default class is the first in the sequence
    r = fe.submit(int(g.test_idx[0]), now=0.0)
    assert r.slo_class == "gold"


def test_frontend_requires_classes(setup):
    g, cfg, params, nai = setup
    with pytest.raises(ValueError):
        ServingFrontend(cfg, params, g, [], mode="host")
    with pytest.raises(ValueError):
        ServingFrontend(cfg, params, g,
                        _two_classes(nai) + _two_classes(nai),
                        mode="host")


# ------------------------------------------------------ parity + steady
def test_pipelined_matches_serial_with_zero_steady_state(setup):
    """The tentpole invariants on one bursty virtual-clock stream: a
    depth-2 front-end serves bit-identically to a depth-1 front-end,
    and after warm-up a replay of the same stream compiles nothing and
    allocates no bucket-sized pack buffers in either class engine."""
    g, cfg, params, nai = setup
    events = _bursty_events(g, nai)
    results = {}
    for depth in (1, 2):
        fe = ServingFrontend(cfg, params, g, _two_classes(nai),
                             mode="compiled", spmm_impl="segment",
                             pipeline_depth=depth)
        for _ in range(depth + 2):               # warm HWMs + pack pool
            _replay(fe, events)
        base = {n: (e.jit_stats["compiles"], e.pack_stats["allocs"])
                for n, e in fe.engines.items()}
        reqs = _replay(fe, events)
        assert all(r.prediction >= 0 for r in reqs)
        for name, eng in fe.engines.items():
            assert eng.jit_stats["compiles"] == base[name][0], name
            assert eng.pack_stats["allocs"] == base[name][1], name
        results[depth] = reqs
    for a, b in zip(results[1], results[2]):
        assert (a.node_id, a.slo_class) == (b.node_id, b.slo_class)
        assert a.prediction == b.prediction
        assert a.exit_order == b.exit_order


def test_frontend_matches_direct_engine(setup):
    """Front-end-served predictions are bit-identical to replaying the
    same batches (regrouped via Request.batch_id) through direct
    engines: the front-end adds routing and deadlines, never numerics."""
    g, cfg, params, nai = setup
    classes = _two_classes(nai)
    fe = ServingFrontend(cfg, params, g, classes, mode="compiled",
                         spmm_impl="segment", pipeline_depth=2)
    reqs = _replay(fe, _bursty_events(g, nai, seed=3))
    groups = defaultdict(list)
    for r in reqs:
        assert r.batch_id >= 0
        groups[(r.slo_class, r.batch_id)].append(r)
    for c in classes:
        eng = NAIServingEngine(cfg, c.nai, params, g, max_wait_s=10.0,
                               mode="compiled", spmm_impl="segment")
        for key in sorted(k for k in groups if k[0] == c.name):
            orig = groups[key]
            eng.submit([r.node_id for r in orig])
            replay = eng.step()
            assert len(replay) == len(orig)
            for a, b in zip(orig, replay):
                assert a.node_id == b.node_id
                assert a.prediction == b.prediction
                assert a.exit_order == b.exit_order


# ------------------------------------------------------------- goodput
def test_goodput_accounting(setup):
    """Real-clock run: a generous budget lands inside the deadline, a
    zero budget cannot — and both are counted in the right bucket."""
    g, cfg, params, nai = setup
    fe = ServingFrontend(cfg, params, g, _two_classes(nai), mode="host")
    hit = fe.submit(int(g.test_idx[0]), "gold", budget_s=1e6)
    miss = fe.submit(int(g.test_idx[1]), "gold", budget_s=0.0)
    fe.flush()                         # drain the partial batch
    assert hit.within_deadline
    assert not miss.within_deadline
    st = fe.stats["gold"]
    assert st.completed == 2
    assert st.deadline_hits == 1
    assert st.deadline_misses == 1
    s = fe.summary()["gold"]
    assert s["goodput_frac"] == pytest.approx(0.5)
    assert s["batches"] >= 1


def test_submit_on_done_is_called_at_the_scored_instant(setup):
    """`submit(on_done=...)` reaches the engine's delivery: each accepted
    request's callback runs once with it terminal, and its deadline is
    scored against the `done_s` stamped just before that call; a shed
    request gets no call."""
    g, cfg, params, nai = setup
    fe = ServingFrontend(cfg, params, g, _two_classes(nai, queue_depth=4),
                         mode="compiled", pipeline_depth=2,
                         spmm_impl="segment")
    calls = []

    def on_done(r):
        calls.append((r, r.status, r.done_s, time.perf_counter()))

    reqs = [fe.submit(int(n), "gold", budget_s=1e6, on_done=on_done)
            for n in g.test_idx[:5]]
    assert reqs[4] is None                         # shed: lane is full
    fe.flush()
    assert [c[0] for c in calls] == reqs[:4]
    for r, status, done_s, t in calls:
        assert status == "completed" and done_s == r.done_s <= t
        assert r.on_done is on_done and r.within_deadline
    assert fe.stats["gold"].deadline_hits == 4
    fe.close()


def test_pending_and_reset(setup):
    g, cfg, params, nai = setup
    fe = ServingFrontend(cfg, params, g, _two_classes(nai), mode="host")
    fe.submit(int(g.test_idx[0]), "gold", now=0.0)
    fe.submit(int(g.test_idx[1]), "best_effort", now=0.0)
    assert fe.pending() == 2
    fe.flush()
    assert fe.pending() == 0
    fe.reset_stats()
    assert fe.stats["gold"].completed == 0
    assert fe.summary()["gold"]["batches"] == 0


# -------------------------------------------------------- conservation
def _conservation_run(setup, seed, n_bursts, queue_depth):
    """Drive bursty overload through a small-laned front-end and check
    the request ledger balances: offered == rejected + completed +
    failed per class, every accepted request terminal exactly once."""
    g, cfg, params, nai = setup
    fe = ServingFrontend(cfg, params, g,
                         _two_classes(nai, queue_depth=queue_depth),
                         mode="host")
    events = _bursty_events(g, nai, n_bursts=n_bursts, seed=seed)
    accepted, terminal = [], []
    for t, cls, nid in events:
        r = fe.submit(nid, cls, now=t, budget_s=1e9)
        if r is not None:
            accepted.append(r)
        terminal += fe.step(now=t)
    terminal += fe.step(now=events[-1][0] + 100.0)
    terminal += fe.flush()
    ids = [id(r) for r in terminal]
    assert len(ids) == len(set(ids)), "a request terminated twice"
    assert set(ids) == set(id(r) for r in accepted), \
        "lost or phantom requests"
    assert fe.pending() == 0
    assert all(r.status in ("completed", "failed") for r in accepted)
    for name, st in fe.stats.items():
        assert st.offered == st.accepted + st.rejected, name
        assert st.accepted == st.completed + st.failed, name
        # submitted == completed + shed (+ failed, zero on clean paths)
        assert st.offered == st.completed + st.rejected + st.failed, name
        assert st.failed == 0
    assert sum(st.rejected for st in fe.stats.values()) > 0, \
        "overload never shed — the property needs backpressure hits"
    return fe


def test_conservation_under_bursty_overload(setup):
    """Deterministic slice of the hypothesis property below — runs even
    where hypothesis is unavailable."""
    fe = _conservation_run(setup, seed=0, n_bursts=8, queue_depth=4)
    # reset_stats starts a fresh ledger that must balance on its own
    fe.reset_stats()
    g, _, _, nai = setup
    for i, nid in enumerate(g.test_idx[:10]):
        fe.submit(int(nid), "gold", now=1000.0 + i * 1e-4, budget_s=1e9)
    fe.step(now=2000.0)
    fe.flush()
    st = fe.stats["gold"]
    assert st.offered == 10
    assert st.offered == st.completed + st.rejected + st.failed
    assert fe.pending() == 0


def test_conservation_property(setup):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1000), n_bursts=st.integers(2, 10),
           queue_depth=st.integers(1, 12))
    def prop(seed, n_bursts, queue_depth):
        g, cfg, params, nai = setup
        fe = ServingFrontend(cfg, params, g,
                             _two_classes(nai, queue_depth=queue_depth),
                             mode="host")
        events = _bursty_events(g, nai, n_bursts=n_bursts, seed=seed)
        accepted, terminal = [], []
        for t, cls, nid in events:
            r = fe.submit(nid, cls, now=t, budget_s=1e9)
            if r is not None:
                accepted.append(r)
            terminal += fe.step(now=t)
        terminal += fe.step(now=events[-1][0] + 100.0)
        terminal += fe.flush()
        assert len(terminal) == len(accepted)
        assert set(map(id, terminal)) == set(map(id, accepted))
        assert fe.pending() == 0
        for name, s in fe.stats.items():
            assert s.offered == s.completed + s.rejected + s.failed

    prop()
