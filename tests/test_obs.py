"""Spans and records of the program's own work (`repro.obs`): the span
API, the serving batch record on the profiler's clock and on a virtual
clock, the offline job record, and the device-side ``nap.*`` scopes."""
import contextlib
import dataclasses
import glob
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.gnn import GNNConfig, init_classifiers, load_dataset
from repro.gnn.nai import NAIConfig, make_compiled_infer
from repro.gnn.store import make_graph
from repro.launch.full_graph_infer import (OfflineConfig,
                                           first_step_distance_quantile,
                                           run_full_graph_infer)
from repro.serving import NAIServingEngine
from repro.serving import engine as engine_mod


# ------------------------------------------------------------ span API
def test_span_adds_to_its_leaf_key_and_nests():
    rec = {}
    with obs.span(rec, "serve.host", batch=3):
        with obs.span(rec, "serve.sample", batch=3):
            time.sleep(0.002)
        with obs.span(rec, "serve.sample", batch=3):
            pass
    assert set(rec) == {"host_s", "sample_s"}
    assert 0.002 <= rec["sample_s"] <= rec["host_s"]
    with pytest.raises(ValueError):
        with obs.span(rec, "offline.fetch", step=1):
            raise ValueError("the span still counts")
    assert rec["fetch_s"] >= 0.0
    assert obs.key_of("offline.total") == "total_s"


def test_publish_keeps_the_newest_records():
    kind = "test.bounded"
    for i in range(obs.MAX_RECORDS + 5):
        obs.publish(kind, {"i": i})
    got = obs.records(kind)
    assert obs.MAX_RECORDS == 1024 and len(got) == 1024
    assert got[0]["i"] == 5 and got[-1]["i"] == 1028
    assert obs.records("test.never") == []


# ------------------------------------------------------------- serving
@pytest.fixture(scope="module")
def setup():
    g = load_dataset("pubmed-like", scale=0.02, seed=4)
    g = dataclasses.replace(
        g, features=np.ascontiguousarray(g.features[:, :64]))
    cfg = GNNConfig("sgc", 64, g.num_classes, k=2, hidden=32, mlp_layers=2)
    params = {"cls": init_classifiers(cfg, jax.random.PRNGKey(0))}
    nai = NAIConfig(t_s=6.0, t_min=1, t_max=2, batch_size=32)
    return g, cfg, params, nai


def _engine(setup, **kw):
    g, cfg, params, nai = setup
    return NAIServingEngine(cfg, nai, params, g, max_wait_s=10.0,
                            mode="compiled", spmm_impl="segment", **kw)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_batch_spans_nest_in_the_profiler_trace(setup, tmp_path):
    g = setup[0]
    eng = _engine(setup)
    nodes = g.test_idx[:20]
    eng.submit(nodes)
    eng.step()                  # compile outside the trace
    eng.flush()
    seq = eng._batch_seq
    with jax.profiler.trace(str(tmp_path)):
        eng.submit(nodes)
        eng.step()
        eng.flush()
    ev = [e for e in _host_events(tmp_path) if e[3].get("batch") == seq]
    by = {name: (a, b) for name, a, b, _ in ev}
    for name in ("nai.serve.host", "nai.serve.sample", "nai.serve.gather",
                 "nai.serve.pack", "nai.serve.dispatch", "nai.serve.sync"):
        assert name in by, sorted(by)
    h0, h1 = by["nai.serve.host"]
    inner = [by[f"nai.serve.{k}"] for k in ("sample", "gather", "pack")]
    for a, b in inner:
        assert h0 <= a <= b <= h1
    assert inner[0][1] <= inner[1][0] and inner[1][1] <= inner[2][0]
    assert h1 <= by["nai.serve.dispatch"][0] <= by["nai.serve.sync"][0]
    rec = eng.batch_timings[-1]
    assert rec["batch"] == seq and obs.records("serve.batch")[-1] is rec


class _Clock:
    """A perf_counter that moves only when a test moves it."""

    def __init__(self, t):
        self.t = t

    def __call__(self):
        return self.t

    def wrap(self, fn, dt):
        def moved(*a, **kw):
            out = fn(*a, **kw)
            self.t += dt
            return out
        return moved


def test_queue_wait_and_stage_intervals_on_a_virtual_clock(setup,
                                                           monkeypatch):
    g = setup[0]
    eng = _engine(setup, pipeline_depth=2)
    clock = _Clock(100.0)
    monkeypatch.setattr(time, "perf_counter", clock)
    monkeypatch.setattr(engine_mod, "sample_support",
                        clock.wrap(engine_mod.sample_support, 0.0625))
    monkeypatch.setattr(engine_mod, "support_stationary_factors",
                        clock.wrap(engine_mod.support_stationary_factors,
                                   0.03125))
    monkeypatch.setattr(engine_mod, "pack_support",
                        clock.wrap(engine_mod.pack_support, 0.015625))
    monkeypatch.setattr(eng, "_inject_host_faults",
                        clock.wrap(lambda: None, 0.25))
    monkeypatch.setattr(eng, "_device_stage",
                        clock.wrap(eng._device_stage, 0.125))
    first, second = g.test_idx[:12], g.test_idx[12:20]
    eng.submit(first[:5], now=90.0)
    eng.submit(first[5:], now=95.5)
    assert eng.step() == []            # batch 0 in flight
    eng._inflight[0].delivery.result(timeout=60)   # the waiter delivered it
    clock.t = 102.0
    eng.submit(second, now=101.0)
    done = eng.step()                  # batch 1's stages, then batch 0's
    done += eng.flush()                # finalize joins the waiter
    r0, r1 = eng.batch_timings
    host = 0.25 + 0.0625 + 0.03125 + 0.015625   # faults hook + host stage
    for rec in (r0, r1):
        assert rec["sample_s"] == 0.0625
        assert rec["gather_s"] == 0.03125
        assert rec["pack_s"] == 0.015625
        assert rec["host_s"] == host
        assert rec["dispatch_s"] == 0.125
        assert rec["sync_s"] == 0.0
    assert r0["n"] == 12 and r1["n"] == 8
    reqs = {r.node_id: r for r in done}
    b0 = [reqs[int(n)] for n in first]
    assert all(r.batched_s == 100.0 for r in b0)
    assert r0["queue_wait_s"] == sum(r.batched_s - r.arrival_s for r in b0)
    assert r0["queue_wait_s"] == 5 * 10.0 + 7 * 4.5
    # batch 0's hold ends when its device results are ready, before the
    # clock moved: its answers left at its dispatch's end, 2 s before
    # the engine thread reached its finalize after batch 1's dispatch
    dispatched0 = 100.0 + host + 0.125
    assert r0["hold_s"] == 0.0
    assert all(r.done_s == dispatched0 for r in b0)
    assert r0["early"] == 1
    assert r0["lead_s"] == (102.0 + host + 0.125) - dispatched0
    for r in b0:   # the record accounts for the whole latency
        parts = (r.batched_s - r.arrival_s + r0["host_s"] + r0["dispatch_s"]
                 + r0["hold_s"] + r0["sync_s"])
        assert r.done_s - r.arrival_s == pytest.approx(parts, abs=1e-12)
    assert r0["rows_real"] <= r0["rows_pad"]
    assert r0["edges_real"] <= r0["edges_pad"]


def _capture_runner_args(eng):
    """Replace the engine's jitted runner with a recorder of its args."""
    seen = []
    run = eng._runner

    def recorder(*args):
        seen.append(args)
        return run(*args)
    eng._runner = recorder
    return run, seen


def test_runner_carries_nap_scopes_and_keeps_its_program(setup,
                                                         monkeypatch):
    g, cfg, params, nai = setup
    eng = _engine(setup)
    run, seen = _capture_runner_args(eng)
    eng.submit(g.test_idx[:16])
    eng.step()
    eng.flush()
    lowered = run.lower(*seen[0])
    text = lowered.as_text(debug_info=True)
    for scope in ("nap.propagate", "nap.exit", "nap.classify"):
        assert scope in text, scope
    assert "jit_run" in text
    # the scopes are metadata only: without them the program is the same
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = make_compiled_infer(cfg, nai, spmm_impl="segment")
    assert bare.lower(*seen[0]).as_text() == lowered.as_text()


# ------------------------------------------------------------- offline
def test_offline_job_record_covers_its_spans(tmp_path):
    store = make_graph(400, avg_deg=6.0, alpha=2.2, seed=3, path=None,
                       feat_dim=16, num_classes=4)
    cfg = GNNConfig("sgc", store.feat_dim, store.num_classes, k=3, r=0.5,
                    hidden=16, mlp_layers=2)
    params = {"cls": init_classifiers(cfg, jax.random.PRNGKey(0))}
    nai = NAIConfig(t_s=first_step_distance_quantile(store, 0.5, 0.5),
                    t_min=1, t_max=3)
    res = run_full_graph_infer(store, cfg, params, nai,
                               OfflineConfig(ckpt_dir=str(tmp_path / "ck")))
    s = res.stats
    assert obs.records("offline.job")[-1] is s
    parts = [s[k] for k in ("upload_s", "pack_s", "ckpt_s", "compute_s",
                            "fetch_s", "classify_s", "result_s")]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= s["total_s"]
    assert s["nodes_per_s"] == store.n / s["total_s"]
    assert s["supersteps_run"] == 3
