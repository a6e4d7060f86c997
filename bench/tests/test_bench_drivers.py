"""Each driver at a tiny size on the CPU with the chip check stubbed, a
run with the timed path broken underneath for each fault a cell can
have, and `run.py` refusing the CPU."""
import importlib.util
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import tinybench

BENCH = Path(__file__).resolve().parents[1]


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run_main",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(root, workload, capsys, seed=2**33 + 5, seconds=1.0):
    rc = _run_module().main(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", "0"], root=root, bench_dir=root / "bench",
        require=tinybench.any_device, t_start=time.perf_counter())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.fixture
def root(tmp_path):
    return tinybench.make_root(tmp_path)


def _wrong_answer(monkeypatch):
    """A class altered where the engine completes a batch."""
    from repro.serving.engine import NAIServingEngine
    complete = NAIServingEngine._complete

    def altered(self, batch, preds, orders, done):
        preds = np.array(preds, copy=True)
        preds[0] = (preds[0] + 1) % self.cfg.num_classes
        return complete(self, batch, preds, orders, done)
    monkeypatch.setattr(NAIServingEngine, "_complete", altered)


def _frozen_step(monkeypatch):
    """A propagation step that returns its state unchanged."""
    from repro.gnn import backends

    def frozen(self, ops, x_full, node_active, active_rb, ts2, *,
               n_batch, n_rows):
        return x_full, backends._distance_exits(x_full, ops["x_inf"], ts2,
                                                n_batch)
    monkeypatch.setattr(backends.SegmentBackend, "step", frozen)


def test_serving_driver_runs_and_is_correct(root, capsys):
    out = run_cell(root, "tiny.serve", capsys)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
    assert out["attempted"] == 60 and out["failed"] == 0
    assert 0 < out["metrics"]["latency_p50_ms"]["value"] \
        <= out["metrics"]["latency_p95_ms"]["value"]
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["compared"]["value"] == 60


@pytest.mark.parametrize("fault", [_wrong_answer, _frozen_step])
def test_serving_faults_are_not_correct(root, capsys, monkeypatch, fault):
    fault(monkeypatch)
    out = run_cell(root, "tiny.serve", capsys)
    assert out["correct"] is False
    c = out["checks"]
    assert (c["logit_gap"]["value"] > c["logit_gap"]["limit"]
            or c["exit_gap"]["value"] > c["exit_gap"]["limit"])


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "arxiv-sgc.serve-uniform", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_sweep_reports_attainment_backlog_and_lateness(root):
    import sweep
    from yardstick import harness
    cell = harness.find_cell("tiny.serve", False, root)
    drv = cell.driver
    dep = drv.build(cell.config, 5)
    fe = drv.frontend(dep, cell.traffic)
    drv.warm(fe, dep)
    rows = [sweep.sweep_rate(drv, fe, dep, cell.traffic, rate, 0.5, 7,
                             deadlines=(0.5, 5.0)) for rate in (20, 80)]
    fe.close()
    assert [r["offered"] for r in rows] == [10, 40]
    for r in rows:
        assert 0.0 <= r["attainment"] <= 1.0
        assert r["met_5s"] >= r["met_0.5s"]
        assert r["backlog_at_close"] >= 0 and r["shed"] == 0
        assert 0.0 <= r["late_p50_ms"] <= r["late_max_ms"]
