"""The offline driver at a tiny size on the CPU, correct, and not correct
with the timed path broken underneath; and over four virtual devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import tinybench
from test_bench_drivers import _frozen_step, run_cell


@pytest.fixture
def root(tmp_path):
    return tinybench.make_root(tmp_path)


def _wrong_answer(monkeypatch):
    """A class altered where the offline classifier produces it."""
    from repro.launch import full_graph_infer as fgi
    make = fgi._make_classifier

    def altered(cfg, tmax):
        classify = make(cfg, tmax)

        def wrong(params, exit_order, series):
            out = np.array(classify(params, exit_order, series), copy=True)
            out[0] = (out[0] + 1) % cfg.num_classes
            return out
        return wrong
    monkeypatch.setattr(fgi, "_make_classifier", altered)


def test_offline_driver_runs_and_is_correct(root, capsys):
    out = run_cell(root, "tiny.offline", capsys, seconds=0.5)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"nodes_per_s", "setup_s"}
    assert out["attempted"] % 600 == 0 and out["attempted"] >= 600
    assert out["checks"]["compared"]["value"] == out["attempted"]
    assert not (root / "bench" / ".out" / "tiny.offline" / "ckpt").exists()


@pytest.mark.parametrize("fault", [_wrong_answer, _frozen_step])
def test_offline_faults_are_not_correct(root, capsys, monkeypatch, fault):
    fault(monkeypatch)
    out = run_cell(root, "tiny.offline", capsys, seconds=0.5)
    assert out["correct"] is False


FOUR_DEVICES = r"""
import json, os, sys, time
from pathlib import Path
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
tests = Path(sys.argv[1])
sys.path[:0] = [str(tests.parents[1] / "src"), str(tests.parent), str(tests)]
import tinybench
from test_bench_drivers import _run_module
from repro.launch import full_graph_infer as fgi

calls = []
run_job = fgi.run_full_graph_infer


def seen(store, cfg, params, nai, ocfg, *, mesh=None, **kw):
    calls.append([None if mesh is None else mesh.devices.size,
                  ocfg.gather_mode])
    return run_job(store, cfg, params, nai, ocfg, mesh=mesh, **kw)


fgi.run_full_graph_infer = seen
root = tinybench.make_root(Path(sys.argv[2]))
cell = tinybench.add_sharded_offline(root, int(sys.argv[3]))
rc = _run_module().main(
    ["--workload", cell, "--seed", str(2**34 + 3), "--seconds", "0.5",
     "--trace", "0"], root=root, bench_dir=root / "bench",
    require=tinybench.any_device, t_start=time.perf_counter())
print(json.dumps(calls), file=sys.stderr)
sys.exit(rc)
"""


@pytest.mark.parametrize("chips, mesh", [(4, 4), (1, None)])
def test_offline_driver_runs_over_the_cell_s_chips(tmp_path, chips, mesh):
    """On four virtual devices, a cell with ``"chips": 4`` runs every job
    on a mesh of four exchanging rows by all-to-all, and is correct; a
    cell with one chip runs with no mesh, as the one-chip cells do."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES, str(Path(__file__).parent),
         str(tmp_path), str(chips)], capture_output=True, text=True,
        env=env, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["checks"]["compared"]["value"] == out["attempted"] > 0
    calls = json.loads(p.stderr.strip().splitlines()[-1])
    assert len(calls) >= 2          # set-up's job and the window's
    assert all(c == [mesh, "alltoall"] for c in calls)
