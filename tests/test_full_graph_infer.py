"""Offline full-graph inference driver tests.

The contract under test: `run_full_graph_infer` classifies every node
BIT-IDENTICALLY to the serving compiled path over the same full-graph
pack (the superstep chain is the fori-loop body, one dispatch per
step), and a run killed after ANY superstep resumes to the exact same
predictions and exit orders. Fault stages (ckpt_write / ckpt_read /
superstep_hang) exercise the tolerate/fallback/retry paths without
breaking parity. The sharded (D=2) CLI kill/resume runs in a
subprocess so the forced host-device count stays isolated."""
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

from repro.gnn.backends import pack_operands
from repro.gnn.distributed import pack_graph
from repro.gnn.models import GNNConfig, init_classifiers
from repro.gnn.nai import NAIConfig, make_compiled_infer
from repro.gnn.store import make_graph
from repro.launch.full_graph_infer import (OfflineConfig,
                                           PreemptionSimulated,
                                           first_step_distance_quantile,
                                           run_full_graph_infer)
from repro.serving.faults import FaultPlan, FaultSpec, WatchdogTimeout

T_MAX = 3


@pytest.fixture(scope="module")
def setup():
    store = make_graph(800, avg_deg=6.0, alpha=2.2, seed=3, path=None,
                       feat_dim=24, num_classes=5)
    t_s = first_step_distance_quantile(store, 0.5, 0.5)
    cfg = GNNConfig("sgc", store.feat_dim, store.num_classes, k=T_MAX,
                    r=0.5, hidden=16, mlp_layers=2)
    params = {"cls": init_classifiers(cfg, jax.random.PRNGKey(0))}
    nai = NAIConfig(t_s=t_s, t_min=1, t_max=T_MAX)
    with tempfile.TemporaryDirectory() as d:
        ref = run_full_graph_infer(store, cfg, params, nai,
                                   OfflineConfig(ckpt_dir=d + "/ck"))
    # a useful reference exercises BOTH early and late exits
    hist = ref.stats["exit_histogram"]
    assert hist[1] > 0 and hist[T_MAX] > 0, hist
    return store, cfg, params, nai, ref


def _run(setup, tmp, **kw):
    store, cfg, params, nai, _ = setup
    plan = kw.pop("fault_plan", None)
    return run_full_graph_infer(store, cfg, params, nai,
                                OfflineConfig(ckpt_dir=tmp, **kw),
                                fault_plan=plan)


def _assert_parity(res, ref):
    np.testing.assert_array_equal(res.predictions, ref.predictions)
    np.testing.assert_array_equal(res.exit_orders, ref.exit_orders)


# --------------------------------------------------- oracle bit-parity
def test_bit_identical_to_serving_compiled_path(setup):
    """The acceptance oracle: the checkpointed superstep chain must
    equal make_compiled_infer (the serving path) on the identical
    full-graph pack — exact equality, not a tolerance."""
    import jax.numpy as jnp
    store, cfg, params, nai, ref = setup
    be, packed = pack_graph(store, 1, cfg.r, "segment", stationary=True)
    ops = {k: jnp.asarray(v)
           for k, v in pack_operands(be, packed).items()}
    run = make_compiled_infer(cfg, nai, spmm_impl="segment")
    preds, eo = run(params["cls"], ops, jnp.asarray(packed.x0),
                    jnp.asarray(packed.x_inf))
    np.testing.assert_array_equal(ref.predictions,
                                  np.asarray(preds)[:store.n])
    np.testing.assert_array_equal(ref.exit_orders,
                                  np.asarray(eo)[:store.n])


@pytest.mark.parametrize("impl", ["block_ell", "fused"])
def test_tile_backends_match(setup, impl, tmp_path):
    res = _run(setup, str(tmp_path / "ck"), spmm_impl=impl)
    _assert_parity(res, setup[4])


# ------------------------------------------------- kill/resume parity
def test_kill_at_every_superstep_resumes_bit_identical(setup, tmp_path):
    """The tentpole property: for every superstep k, a run preempted
    right after committing k and then rerun produces exactly the
    uninterrupted run's outputs, resuming from k (no recompute of the
    committed prefix)."""
    ref = setup[4]
    for k in range(T_MAX):
        ck = str(tmp_path / f"kill{k}")
        with pytest.raises(PreemptionSimulated):
            _run(setup, ck, crash_after=k)
        res = _run(setup, ck)
        assert res.stats["resumed_from"] == k
        assert res.stats["supersteps_run"] == T_MAX - k
        _assert_parity(res, ref)


def test_repeated_preemption_and_completed_rerun(setup, tmp_path):
    """Die after every single superstep in sequence (the worst
    preemption schedule), then once more on the completed directory —
    the final rerun resumes at t_max, runs zero supersteps, and still
    emits the exact outputs."""
    ck = str(tmp_path / "ck")
    for k in range(T_MAX):
        with pytest.raises(PreemptionSimulated):
            _run(setup, ck, crash_after=k)
    res = _run(setup, ck)
    _assert_parity(res, setup[4])
    again = _run(setup, ck)
    assert again.stats["resumed_from"] == T_MAX
    assert again.stats["supersteps_run"] == 0
    _assert_parity(again, setup[4])


def test_no_resume_ignores_existing_checkpoints(setup, tmp_path):
    ck = str(tmp_path / "ck")
    with pytest.raises(PreemptionSimulated):
        _run(setup, ck, crash_after=1)
    res = _run(setup, ck, resume=False)
    assert res.stats["resumed_from"] == 0
    assert res.stats["supersteps_run"] == T_MAX
    _assert_parity(res, setup[4])


# ------------------------------------------------------- fault stages
def test_corrupt_checkpoint_falls_back_one_superstep(setup, tmp_path):
    ck = str(tmp_path / "ck")
    with pytest.raises(PreemptionSimulated):
        _run(setup, ck, crash_after=2)
    path = os.path.join(ck, "step_00002", "x.npy")
    with open(path, "r+b") as fh:
        fh.seek(os.path.getsize(path) // 2)
        b = fh.read(1)
        fh.seek(-1, 1)
        fh.write(bytes([b[0] ^ 0xFF]))
    res = _run(setup, ck)
    assert res.stats["resumed_from"] == 1
    assert res.stats["corrupt_steps"] == 1
    assert res.stats["fallbacks"]
    _assert_parity(res, setup[4])


def test_ckpt_write_fault_is_tolerated_and_resume_falls_back(
        setup, tmp_path):
    """A failed checkpoint write (payloads on disk, manifest never
    committed) must not kill the run; a subsequent crash resumes from
    the last step that DID commit — with intact parity."""
    ck = str(tmp_path / "ck")
    plan = FaultPlan([FaultSpec("ckpt_write", at=(2,))])
    with pytest.raises(PreemptionSimulated):
        _run(setup, ck, crash_after=T_MAX, fault_plan=plan)
    res = _run(setup, ck)
    assert res.stats["resumed_from"] < T_MAX
    _assert_parity(res, setup[4])


def test_ckpt_read_fault_at_resume_falls_back(setup, tmp_path):
    ck = str(tmp_path / "ck")
    with pytest.raises(PreemptionSimulated):
        _run(setup, ck, crash_after=2)
    plan = FaultPlan([FaultSpec("ckpt_read", at=(0,))])
    res = _run(setup, ck, fault_plan=plan)
    assert res.stats["corrupt_steps"] >= 1
    _assert_parity(res, setup[4])


def test_superstep_hang_retries_deterministically(setup, tmp_path):
    plan = FaultPlan([FaultSpec("superstep_hang", at=(0,),
                                max_fires=1)])
    res = _run(setup, str(tmp_path / "ck"), fault_plan=plan)
    assert res.stats["watchdog_retries"] == 1
    assert res.stats["injected"]["superstep_hang"]["fired"] == 1
    _assert_parity(res, setup[4])


def test_superstep_hang_every_attempt_times_out(setup, tmp_path):
    plan = FaultPlan([FaultSpec("superstep_hang", rate=1.0)])
    with pytest.raises(WatchdogTimeout):
        _run(setup, str(tmp_path / "ck"), fault_plan=plan)


def test_config_validation():
    with pytest.raises(ValueError, match="ckpt_dir"):
        OfflineConfig(ckpt_dir="")
    with pytest.raises(ValueError, match="watchdog_s"):
        OfflineConfig(ckpt_dir="x", watchdog_s=-1)
    with pytest.raises(ValueError, match="straggler_factor"):
        OfflineConfig(ckpt_dir="x", straggler_factor=1.0)
    with pytest.raises(ValueError, match="crash_after"):
        OfflineConfig(ckpt_dir="x", crash_after=-1)


# ------------------------------------------- sharded CLI kill/resume
SCRIPT = r"""
import os, sys, subprocess, tempfile
import numpy as np

root = os.getcwd()
env = dict(os.environ)
env["PYTHONPATH"] = os.path.join(root, "src")
env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

with tempfile.TemporaryDirectory() as d:
    store = os.path.join(d, "store")
    subprocess.run([sys.executable, "-c",
        "from repro.gnn.store import make_graph; import sys; "
        "make_graph(4000, avg_deg=6.0, alpha=2.2, seed=5, "
        "path=sys.argv[1], feat_dim=24, num_classes=7)", store],
        env=env, check=True)
    base = [sys.executable, "-m", "repro.launch.full_graph_infer",
            "--store", store, "--shards", "2", "--gather", "alltoall",
            "--t-max", "3", "--t-s-quantile", "0.5"]

    ck_a = os.path.join(d, "ck_clean")
    subprocess.run(base + ["--ckpt", ck_a], env=env, check=True)

    ck_b = os.path.join(d, "ck_kill")
    p = subprocess.run(base + ["--ckpt", ck_b, "--crash-after", "1"],
                       env=env)
    assert p.returncode == 17, p.returncode
    subprocess.run(base + ["--ckpt", ck_b], env=env, check=True)

    for name in ("predictions", "exit_orders"):
        a = np.load(os.path.join(ck_a, "result", name + ".npy"))
        b = np.load(os.path.join(ck_b, "result", name + ".npy"))
        assert np.array_equal(a, b), name
print("SHARDED_OFFLINE_OK")
"""


def test_sharded_cli_kill_resume_parity():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=900)
    assert "SHARDED_OFFLINE_OK" in out.stdout, out.stdout + out.stderr
