"""`chip_smoke.py` at a tiny size on the CPU (Pallas interpret mode, as the
platform selects): every phase's checks must hold, the sharded phases on
four virtual devices, and the entry point must refuse a non-TPU platform
without printing a result."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCALE = 0.01          # 2,000 nodes of the arxiv-like generator, f=128


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_tiny(smoke):
    cells = (smoke.Cell("segment", 3, 32, 2),
             smoke.Cell("block_ell", 2, 16, 2),
             smoke.Cell("fused", 2, 16, 2))
    res = smoke.run_phases(1, scale=SCALE, cells=cells)
    by = {r["phase"]: r for r in res}
    for c in cells:
        r = by[f"serve/{c.backend}"]
        assert r["served"] == 2 * c.batch * c.n_batches
        assert r["failed"] == r["retried"] == r["steady_compiles"] == 0
        assert r["host"]["unexplained"] == 0
        assert ("tiles" in r["operand_bytes"]) == (c.backend != "segment")
    off = by["offline"]
    assert off["oracle_equal"] and off["resume_equal"]
    assert off["resumed_from"] == 1


def test_host_agreement_flags_wrong_answers(smoke):
    """A served answer that differs from the host reference and is not a
    near-tie must count as unexplained."""
    import numpy as np
    from repro.gnn.nai import NAIConfig, infer_batch_host
    g, store, cfg, params, t_s = smoke.build_setup(SCALE, 0, 2)
    cell = smoke.Cell("segment", 2, 16, 2)
    nai = NAIConfig(t_s=t_s, t_min=1, t_max=2, batch_size=16)
    stream = smoke.request_stream(g, cell, 0)
    preds, orders = [], []
    for nodes in stream:
        uniq, inv = np.unique(nodes, return_inverse=True)
        p, o, _, _, _ = infer_batch_host(cfg, nai, params, store, uniq)
        preds.append(p[inv])
        orders.append(o[inv])
    preds, orders = np.concatenate(preds), np.concatenate(orders)
    good = smoke.host_agreement(store, cfg, params, nai, stream, preds,
                                orders)
    assert good["exact"] == 32 and good["unexplained"] == 0
    preds[3] = (preds[3] + 1) % cfg.num_classes
    orders[20] = 3 - orders[20]
    bad = smoke.host_agreement(store, cfg, params, nai, stream, preds,
                               orders)
    assert bad["exact"] == 30 and bad["unexplained"] == 2, bad


SHARDED = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "src")
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
cells = (smoke.Cell("segment", 3, 32, 2), smoke.Cell("block_ell", 2, 16, 2))
res = smoke.run_phases(4, scale=SCALE, cells=cells)
assert [r["phase"] for r in res] == ["sharded_serve/segment",
    "sharded_serve/block_ell", "sharded_offline"], res
print("SHARDED_SMOKE_OK")
""".replace("SCALE", repr(SCALE))


def test_sharded_phases_tiny_on_virtual_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SHARDED], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_SMOKE_OK" in out.stdout, out.stdout + out.stderr


def test_entry_point_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    for line in out.stdout.splitlines():
        try:
            assert "ok" not in json.loads(line)
        except json.JSONDecodeError:
            pass
