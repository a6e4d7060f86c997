"""Smoke run of the NAI system on a TPU: online serving and checkpointed
offline inference through their normal entry points, at the full width of
the arxiv-like deployment (169,343 nodes, f=128, 40 classes; the SGC
per-order linear classifiers are random, from a seed — Eq. 8 exits do not
depend on them).

    python chip_smoke.py             # one chip: the serve and offline phases
    python chip_smoke.py --chips 4   # four chips: sharded against one device

One chip:

* ``serve/<backend>`` — requests through `NAIServingEngine` in compiled
  mode (pipeline depth 2, batches formed exactly as submitted). The request
  stream is served twice: the second pass must compile nothing and repeat
  the first bit for bit. Every request is checked against
  `infer_batch_host` (the NumPy Algorithm 1) on the same params and the
  same batch composition, within the bounds stated below. ``segment`` runs
  at T_max=3, batch 512; the tile backends at T_max=2, batch 64, whose
  block-ELL tiles are 2.15 GB per batch (larger batches do not fit one
  chip in that format yet).
* ``offline`` — `run_full_graph_infer` over the whole graph must equal
  `make_compiled_infer` on the same full-graph pack exactly, and a run
  preempted after superstep 1 and resumed must equal the uninterrupted one.

Four chips (``--chips 4``): sharded serving at D=4 (``alltoall`` and
``halo`` frontier exchange) against the one-device engine on the same
batches, and `run_full_graph_infer` on a D=4 mesh against D=1 — exact
equality of every prediction and exit order.

The last line of standard output is the JSON result, printed only when
every check passed on a TPU. Exit code 1: a check failed or a phase
raised; 2: JAX found no TPU (or too few chips).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.gnn import load_dataset                           # noqa: E402
from repro.gnn.backends import pack_operands                 # noqa: E402
from repro.gnn.distributed import pack_graph                 # noqa: E402
from repro.gnn.models import GNNConfig, init_classifiers     # noqa: E402
from repro.gnn.nai import (NAIConfig, _subgraph_spmm,        # noqa: E402
                           infer_batch_host, make_compiled_infer,
                           support_stationary_state)
from repro.gnn.sampler import sample_support                 # noqa: E402
from repro.gnn.store import as_store                         # noqa: E402
from repro.launch.full_graph_infer import (                  # noqa: E402
    OfflineConfig, PreemptionSimulated, first_step_distance_quantile,
    run_full_graph_infer)
from repro.launch.mesh import make_serving_mesh              # noqa: E402
from repro.runtime import enable_compile_cache               # noqa: E402
from repro.serving import EngineConfig, NAIServingEngine     # noqa: E402
from repro.serving.engine import Request                     # noqa: E402

DATASET = "arxiv-like"

# Agreement with the host reference. The compiled paths and the host add
# the same f32 terms in another order (a scatter-add on the device, MXU
# accumulation in the tile kernels), which moves an Eq. 8 distance by a
# few f32 ulps of the feature norm; the host compares in float64. So:
# * an exit order may differ from the host's only where the host distance
#   at the earlier of the two orders lies within REL_DIST_MARGIN of T_s;
# * with equal exit orders a prediction may differ only where the host's
#   two largest logits lie within REL_LOGIT_MARGIN of each other;
# * such near-ties may cover at most MAX_NEAR_TIE_SHARE of the requests.
# Every other difference is a failure.
REL_DIST_MARGIN = 1e-3
REL_LOGIT_MARGIN = 1e-4
MAX_NEAR_TIE_SHARE = 0.01


@dataclasses.dataclass(frozen=True)
class Cell:
    """One serving configuration: backend, NAP depth, batch size and the
    number of batches in the request stream."""
    backend: str
    t_max: int
    batch: int
    n_batches: int


ONE_CHIP_CELLS = (Cell("segment", 3, 512, 2),
                  Cell("block_ell", 2, 64, 4),
                  Cell("fused", 2, 64, 4))
SHARDED_CELLS = (Cell("segment", 3, 512, 2),
                 Cell("block_ell", 2, 64, 4))
OFFLINE_T_MAX = 3


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ setup
def build_setup(scale: float, seed: int, k: int):
    """The arxiv-like graph as a store, seeded SGC classifiers for orders
    1..k (linear, the paper's SGC form), and the data-driven T_s: the
    median first-step Eq. 8 distance."""
    g = load_dataset(DATASET, scale=scale, seed=seed)
    store = as_store(g)
    cfg = GNNConfig("sgc", store.feat_dim, g.num_classes, k=k,
                    mlp_layers=1)
    params = {"cls": init_classifiers(cfg, jax.random.PRNGKey(seed))}
    t_s = first_step_distance_quantile(store, cfg.r, 0.5)
    return g, store, cfg, params, t_s


def request_stream(g, cell: Cell, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.choice(g.test_idx, size=cell.batch, replace=False)
            for _ in range(cell.n_batches)]


def serve_stream(engine, stream):
    """Submit each batch, step, flush; returns (node, prediction, exit
    order, status) arrays in completion order."""
    reqs = []
    for nodes in stream:
        now = time.perf_counter()
        batch = [Request(int(n), now) for n in nodes]
        for r in batch:
            engine.submit_request(r)
        reqs += batch
        engine.step()
    engine.flush()
    return (np.array([r.node_id for r in reqs]),
            np.array([r.prediction for r in reqs]),
            np.array([r.exit_order for r in reqs]),
            [r.status for r in reqs])


def make_engine(store, cfg, params, nai, backend, mesh=None,
                gather_mode="halo"):
    return NAIServingEngine(
        cfg, nai, params, store,
        config=EngineConfig(mode="compiled", spmm_impl=backend,
                            pipeline_depth=2, max_wait_s=10.0, mesh=mesh,
                            gather_mode=gather_mode))


# ---------------------------------------------------- host agreement
def _host_series(store, nodes, t_max, r):
    """Batch-row series X^(0..t_max) of the host path (every support row
    propagated, which equals Algorithm 1's values at rows still active)
    and the float64 stationary state, for explaining a disagreement."""
    sup = sample_support(store, nodes, t_max, r)
    x = store.gather_features(sup.nodes).astype(np.float32)
    x_inf = support_stationary_state(store, sup, x, r)
    series = [x[:sup.n_batch]]
    everyone = np.ones(len(sup), bool)
    for _ in range(t_max):
        x, _ = _subgraph_spmm(sup, x, everyone)
        series.append(x[:sup.n_batch])
    return np.stack(series), x_inf


def _host_logits(cls_l, feats):
    """Float64 logits of a linear SGC head."""
    w = np.asarray(cls_l["w0"], np.float64)
    return feats.astype(np.float64) @ w + np.asarray(cls_l["b0"], np.float64)


def host_agreement(store, cfg, params, nai, stream, preds, orders):
    """Compare served predictions/exit orders (stream order) with
    `infer_batch_host` batch by batch; classify each disagreement as a
    stated near-tie or a failure."""
    exact = near = 0
    bad = []
    pos = 0
    for nodes in stream:
        uniq, inv = np.unique(nodes, return_inverse=True)
        hp, ho, _, _, _ = infer_batch_host(cfg, nai, params, store, uniq)
        hp, ho = hp[inv], ho[inv]
        gp, go = preds[pos:pos + len(nodes)], orders[pos:pos + len(nodes)]
        pos += len(nodes)
        diff = np.flatnonzero((hp != gp) | (ho != go))
        exact += len(nodes) - len(diff)
        if not len(diff):
            continue
        series, x_inf = _host_series(store, uniq, nai.t_max, cfg.r)
        for i in diff:
            u = inv[i]
            if ho[i] != go[i]:
                l = int(min(ho[i], go[i]))
                d = float(np.linalg.norm(series[l, u] - x_inf[u]))
                ok = (l < nai.t_max
                      and abs(d - nai.t_s) <= REL_DIST_MARGIN * nai.t_s)
                why = f"exit {go[i]} vs host {ho[i]}, d_{l}={d!r}"
            else:
                l = int(ho[i])
                z = np.sort(_host_logits(params["cls"][l], series[l, u]))
                gap = float(z[-1] - z[-2])
                ok = gap <= REL_LOGIT_MARGIN * max(1.0, abs(float(z[-1])))
                why = f"class {gp[i]} vs host {hp[i]}, logit gap {gap!r}"
            if ok:
                near += 1
            else:
                bad.append(f"node {int(nodes[i])}: {why}")
    n = len(preds)
    return {"requests": n, "exact": exact, "near_tie": near,
            "unexplained": len(bad), "examples": bad[:5]}


# ------------------------------------------------------------ phases
def serve_phase(g, store, cfg, params, t_s, cell: Cell, seed: int):
    """Serve `cell`'s stream twice through one compiled engine and check
    it against the host reference. Returns the printed summary."""
    nai = NAIConfig(t_s=t_s, t_min=1, t_max=cell.t_max,
                    batch_size=cell.batch)
    stream = request_stream(g, cell, seed)
    eng = make_engine(store, cfg, params, nai, cell.backend)
    t0 = time.perf_counter()
    nodes, preds, orders, status = serve_stream(eng, stream)
    first_s = time.perf_counter() - t0
    c0, k0 = eng.jit_stats["compiles"], eng.jit_cache_size()
    _, preds2, orders2, status2 = serve_stream(eng, stream)
    s = eng.stats.summary()
    out = {
        "phase": f"serve/{cell.backend}", "t_max": cell.t_max,
        "batch": cell.batch, "served": s["served"], "failed": s["failed"],
        "retried": s["retried"],
        "steady_compiles": eng.jit_stats["compiles"] - c0,
        "steady_traces": eng.jit_cache_size() - k0,
        "first_pass_s": first_s,
        "exit_hist": dict(sorted(eng.stats.exit_hist.items())),
        "operand_bytes": {k: v for k, v in eng.pooled_bytes().items()
                          if v and k in ("tiles", "x0", "src")},
    }
    eng.flush()
    del eng
    out["host"] = host_agreement(store, cfg, params, nai, stream, preds,
                                 orders)
    log(json.dumps(out))
    n = len(nodes)
    check(s["served"] == 2 * n and s["failed"] == 0 and s["retried"] == 0,
          f"{out['phase']}: served {s['served']} of {2 * n}, failed "
          f"{s['failed']}, retried {s['retried']}")
    check(all(st == "completed" for st in status + status2),
          f"{out['phase']}: requests not completed")
    check(out["steady_compiles"] == 0 and out["steady_traces"] == 0,
          f"{out['phase']}: compiles in the repeated pass")
    check(np.array_equal(preds, preds2) and np.array_equal(orders, orders2),
          f"{out['phase']}: the repeated pass differs from the first")
    h = out["host"]
    check(h["unexplained"] == 0,
          f"{out['phase']}: {h['unexplained']} requests disagree with "
          f"the host reference beyond the stated bounds: {h['examples']}")
    check(h["near_tie"] <= MAX_NEAR_TIE_SHARE * n,
          f"{out['phase']}: {h['near_tie']} near-tie disagreements")
    return out


def offline_phase(store, cfg, params, t_s, t_max: int, workdir: Path):
    """Whole-graph checkpointed inference against the serving path's
    compiled runner on the same pack, then a preempt-and-resume run."""
    nai = NAIConfig(t_s=t_s, t_min=1, t_max=t_max)
    t0 = time.perf_counter()
    ref = run_full_graph_infer(store, cfg, params, nai,
                               OfflineConfig(ckpt_dir=str(workdir / "full")))
    run_s = time.perf_counter() - t0
    be, packed = pack_graph(store, 1, cfg.r, "segment", stationary=True)
    ops = {k: jnp.asarray(v) for k, v in pack_operands(be, packed).items()}
    run = make_compiled_infer(cfg, nai, spmm_impl="segment")
    preds, eo = run(params["cls"], ops, jnp.asarray(packed.x0),
                    jnp.asarray(packed.x_inf))
    preds, eo = np.asarray(preds)[:store.n], np.asarray(eo)[:store.n]
    oracle_eq = (np.array_equal(ref.predictions, preds)
                 and np.array_equal(ref.exit_orders, eo))
    kill = str(workdir / "kill")
    try:
        run_full_graph_infer(store, cfg, params, nai,
                             OfflineConfig(ckpt_dir=kill, crash_after=1))
        preempted = False
    except PreemptionSimulated:
        preempted = True
    res = run_full_graph_infer(store, cfg, params, nai,
                               OfflineConfig(ckpt_dir=kill))
    resume_eq = (np.array_equal(ref.predictions, res.predictions)
                 and np.array_equal(ref.exit_orders, res.exit_orders))
    out = {"phase": "offline", "n": int(store.n), "t_max": t_max,
           "run_s": run_s, "exit_hist": ref.stats["exit_histogram"],
           "oracle_equal": oracle_eq,
           "oracle_pred_diff": int((ref.predictions != preds).sum()),
           "oracle_exit_diff": int((ref.exit_orders != eo).sum()),
           "preempted": preempted,
           "resumed_from": res.stats["resumed_from"],
           "resume_equal": resume_eq}
    log(json.dumps(out))
    check(oracle_eq, "offline: differs from make_compiled_infer")
    check(preempted and res.stats["resumed_from"] == 1,
          "offline: crash_after=1 did not preempt and resume at 1")
    check(resume_eq, "offline: resumed run differs from uninterrupted")
    return out


def sharded_serve_phase(g, store, cfg, params, t_s, cell: Cell, seed: int,
                        n_shards: int):
    """The same batches through a one-device engine and D-shard engines
    (``alltoall`` and ``halo`` exchange): every completion must match."""
    nai = NAIConfig(t_s=t_s, t_min=1, t_max=cell.t_max,
                    batch_size=cell.batch)
    stream = request_stream(g, cell, seed)
    base = serve_stream(make_engine(store, cfg, params, nai, cell.backend),
                        stream)
    out = {"phase": f"sharded_serve/{cell.backend}", "shards": n_shards,
           "t_max": cell.t_max, "batch": cell.batch,
           "requests": len(base[0])}
    mesh = make_serving_mesh(n_shards)
    for gm in ("alltoall", "halo"):
        eng = make_engine(store, cfg, params, nai, cell.backend, mesh=mesh,
                          gather_mode=gm)
        check(eng.n_shards == n_shards, f"engine has {eng.n_shards} shards")
        got = serve_stream(eng, stream)
        s = eng.stats.summary()
        del eng
        same = all(np.array_equal(a, b) for a, b in zip(base[:3], got[:3]))
        out[gm] = {"equal": same, "failed": s["failed"],
                   "retried": s["retried"],
                   "pred_diff": int((base[1] != got[1]).sum()),
                   "exit_diff": int((base[2] != got[2]).sum())}
    log(json.dumps(out))
    for gm in ("alltoall", "halo"):
        r = out[gm]
        check(r["failed"] == 0 and r["retried"] == 0,
              f"{out['phase']}/{gm}: failed or retried requests")
        check(r["equal"], f"{out['phase']}/{gm}: differs from one device")
    return out


def sharded_offline_phase(store, cfg, params, t_s, t_max: int,
                          n_shards: int, workdir: Path):
    nai = NAIConfig(t_s=t_s, t_min=1, t_max=t_max)
    one = run_full_graph_infer(store, cfg, params, nai,
                               OfflineConfig(ckpt_dir=str(workdir / "d1")))
    many = run_full_graph_infer(store, cfg, params, nai,
                                OfflineConfig(ckpt_dir=str(workdir / "dn")),
                                mesh=make_serving_mesh(n_shards))
    same = (np.array_equal(one.predictions, many.predictions)
            and np.array_equal(one.exit_orders, many.exit_orders))
    out = {"phase": "sharded_offline", "shards": many.stats["shards"],
           "gather_mode": many.stats["gather_mode"], "n": int(store.n),
           "equal": same,
           "pred_diff": int((one.predictions != many.predictions).sum()),
           "exit_diff": int((one.exit_orders != many.exit_orders).sum())}
    log(json.dumps(out))
    check(many.stats["shards"] == n_shards, "offline mesh not sharded")
    check(same, "sharded_offline: D-shard run differs from one device")
    return out


def run_phases(chips: int, *, scale: float = 1.0, seed: int = 0,
               cells=None, offline_t_max: int = OFFLINE_T_MAX):
    """Every phase of a `chips`-chip run; raises on the first failed
    check. `scale`/`cells` shrink the run for tests on the CPU."""
    k = max(c.t_max for c in (cells or ONE_CHIP_CELLS + SHARDED_CELLS))
    k = max(k, offline_t_max)
    t0 = time.perf_counter()
    g, store, cfg, params, t_s = build_setup(scale, seed, k)
    log(json.dumps({"phase": "setup", "dataset": DATASET, "n": int(g.n),
                    "edges": int(store.num_edges), "f": int(store.feat_dim),
                    "classes": int(g.num_classes), "t_s": t_s,
                    "setup_s": time.perf_counter() - t0}))
    results = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if chips == 1:
            for cell in cells or ONE_CHIP_CELLS:
                results.append(serve_phase(g, store, cfg, params, t_s, cell,
                                           seed))
            results.append(offline_phase(store, cfg, params, t_s,
                                         offline_t_max, Path(tmp)))
        else:
            for cell in cells or SHARDED_CELLS:
                results.append(sharded_serve_phase(g, store, cfg, params,
                                                   t_s, cell, seed, chips))
            results.append(sharded_offline_phase(store, cfg, params, t_s,
                                                 offline_t_max, chips,
                                                 Path(tmp)))
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded comparisons")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    log(f"chip_smoke: compile cache at {enable_compile_cache()}")
    log(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                    "count": len(devices), "jax": jax.__version__}))
    try:
        run_phases(args.chips, seed=args.seed)
    except Exception:   # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
