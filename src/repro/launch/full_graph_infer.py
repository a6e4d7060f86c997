"""Checkpointed, preemption-tolerant full-graph offline NAI inference.

The serving stack answers per-batch queries; the paper's other product
surface is the InferTurbo-style batch job — classify EVERY node of a
`GraphStore` graph, at scales where one pass is a long-running job that
must survive preemption. This driver is that surface:

* the whole graph is viewed as its own support
  (`repro.gnn.distributed.graph_as_support`) and packed with
  `pack_support(n_shards=D)` — the same shard-major CB-superblock row
  partition, operands, and backends serving uses, with the REAL Eq. 7
  stationary state so Eq. 8 adaptive exits run (`pack_graph(
  stationary=True)`);
* propagation runs as **supersteps** — one jitted NAP step per
  dispatch (`repro.gnn.backends.make_superstep`, bit-identical
  arithmetic to the serving fori-loop body) with
  ``gather_mode="alltoall"`` exchanging only referenced CB blocks
  between shards each step;
* after every superstep the full propagation state (padded feature
  state + exit orders) is committed to a CRC32-checksummed, atomically
  updated checkpoint (`repro.launch.checkpoint.CheckpointManager`);
* a killed/preempted run resumes from the last complete superstep and
  produces **bit-identical** final predictions and exit orders — the
  parity contract tests/test_full_graph_infer.py and
  benchmarks/full_graph_infer_bench.py pin.

Failure model (composes with the PR-8 fault machinery):

* **crash / preemption at any instant** — the atomic manifest commit
  means the directory always names a complete superstep prefix; resume
  replays from the highest complete superstep k (work after k is
  re-done, never re-counted twice — supersteps are pure functions of
  the checkpointed state).
* **corrupt checkpoint** (bit rot, torn write) — CRC verification at
  load raises typed `CheckpointCorruption`; the driver falls back one
  superstep at a time until a verifiable chain 0..k loads (0 = cold
  start), counting the fallbacks in `stats`.
* **checkpoint write failure** — logged and tolerated: the run
  continues (the in-memory state is still good); a later crash simply
  resumes from an earlier superstep.
* **hang / straggler** — a per-superstep watchdog
  (`OfflineConfig.watchdog_s`) polls readiness with a deadline and
  deterministically retries the superstep (same inputs, same result);
  supersteps slower than `straggler_factor`× the median of previous
  steps are recorded as stragglers. The ``superstep_hang`` fault stage
  simulates a hung dispatch through the same retry path.

Observability: the run's `stats` dict is the job's record
(`repro.obs`). Spans ``offline.pack``, ``offline.upload`` (superstep
build and operand transfer), ``offline.compute`` and ``offline.fetch``
(per superstep, with the step), ``offline.ckpt``, ``offline.classify``
and ``offline.result`` (the result write) add their seconds to it;
``offline.total`` wraps the whole job, so ``nodes_per_s`` is nodes over
the job's whole wall time. A finished job's record is published as
``"offline.job"``.

Deterministic by construction: supersteps are jitted pure functions,
checkpoint payloads round-trip bit-exactly, classifier params come
from a seeded init — so interrupted == uninterrupted is exact
equality, not a tolerance.

CLI (the bench and the CI smoke job drive this; set ``XLA_FLAGS=
--xla_force_host_platform_device_count=D`` in the environment for
multi-shard runs on CPU)::

    PYTHONPATH=src python -m repro.launch.full_graph_infer \\
        --store STORE_DIR --ckpt CKPT_DIR [--shards D] \\
        [--impl segment] [--gather alltoall] [--t-max 3] \\
        [--t-s 6.0 | --t-s-quantile 0.5] [--crash-after K]

Exit code 17 = simulated preemption (``--crash-after``): the run died
on purpose after committing superstep K; rerun the same command to
resume.
"""
from __future__ import annotations

import dataclasses
import json
import statistics
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding

from repro.gnn.backends import (make_superstep, normalize_mesh,
                                operand_logical, pack_operands)
from repro.gnn.distributed import pack_graph
from repro.gnn.models import apply_classifier
from repro.gnn.packing import shard_batch_perm, step_active_blocks
from repro.gnn.store import as_store
from repro.launch.checkpoint import (CheckpointCorruption, CheckpointError,
                                     CheckpointManager)
from repro.obs import publish, span
from repro.serving.faults import InjectedFault, WatchdogTimeout
from repro.sharding.logical import spec

EXIT_PREEMPTED = 17


class PreemptionSimulated(RuntimeError):
    """Raised by ``crash_after``: the run terminated itself right after
    committing that superstep's checkpoint — the deterministic stand-in
    for a SIGKILL the tests and the bench sweep over."""


@dataclasses.dataclass(frozen=True)
class OfflineConfig:
    """Driver knobs (the NAI/model config rides in separately)."""
    ckpt_dir: str
    spmm_impl: str = "segment"
    gather_mode: str = "alltoall"    # collapses to dense at D=1
    resume: bool = True
    watchdog_s: float = 0.0          # 0 = no per-superstep watchdog
    superstep_retries: int = 2
    straggler_factor: float = 4.0
    crash_after: Optional[int] = None
    cls_chunk: int = 8192            # classification rows per dispatch

    def __post_init__(self):
        if not self.ckpt_dir:
            raise ValueError("ckpt_dir is required: the checkpointed "
                             "driver has no checkpoint-free mode")
        if self.watchdog_s < 0:
            raise ValueError(f"watchdog_s must be >= 0, "
                             f"got {self.watchdog_s}")
        if self.superstep_retries < 0:
            raise ValueError(f"superstep_retries must be >= 0, "
                             f"got {self.superstep_retries}")
        if self.straggler_factor <= 1.0:
            raise ValueError(f"straggler_factor must be > 1, "
                             f"got {self.straggler_factor}")
        if self.cls_chunk < 1:
            raise ValueError(f"cls_chunk must be >= 1, "
                             f"got {self.cls_chunk}")
        if self.crash_after is not None and self.crash_after < 0:
            raise ValueError(f"crash_after must be >= 0, "
                             f"got {self.crash_after}")


@dataclasses.dataclass
class OfflineResult:
    predictions: np.ndarray   # (n,) int32 argmax class ids
    exit_orders: np.ndarray   # (n,) int32 in [t_min, t_max]
    stats: Dict


def first_step_distance_quantile(store, r: float, q: float = 0.5
                                 ) -> float:
    """A data-driven exit threshold: the `q` quantile of the Eq. 8
    distance ||X^(1) - X^inf|| over all nodes, computed with the same
    f32 arithmetic the compiled path uses. Deterministic for a given
    store, so a resumed CLI run recomputes the identical T_s."""
    from repro.gnn.distributed import graph_as_support
    from repro.gnn.nai import support_stationary_factors
    store = as_store(store)
    sup = graph_as_support(store, r)
    x0 = np.asarray(store.features, np.float32)
    c64, s64 = support_stationary_factors(store, sup, x0, r)
    x_inf = (c64[:, None] * s64[None, :]).astype(np.float32)
    contrib = sup.coef[:, None] * x0[sup.src]
    x1 = np.asarray(jax.ops.segment_sum(contrib, sup.dst,
                                        num_segments=store.n))
    d = np.linalg.norm(x1 - x_inf, axis=1)
    return float(np.quantile(d, q))


def _make_classifier(cfg, tmax: int):
    """Jitted per-order classification over one fixed-size row chunk —
    row-wise (each row reads only its own series), so chunking cannot
    perturb the predictions."""

    @jax.jit
    def classify(cls_params, exit_order, series):
        with jax.named_scope("nap.classify"):
            preds = jnp.zeros(exit_order.shape, jnp.int32)
            for l in range(1, tmax + 1):
                feats = series[:l + 1, :, :cfg.feat_dim]
                z = apply_classifier(cfg, cls_params[l], feats, l)
                preds = jnp.where(exit_order == l,
                                  jnp.argmax(z, -1).astype(jnp.int32),
                                  preds)
            return preds

    return classify


def _resume_chain(mgr: CheckpointManager, stats: Dict):
    """Load the longest verifiable checkpoint chain 0..k (descending
    from the newest committed superstep, falling back one superstep per
    corrupt/unreadable checkpoint). Returns (k, {step: payload}) or
    (None, {}) for a cold start."""
    loaded: Dict[int, Dict[str, np.ndarray]] = {}
    committed = set(mgr.steps())
    bad: set = set()
    for k in sorted(committed, reverse=True):
        if any(b <= k for b in bad):
            continue        # a corrupt ancestor poisons everything above
        ok = True
        for j in range(k + 1):
            if j in loaded:
                continue
            if j not in committed:
                ok = False  # gap in the chain: series not reconstructible
                break
            try:
                loaded[j] = mgr.load_step(j)
            except (CheckpointCorruption, CheckpointError) as e:
                stats["corrupt_steps"] += 1
                stats["fallbacks"].append(
                    {"step": j, "error": f"{type(e).__name__}: {e}"})
                bad.add(j)
                ok = False
                break
        if ok:
            return k, loaded
    return None, {}


def run_full_graph_infer(store, cfg, params, nai, ocfg: OfflineConfig,
                         *, mesh=None, fault_plan=None) -> OfflineResult:
    """Classify every node of `store` with NAI, checkpointing at
    superstep granularity. `cfg`/`params` are the trained classifier
    stack (`repro.gnn.models`), `nai` the `NAIConfig`, `ocfg` the
    driver knobs. Returns predictions/exit orders for the n REAL nodes
    in store order, plus run stats (resume point, fallbacks, straggler
    and watchdog counters, span seconds, checkpoint overhead)."""
    stats: Dict = {}
    with span(stats, "offline.total"):
        predictions, exit_orders = _infer(store, cfg, params, nai, ocfg,
                                          mesh, fault_plan, stats)
    stats["nodes_per_s"] = stats["n"] / stats["total_s"]
    publish("offline.job", stats)
    return OfflineResult(predictions=predictions,
                         exit_orders=exit_orders, stats=stats)


def _infer(store, cfg, params, nai, ocfg: OfflineConfig, mesh, fault_plan,
           stats: Dict):
    """The job behind `run_full_graph_infer`: fills `stats` and returns
    ``(predictions, exit_orders)``."""
    store = as_store(store)
    mesh = normalize_mesh(mesh)
    D = int(mesh.shape["data"]) if mesh is not None else 1
    gather_mode = ocfg.gather_mode if D > 1 else "dense"
    tmax = nai.t_max
    injector = (fault_plan.injector()
                if fault_plan is not None and not fault_plan.empty
                else None)

    with span(stats, "offline.pack"):
        be, packed = pack_graph(store, D, cfg.r, ocfg.spmm_impl,
                                halo=gather_mode != "dense", stationary=True)
        sa = (step_active_blocks(packed.hop_rb, tmax)
              if be.uses_tiles else None)
        ops_np = pack_operands(be, packed, sa)
        if be.uses_dense_x_inf:
            ops_np["x_inf"] = packed.x_inf
    nb_pad, n_pad = packed.n_batch, packed.n_pad

    fingerprint = {
        "store": store.name, "n": int(store.n),
        "num_edges": int(store.num_edges),
        "mutation_clock": int(store.mutation_clock),
        "feat_dim": int(store.feat_dim), "shards": D,
        "impl": ocfg.spmm_impl, "gather_mode": gather_mode,
        "r": float(cfg.r), "t_s": float(nai.t_s),
        "t_min": int(nai.t_min), "t_max": int(tmax),
        "nb_pad": int(nb_pad), "n_pad": int(n_pad),
        "f_pad": int(packed.x0.shape[1]),
    }
    mgr = CheckpointManager(ocfg.ckpt_dir, fingerprint=fingerprint,
                            injector=injector)

    stats.update({
        "n": int(store.n), "shards": D, "impl": ocfg.spmm_impl,
        "gather_mode": gather_mode, "t_max": tmax,
        "nb_pad": int(nb_pad), "n_pad": int(n_pad),
        "resumed_from": None, "supersteps_run": 0, "corrupt_steps": 0,
        "fallbacks": [], "ckpt_write_failures": 0,
        "watchdog_retries": 0, "stragglers": [],
        "upload_s": 0.0, "compute_s": 0.0, "fetch_s": 0.0, "ckpt_s": 0.0,
        "classify_s": 0.0, "result_s": 0.0,
    })

    # ---------------------------------------------------------- resume
    snaps: Dict[int, np.ndarray] = {}   # step -> batch-row state X^(l)
    start = None
    if ocfg.resume:
        start, loaded = _resume_chain(mgr, stats)
        if start is not None:
            for j in range(start + 1):
                snaps[j] = loaded[j]["x"][:nb_pad]
            x_host = loaded[start]["x"]
            eo_host = loaded[start]["exit_order"]
    if start is None:
        x_host = packed.x0
        eo_host = np.zeros(nb_pad, np.int32)
        snaps[0] = x_host[:nb_pad]
        _save(mgr, 0, x_host, eo_host, stats)
        start = 0
    stats["resumed_from"] = int(start)

    # ------------------------------------------------- superstep loop
    with span(stats, "offline.upload"):
        step_fn = make_superstep(be, nai, n_batch=nb_pad, n_rows=n_pad,
                                 mesh=mesh, gather_mode=gather_mode)
        if mesh is not None:
            logical = operand_logical(be, gather_mode)
            ops_dev = {k: jax.device_put(
                v, NamedSharding(mesh, spec(*logical[k], mesh=mesh)))
                for k, v in ops_np.items()}
            row_sh = NamedSharding(mesh, spec("row_shard", None, mesh=mesh))
            eo_sh = NamedSharding(mesh, spec("row_shard", mesh=mesh))
            x_dev = jax.device_put(np.asarray(x_host), row_sh)
            eo_dev = jax.device_put(np.asarray(eo_host), eo_sh)
        else:
            ops_dev = {k: jnp.asarray(v) for k, v in ops_np.items()}
            x_dev, eo_dev = jnp.asarray(x_host), jnp.asarray(eo_host)
    durations: List[float] = []
    if ocfg.crash_after is not None and ocfg.crash_after <= start:
        raise PreemptionSimulated(
            f"simulated preemption after superstep {start} "
            f"(crash_after={ocfg.crash_after} already committed)")

    for l in range(start + 1, tmax + 1):
        before = stats["compute_s"]
        with span(stats, "offline.compute", step=l):
            x_dev, eo_dev = _superstep(step_fn, ops_dev, x_dev, eo_dev, l,
                                       ocfg, injector, stats)
        dur = stats["compute_s"] - before
        if len(durations) >= 2:
            med = statistics.median(durations)
            if dur > ocfg.straggler_factor * med:
                stats["stragglers"].append(
                    {"step": l, "s": round(dur, 6),
                     "median_s": round(med, 6)})
        durations.append(dur)
        stats["supersteps_run"] += 1
        with span(stats, "offline.fetch", step=l):
            x_host = np.asarray(x_dev)
            eo_host = np.asarray(eo_dev)
            snaps[l] = x_host[:nb_pad]
        _save(mgr, l, x_host, eo_host, stats)
        if ocfg.crash_after is not None and l >= ocfg.crash_after:
            raise PreemptionSimulated(
                f"simulated preemption after superstep {l}")

    # -------------------------------------------------- classification
    with span(stats, "offline.classify"):
        eo_final = np.where(eo_host == 0, tmax, eo_host).astype(np.int32)
        f_pad = packed.x0.shape[1]
        series = np.stack([snaps[j] for j in range(tmax + 1)])
        classify = _make_classifier(cfg, tmax)
        chunk = min(ocfg.cls_chunk, nb_pad)
        preds = np.empty(nb_pad, np.int32)
        for lo in range(0, nb_pad, chunk):
            hi = min(lo + chunk, nb_pad)
            s_blk = series[:, lo:hi]
            e_blk = eo_final[lo:hi]
            if hi - lo < chunk:     # pad the tail to the compiled shape
                s_blk = np.concatenate(
                    [s_blk, np.zeros((tmax + 1, chunk - (hi - lo), f_pad),
                                     s_blk.dtype)], axis=1)
                e_blk = np.concatenate(
                    [e_blk, np.full(chunk - (hi - lo), tmax, np.int32)])
            out = classify(params["cls"], jnp.asarray(e_blk),
                           jnp.asarray(s_blk))
            preds[lo:hi] = np.asarray(out)[:hi - lo]
        if D > 1:
            unperm = shard_batch_perm(nb_pad, D)
            preds = preds[unperm]
            eo_final = eo_final[unperm]
        n = store.n
        predictions = np.ascontiguousarray(preds[:n])
        exit_orders = np.ascontiguousarray(eo_final[:n])
    stats["exit_histogram"] = np.bincount(
        exit_orders, minlength=tmax + 1).tolist()
    stats["ckpt_bytes"] = mgr.total_bytes()
    busy = stats["compute_s"] + stats["ckpt_s"]
    stats["ckpt_overhead_frac"] = (stats["ckpt_s"] / busy
                                   if busy > 0 else 0.0)
    stats["node_steps_per_s"] = (n * stats["supersteps_run"]
                                 / stats["compute_s"]
                                 if stats["compute_s"] > 0 else 0.0)
    with span(stats, "offline.result"):
        mgr.save_result({"predictions": predictions,
                         "exit_orders": exit_orders})
    if injector is not None:
        stats["injected"] = injector.summary()
    return predictions, exit_orders


def _save(mgr: CheckpointManager, l: int, x_host, eo_host, stats: Dict):
    """Commit superstep `l`'s state. A failed write is tolerated and
    counted: the in-memory state is still good, and a crash later simply
    resumes from an earlier committed superstep."""
    with span(stats, "offline.ckpt", step=l):
        try:
            mgr.save_step(l, {"x": x_host, "exit_order": eo_host})
        except (InjectedFault, CheckpointError, OSError) as e:
            stats["ckpt_write_failures"] += 1
            stats["fallbacks"].append(
                {"step": l, "error": f"write: {type(e).__name__}: {e}"})


def _superstep(step_fn, ops_dev, x_dev, eo_dev, l: int, ocfg: OfflineConfig,
               injector, stats: Dict):
    """Run superstep `l` to completion, retrying a hung attempt (the
    ``superstep_hang`` fault or the watchdog) up to
    ``ocfg.superstep_retries`` times. Returns the new device state."""
    for attempt in range(ocfg.superstep_retries + 1):
        last = attempt == ocfg.superstep_retries
        if injector is not None \
                and injector.fire("superstep_hang") is not None:
            # simulated hung dispatch: the watchdog path declares the
            # attempt dead and retries deterministically
            stats["watchdog_retries"] += 1
            if last:
                raise WatchdogTimeout(
                    f"superstep {l} hung on every attempt "
                    f"({ocfg.superstep_retries + 1})")
            continue
        x_new, eo_new = step_fn(ops_dev, x_dev, eo_dev, jnp.int32(l))
        if ocfg.watchdog_s > 0:
            deadline = time.monotonic() + ocfg.watchdog_s
            while not (x_new.is_ready() and eo_new.is_ready()):
                if time.monotonic() > deadline:
                    break
                time.sleep(1e-4)
            if not (x_new.is_ready() and eo_new.is_ready()):
                stats["watchdog_retries"] += 1
                if last:
                    raise WatchdogTimeout(
                        f"superstep {l} exceeded the {ocfg.watchdog_s}s "
                        f"watchdog on every attempt")
                continue
        jax.block_until_ready((x_new, eo_new))
        return x_new, eo_new


# ----------------------------------------------------------------- CLI
def _main(argv=None) -> int:
    import argparse

    from repro.gnn.models import GNNConfig, init_classifiers
    from repro.gnn.nai import NAIConfig
    from repro.gnn.store import MmapStore
    from repro.launch.mesh import make_serving_mesh
    from repro.runtime import enable_compile_cache

    ap = argparse.ArgumentParser(
        description="Offline checkpointed full-graph NAI inference "
                    "over an on-disk GraphStore. Rerun the identical "
                    "command after a crash/preemption to resume from "
                    "the last complete superstep (bit-identical "
                    "results).")
    ap.add_argument("--store", required=True, help="MmapStore directory")
    ap.add_argument("--ckpt", required=True, help="checkpoint directory")
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--impl", default="segment",
                    choices=("segment", "block_ell", "fused"))
    ap.add_argument("--gather", default="alltoall",
                    choices=("dense", "halo", "alltoall"))
    ap.add_argument("--t-max", type=int, default=3)
    ap.add_argument("--t-min", type=int, default=1)
    ap.add_argument("--t-s", type=float, default=None)
    ap.add_argument("--t-s-quantile", type=float, default=None,
                    help="derive T_s from this quantile of the "
                         "first-step exit distance (deterministic, so "
                         "resumed runs agree)")
    ap.add_argument("--r", type=float, default=0.5)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0,
                    help="classifier init seed (resume recomputes the "
                         "identical params)")
    ap.add_argument("--crash-after", type=int, default=None,
                    help=f"simulate preemption right after committing "
                         f"this superstep (exit code {EXIT_PREEMPTED})")
    ap.add_argument("--no-resume", action="store_true",
                    help="ignore existing checkpoints (fresh run)")
    ap.add_argument("--watchdog-s", type=float, default=0.0)
    ap.add_argument("--out-json", default="",
                    help="also write the run summary JSON here")
    args = ap.parse_args(argv)

    enable_compile_cache()
    store = MmapStore(args.store)
    if args.t_s is None:
        q = 0.5 if args.t_s_quantile is None else args.t_s_quantile
        t_s = first_step_distance_quantile(store, args.r, q)
    else:
        t_s = args.t_s
    cfg = GNNConfig("sgc", store.feat_dim, store.num_classes,
                    k=args.t_max, r=args.r, hidden=args.hidden,
                    mlp_layers=2)
    params = {"cls": init_classifiers(cfg, jax.random.PRNGKey(args.seed))}
    nai = NAIConfig(t_s=t_s, t_min=args.t_min, t_max=args.t_max)
    mesh = make_serving_mesh(args.shards) if args.shards > 1 else None
    ocfg = OfflineConfig(ckpt_dir=args.ckpt, spmm_impl=args.impl,
                         gather_mode=args.gather,
                         resume=not args.no_resume,
                         watchdog_s=args.watchdog_s,
                         crash_after=args.crash_after)
    try:
        res = run_full_graph_infer(store, cfg, params, nai, ocfg,
                                   mesh=mesh)
    except PreemptionSimulated as e:
        print(f"PREEMPTED: {e}", flush=True)
        return EXIT_PREEMPTED
    summary = {"t_s": t_s, **res.stats}
    line = json.dumps(summary, sort_keys=True)
    print(f"OFFLINE_SUMMARY {line}", flush=True)
    if args.out_json:
        with open(args.out_json, "w") as fh:
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(_main())
