"""Node-Adaptive Inference — Algorithm 1 of the paper.

Two execution paths:

* `infer_batch_host` — the faithful serving path. Real frontier shrinking:
  exited nodes drop out of the supporting set, later propagation steps touch
  fewer edges, and MAC counters track exactly the paper's four procedures
  (stationary state, feature propagation, distance computation,
  classification).

* `infer_batch_masked` — the compiled TPU path. Static shapes, a
  `lax.fori_loop` over orders with per-node active masks; compute saving is
  realized at tile granularity by the Pallas SpMM kernel's block
  predication (repro.kernels.spmm). Numerics match the host path.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.gnn.backends import get_backend, normalize_mesh, run_propagation
from repro.gnn.graph import Graph
from repro.gnn.models import (GNNConfig, apply_classifier,
                              classification_macs)
from repro.gnn.packing import shard_batch_perm
from repro.gnn.sampler import Support, sample_support
from repro.gnn.store import as_store


@dataclasses.dataclass(frozen=True)
class NAIConfig:
    t_s: float = 0.1        # smoothness threshold T_s
    t_min: int = 1          # minimum propagation order
    t_max: int = 2          # maximum propagation order (<= k)
    batch_size: int = 500   # paper evaluates with batch 500

    def __post_init__(self):
        """Fail loudly on configs that would serve garbage silently:
        t_min > t_max makes `infer_batch_host` return all-(-1)
        predictions with exit order 0 and no error. The serving
        front-end's SLO classes construct these configs programmatically
        (`dataclasses.replace` re-runs this check), so a bad tier
        definition must raise at construction, not at serve time."""
        if self.t_min < 1:
            raise ValueError(f"t_min must be >= 1, got {self.t_min}")
        if self.t_min > self.t_max:
            raise ValueError(
                f"t_min ({self.t_min}) > t_max ({self.t_max}): no "
                f"propagation order would ever classify, every "
                f"prediction would be -1")
        if self.t_s < 0:
            raise ValueError(f"t_s must be >= 0, got {self.t_s}")
        if self.batch_size < 1:
            raise ValueError(
                f"batch_size must be >= 1, got {self.batch_size}")


@dataclasses.dataclass
class NAIResult:
    predictions: np.ndarray      # (n_test,) argmax class
    orders: np.ndarray           # (n_test,) exit order per node (Table 4)
    macs: Dict[str, float]       # per-node averaged MACs by procedure
    fp_macs: float               # feature-processing MACs per node
    total_macs: float
    wall_time_s: float
    fp_time_s: float


def _subgraph_spmm(sup: Support, x: np.ndarray, active_nodes: np.ndarray
                   ) -> Tuple[np.ndarray, int]:
    """One propagation step restricted to edges whose destination is in
    `active_nodes` (bool mask over support). Returns (new_x, edges_used)."""
    emask = active_nodes[sup.dst]
    src, dst, coef = sup.src[emask], sup.dst[emask], sup.coef[emask]
    out = x.copy()
    acc = np.zeros_like(x)
    np.add.at(acc, dst, coef[:, None] * x[src])
    out[active_nodes] = acc[active_nodes]
    return out, int(emask.sum())


def support_stationary_factors(g, sup: Support, x0: np.ndarray,
                               r: float) -> Tuple[np.ndarray, np.ndarray]:
    """The stationary state Â^∞ X at the batch rows (Eq. 7) is rank-1 by
    construction; return its factors (c (n_batch,), s (f,)) in float64 so
    x_inf = c ⊗ s. The fused step kernel consumes the factors directly
    (it never materializes the dense x_inf). `g` is a `GraphStore` (or a
    raw `Graph`, wrapped) — degrees come from the store-build metadata,
    gathered at the support rows only."""
    store = as_store(g)
    dt = (np.asarray(store.degrees[sup.nodes]) + 1).astype(np.float64)
    denom = 2.0 * sup.sub_edges + len(sup)
    s = ((dt ** (1.0 - r))[:, None] * x0).sum(axis=0)
    c = (dt[:sup.n_batch] ** r) / denom
    return c, s


def support_stationary_state(g, sup: Support, x0: np.ndarray,
                             r: float) -> np.ndarray:
    """Rank-1 stationary state Â^∞ X at the batch rows (Eq. 7) over the
    sampled subgraph, float64. Shared by the host and compiled serving
    paths so their exit distances use the same arithmetic (the compiled
    path then casts to float32; nodes within f32 rounding of T_s may
    exit one order apart across paths)."""
    c, s = support_stationary_factors(g, sup, x0, r)
    return c[:, None] * s[None, :]


def _needed_mask(sup: Support, active_batch: np.ndarray, remaining_hops: int
                 ) -> np.ndarray:
    """Support nodes within `remaining_hops` of any active batch node —
    the only values the next propagation step must produce."""
    S = len(sup)
    dist = np.full(S, np.iinfo(np.int32).max, np.int32)
    dist[:sup.n_batch][active_batch] = 0
    in_frontier = np.zeros(S, bool)
    in_frontier[:sup.n_batch][active_batch] = True
    # reverse BFS over subgraph edges (dst -> src one hop per level); the
    # per-hop edge filter is an O(E) boolean gather over support ids, not
    # an np.isin merge-scan against the frontier list
    for h in range(1, remaining_hops + 1):
        if not in_frontier.any():
            break
        cand = sup.src[in_frontier[sup.dst]]
        new = cand[dist[cand] > h]
        dist[new] = h
        in_frontier[:] = False
        in_frontier[new] = True
    return dist <= remaining_hops


def infer_batch_host(cfg: GNNConfig, nai: NAIConfig, params, g,
                     batch_nodes: np.ndarray):
    """Algorithm 1 for one batch over a `GraphStore` (or raw `Graph`).
    Returns (preds, orders, macs, fp_time_s, wall_s)."""
    store = as_store(g)
    f = store.feat_dim
    t0 = time.perf_counter()
    sup = sample_support(store, batch_nodes, nai.t_max, cfg.r)
    nb = sup.n_batch
    x = store.gather_features(sup.nodes).astype(np.float32)
    macs = {"stationary": 0.0, "propagation": 0.0, "distance": 0.0,
            "classification": 0.0}

    # line 2: stationary state over the sampled subgraph (Eq. 7, rank-1)
    x_inf = support_stationary_state(g, sup, x, cfg.r)
    macs["stationary"] += len(sup) * f + nb * f

    preds = np.full(nb, -1, np.int64)
    orders = np.zeros(nb, np.int64)
    active = np.ones(nb, bool)
    fp_t0 = time.perf_counter()
    fp_elapsed = 0.0

    series = [x]                                           # X^(0..l) at support
    for l in range(1, nai.t_max + 1):
        t_fp = time.perf_counter()
        needed = _needed_mask(sup, active, nai.t_max - l)
        x, edges = _subgraph_spmm(sup, series[-1], needed)
        series.append(x)
        macs["propagation"] += edges * f
        fp_elapsed += time.perf_counter() - t_fp

        if l < nai.t_min:
            continue
        exit_now = np.zeros(nb, bool)
        if l < nai.t_max:
            t_fp = time.perf_counter()
            d = np.linalg.norm(x[:nb][active] - x_inf[active], axis=1)
            macs["distance"] += active.sum() * f
            fp_elapsed += time.perf_counter() - t_fp
            idx = np.flatnonzero(active)
            exit_now[idx[d < nai.t_s]] = True
        else:
            exit_now = active.copy()
        if exit_now.any():
            feats_l = np.stack([s[:nb][exit_now] for s in series])  # (l+1,e,f)
            z = apply_classifier(cfg, params["cls"][l], jnp.asarray(feats_l), l)
            preds[exit_now] = np.asarray(jnp.argmax(z, -1))
            orders[exit_now] = l
            macs["classification"] += exit_now.sum() * classification_macs(cfg, l)
            active &= ~exit_now
        if not active.any():
            break
    wall = time.perf_counter() - t0
    macs = {k: v / nb for k, v in macs.items()}
    return preds, orders, macs, fp_elapsed, wall


def infer_all(cfg: GNNConfig, nai: NAIConfig, params, g: Graph,
              nodes: Optional[np.ndarray] = None) -> NAIResult:
    nodes = g.test_idx if nodes is None else nodes
    preds = np.empty(len(nodes), np.int64)
    orders = np.empty(len(nodes), np.int64)
    macs_sum: Dict[str, float] = {}
    fp_time = 0.0
    wall = 0.0
    for i in range(0, len(nodes), nai.batch_size):
        b = nodes[i:i + nai.batch_size]
        p, o, m, fp, w = infer_batch_host(cfg, nai, params, g, b)
        preds[i:i + len(b)] = p
        orders[i:i + len(b)] = o
        for k, v in m.items():
            macs_sum[k] = macs_sum.get(k, 0.0) + v * len(b)
        fp_time += fp
        wall += w
    n = len(nodes)
    macs = {k: v / n for k, v in macs_sum.items()}
    fp_macs = macs["propagation"] + macs["distance"]
    return NAIResult(
        predictions=preds, orders=orders, macs=macs, fp_macs=fp_macs,
        total_macs=sum(macs.values()), wall_time_s=wall, fp_time_s=fp_time)


def accuracy(result: NAIResult, g: Graph,
             nodes: Optional[np.ndarray] = None) -> float:
    nodes = g.test_idx if nodes is None else nodes
    return float((result.predictions == g.labels[nodes]).mean())


def order_distribution(result: NAIResult, k: int) -> np.ndarray:
    """Node count per exit order 1..k (paper Table 4)."""
    return np.bincount(result.orders, minlength=k + 1)[1:k + 1]


# --------------------------------------------------------------- jax masked
def infer_batch_masked(cfg: GNNConfig, nai: NAIConfig, params,
                       sup_src, sup_dst, sup_coef, x0, x_inf, n_batch: int,
                       *, spmm_impl: str = "segment", ell=None,
                       step_active=None, x_inf_factors=None, mesh=None,
                       halo_operands=None, gather_mode: str = "dense"):
    """Compiled NAP: fori over orders with exit masks (static shapes).

    Returns (exit_order (nb,), stacked BATCH-ROW features
    (T_max+1, n_batch, f)). The propagation state stays (S, f) inside the
    loop — every support row keeps propagating — but the per-step history
    written to the carry holds only the batch region: classification
    (`make_compiled_infer`) never reads support rows, and with T_max-hop
    supports S is routinely 10–50× n_batch, so carrying S rows per step
    was almost entirely dead HBM traffic.

    This is a thin compatibility wrapper over the `PropagationBackend`
    registry (`repro.gnn.backends`): `spmm_impl` names a registered
    backend — ``segment`` (jnp segment-sum over sup_src/sup_dst/sup_coef),
    ``block_ell`` (Pallas block-ELL kernel over ``ell=(tiles, tile_col,
    valid)`` + the static `step_active` row-block predicate from
    `repro.gnn.packing.step_active_blocks`), or ``fused`` (one-kernel
    SpMM + exit decision, streaming `x_inf_factors=(c, s)` instead of the
    dense x_inf) — and the shared masked loop in
    `repro.gnn.backends.run_propagation` drives its ``step``. Exit
    arithmetic (squared f32 distance vs squared threshold, negative
    threshold = gated off) is identical across backends, so exit orders
    stay bit-consistent even for distances at the threshold.

    `mesh` (a mesh with a ``data`` axis, operands packed with
    ``pack_support(n_shards=D)``) runs the same loop under shard_map;
    results come back in the packed shard-major batch order (undo with
    `repro.gnn.packing.shard_batch_perm`). `gather_mode` selects the
    sharded per-step frontier exchange (``dense`` all_gather, or the
    ``halo``/``alltoall`` frame exchange — those need `halo_operands`,
    the ``halo_*`` metadata dict from a ``pack_support(halo=True)``
    pack; see `repro.gnn.backends`).

    Per-order classification lives in `make_compiled_infer`, which wraps
    this core in one jitted function.
    """
    backend = get_backend(spmm_impl)
    ops = dict(halo_operands or {})
    if backend.uses_tiles:
        if ell is None:
            raise ValueError(f"{spmm_impl} path needs ell="
                             f"(tiles, tile_col, valid)")
        ops["tiles"], ops["tile_col"], ops["valid"] = ell
        ops["step_active"] = jnp.asarray(step_active, jnp.int32)
    if backend.uses_edges:
        ops["src"], ops["dst"], ops["coef"] = sup_src, sup_dst, sup_coef
    if backend.uses_factors:
        if x_inf_factors is None:
            raise ValueError("fused path needs x_inf_factors=(c, s), the "
                             "rank-1 stationary-state factors")
        ops["c_inf"] = jnp.asarray(x_inf_factors[0], x0.dtype)
        ops["s_inf"] = jnp.asarray(x_inf_factors[1], x0.dtype)
    if backend.uses_dense_x_inf:
        ops["x_inf"] = x_inf
    return run_propagation(backend, nai, ops, x0, n_batch, mesh=mesh,
                           gather_mode=gather_mode)


def make_compiled_infer(cfg: GNNConfig, nai: NAIConfig, *,
                        spmm_impl: str = "block_ell",
                        donate: Optional[bool] = None,
                        mesh=None, gather_mode: str = "dense",
                        return_series: bool = False):
    """One jitted function: masked NAP propagation + per-order
    classification (unrolled over orders, selected by exit mask).

    The returned callable takes ``(cls_params, operands, x0, x_inf)`` where
    `operands` is a dict — ``tiles/tile_col/valid/step_active`` for
    ``block_ell``, the same plus ``c_inf/s_inf`` (rank-1 stationary-state
    factors) for ``fused``, ``src/dst/coef`` for ``segment`` (see the
    backend's ``operand_logical`` keys in `repro.gnn.backends`, plus the
    ``halo_*`` metadata for halo gather modes) — and returns
    ``(predictions (nb,), exit_order (nb,))``. All shape specialization
    happens through jax.jit's cache; callers bucket their operand shapes
    (repro.gnn.packing) so repeat batches hit it. The number of traced
    shapes is exposed via the jitted function's ``_cache_size()``.

    `mesh` (any mesh with a ``data`` axis of size D > 1; operands must
    come from ``pack_support(..., n_shards=D)``) runs the propagation
    loop sharded under shard_map — each device owns its round-robin row
    superblocks, the per-step frontier exchange selected by
    `gather_mode` (``dense`` all_gather / ``halo`` static frame gather /
    ``alltoall`` ragged exchange; halo modes need a
    ``pack_support(halo=True)`` pack). Per-order classification ALSO
    runs inside the sharded region — each shard classifies its own batch
    rows and only the (nb,) argmax class ids and exit orders are
    gathered and un-permuted back to the original batch order, so the
    (T_max+1, nb, f) series and the (nb, C) logits are never
    replicated. Predictions are positionally identical to single-device
    serving.

    `donate` hands the per-batch operands (``operands``, ``x0``,
    ``x_inf`` — NOT the classifier params, which persist across batches)
    to XLA as donated buffers, so bucketed repeat batches overwrite the
    previous batch's HBM allocations instead of growing the footprint.
    Default (None) enables donation everywhere except the CPU backend,
    which does not implement donation and would warn per compile. The
    effective donated argnums are exposed as ``run._donate_argnums``.

    `return_series=True` makes the callable return ``(predictions,
    exit_order, series (T_max+1, nb, f))`` — the batch-row propagation
    history in ORIGINAL batch order, which the serving engine's
    propagated-feature cache fills from (steps 1..T_max of a batch row
    are exact global values, since batch rows always propagate at the
    full budget).
    """
    backend = get_backend(spmm_impl)
    tmax = nai.t_max
    mesh = normalize_mesh(mesh)
    n_shards = int(mesh.shape["data"]) if mesh is not None else 1
    if mesh is None:
        gather_mode = "dense"
    if donate is None:
        donate = jax.default_backend() != "cpu"
    donate_argnums = (1, 2, 3) if donate else ()

    def classify(cls_params, exit_order, series):
        """Per-order classification selected by exit mask — row-wise, so
        it runs unchanged on a shard's local batch rows or the full
        batch."""
        with jax.named_scope("nap.classify"):
            preds = jnp.zeros(exit_order.shape, jnp.int32)
            for l in range(1, tmax + 1):
                # series already carries batch rows only
                feats = series[:l + 1, :, :cfg.feat_dim]
                z = apply_classifier(cfg, cls_params[l], feats, l)
                preds = jnp.where(exit_order == l,
                                  jnp.argmax(z, -1).astype(jnp.int32),
                                  preds)
            return preds

    @functools.partial(jax.jit, donate_argnums=donate_argnums)
    def run(cls_params, operands, x0, x_inf):
        nb = x_inf.shape[0]
        ops = dict(operands)
        if backend.uses_dense_x_inf:
            ops["x_inf"] = x_inf
        out = run_propagation(
            backend, nai, ops, x0, nb, mesh=mesh, gather_mode=gather_mode,
            classify=classify, cls_params=cls_params,
            return_series=return_series)
        if return_series:
            exit_order, preds, series = out
        else:
            (exit_order, preds), series = out, None
        if n_shards > 1:
            # shard-major packed order -> original batch order (a static
            # gather; shard_batch_perm[r] is where batch row r landed)
            unperm = shard_batch_perm(nb, n_shards)
            exit_order = exit_order[unperm]
            preds = preds[unperm]
            if series is not None:
                series = series[:, unperm, :]
        if return_series:
            return preds, exit_order, series
        return preds, exit_order

    run._donate_argnums = donate_argnums
    return run
