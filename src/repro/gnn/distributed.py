"""Distributed feature propagation on the PropagationBackend stack.

This module used to carry a toy dense `shard_map` segment-sum that shared
zero code with the block-ELL/fused kernels the serving engine actually
runs — a dead end for scaling work. It is now a thin veneer over the
real stack: the whole graph is viewed as its own support (`
graph_as_support`), packed with `repro.gnn.packing.pack_support(
n_shards=D)` into the same shard-major row-partitioned operands serving
uses, and propagated by `repro.gnn.backends.run_propagation` under
shard_map — so ANY registered backend (``segment``, ``block_ell``,
``fused``) runs node-partitioned across the mesh's ``data`` axis, and
full-graph distributed propagation exercises exactly the code path that
serves batches. The old module's numeric oracles (host
`propagated_series` agreement) live on in tests/test_distributed_gnn.py
as cross-checks of the new path.

`distributed_nap_distances` keeps the feature-axis story: per-node
||x - x_inf|| with features sharded over ``model`` — a local partial
sum of squares plus a psum over the feature axis. Serving shards rows
(features are a few hundred wide; rows are the memory axis), but the
helper documents how a feature-sharded deployment would reduce Eq. 8.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.gnn.backends import get_backend, pack_operands, run_propagation
from repro.gnn.packing import (pack_support, shard_batch_perm,
                               step_active_blocks)
from repro.gnn.sampler import Support
from repro.gnn.store import as_store


def graph_as_support(g, r: float = 0.5) -> Support:
    """The whole graph viewed as its own support: every node is a batch
    node at hop 0 and the induced subgraph is the graph itself. Feeding
    this through `pack_support(n_shards=D)` turns full-graph propagation
    into the serving engine's sharded operand problem. `g` is a
    `GraphStore` (or a raw `Graph`, wrapped): the edge list and
    coefficients come from the store's CSR views in CSR (dst-major)
    order, with degrees from the store-build metadata."""
    store = as_store(g)
    n = store.n
    src, dst = store.coo()
    return Support(nodes=np.arange(n, dtype=np.int64),
                   hop=np.zeros(n, np.int32), n_batch=n,
                   src=src, dst=dst,
                   coef=store.edge_coefficients(r),
                   sub_edges=store.num_edges)


def pack_graph(g, n_shards: int, r: float = 0.5,
               spmm_impl: str = "segment", *, nb_bucket=None,
               s_bucket=None, tb_bucket=None, halo: bool = False,
               stationary: bool = False):
    """(backend, PackedSupport) for full-graph propagation.

    Default (`stationary=False`, the `distributed_series` oracle path):
    exits are disabled downstream (t_min > t_max), so the stationary
    operands are inert — zero rank-1 factors for the fused backend, an
    all-zero dense x_inf otherwise. `stationary=True` (the offline
    full-graph NAI driver, `repro.launch.full_graph_infer`) packs the
    REAL Eq. 7 stationary state of the whole graph instead — the exact
    factors `repro.gnn.nai.support_stationary_factors` computes, cast
    f32 the same way the serving path casts them — so the Eq. 8 exit
    decision runs with the same arithmetic serving uses.

    Explicit buckets pin the padding geometry so runs at different
    shard counts are bit-comparable. `halo=True` emits the halo-frame
    metadata for the non-dense gather modes (full-graph partitions of a
    well-mixed graph reference most blocks, so expect a halo fraction
    near 1 — batch serving is where the halo pays)."""
    be = get_backend(spmm_impl)
    store = as_store(g)
    sup = graph_as_support(store, r)
    x0 = np.asarray(store.features, np.float32)
    f = x0.shape[1]
    if stationary:
        from repro.gnn.nai import support_stationary_factors
        c64, s64 = support_stationary_factors(store, sup, x0, r)
        factors = ((c64.astype(np.float32), s64.astype(np.float32))
                   if be.uses_factors else None)
        x_inf = (np.zeros((sup.n_batch, 0), np.float32)
                 if be.uses_factors
                 else (c64[:, None] * s64[None, :]).astype(np.float32))
    else:
        factors = ((np.zeros(sup.n_batch, np.float32),
                    np.zeros(f, np.float32)) if be.uses_factors else None)
        x_inf = np.zeros((sup.n_batch, 0 if be.uses_factors else f),
                         np.float32)
    packed = pack_support(sup, x0, x_inf, nb_bucket=nb_bucket,
                          s_bucket=s_bucket, tb_bucket=tb_bucket,
                          build_tiles=be.uses_tiles,
                          build_edges=be.uses_edges,
                          x_inf_factors=factors, n_shards=n_shards,
                          halo=halo)
    return be, packed


def distributed_series(mesh, g, k: int, r: float = 0.5,
                       spmm_impl: str = "segment", *, nb_bucket=None,
                       s_bucket=None, tb_bucket=None,
                       gather_mode: str = "dense"):
    """[X^(0..k)] computed with the sharded backend step; host-verifiable
    against `repro.gnn.graph.propagated_series`. The mesh's ``data`` axis
    size is the shard count (1 = single-device path). `gather_mode`
    selects the per-step frontier exchange (`repro.gnn.backends`)."""
    g = as_store(g)
    D = int(mesh.shape["data"]) if "data" in mesh.axis_names else 1
    halo = gather_mode != "dense" and D > 1
    be, packed = pack_graph(g, D, r, spmm_impl, nb_bucket=nb_bucket,
                            s_bucket=s_bucket, tb_bucket=tb_bucket,
                            halo=halo)
    # t_min > t_max keeps the threshold sentinel negative on every step,
    # so no node ever exits and the loop is pure propagation. NAIConfig
    # itself rejects that combination (a real serving config with it
    # silently returns -1 predictions), so this propagation-only use
    # passes the loop the raw attributes instead of a validated config.
    nai = SimpleNamespace(t_s=0.0, t_min=k + 1, t_max=k)
    sa = (step_active_blocks(packed.hop_rb, k) if be.uses_tiles else None)
    ops = {key: jnp.asarray(v)
           for key, v in pack_operands(be, packed, sa).items()}
    if be.uses_dense_x_inf:
        ops["x_inf"] = jnp.asarray(packed.x_inf)
    _, series = run_propagation(be, nai, ops, jnp.asarray(packed.x0),
                                packed.n_batch,
                                mesh=mesh if D > 1 else None,
                                gather_mode=gather_mode if halo
                                else "dense")
    # un-permute on the host: a gather of the row-sharded series by a
    # host permutation has no sharding JAX can infer
    series = np.asarray(series)
    if D > 1:
        series = series[:, shard_batch_perm(packed.n_batch, D), :]
    f = g.feat_dim
    return [series[ell, :g.n, :f] for ell in range(k + 1)]


def distributed_nap_distances(mesh, x, x_inf):
    """Per-node ||x - x_inf|| with features sharded over 'model': local
    partial sum of squares + psum over the feature axis."""

    def local(x, xi):
        d2 = jnp.sum(jnp.square(x - xi), axis=1, keepdims=True)
        return jax.lax.psum(d2, "model")

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("data", "model"), P("data", "model")),
                       out_specs=P("data", None))
    return jnp.sqrt(fn(x, x_inf)[:, 0])
