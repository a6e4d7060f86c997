"""Propagation backends: one interface over every SpMM implementation.

Before this module the three `spmm_impl` choices (``segment`` edge-list
segment-sum, ``block_ell`` Pallas SpMM + jnp exit distance, ``fused``
one-kernel SpMM+exit) each carried their own branch in
`repro.gnn.nai.infer_batch_masked`, their own operand-dict construction in
the serving engine, and no story for running across devices. Here every
implementation is a `PropagationBackend` registered in `BACKENDS`:

* ``step(operands, x_full, node_active, active_rb, ts2, ...)`` — ONE NAP
  propagation step: consume the (gathered) feature state, produce the
  propagated rows this backend owns plus the per-batch-node exit flags.
  The exit arithmetic is pinned to squared-f32 distance vs the squared
  threshold (negative threshold = exits disabled this step), exactly what
  the fused kernel computes in VMEM, so exit orders are bit-consistent
  across backends.
* `run_propagation` — the ONE masked NAP fori-loop (previously
  triplicated): carries ``(x, series, exit_order, live)``, asks the
  backend for each step, and runs either single-device or **sharded**
  under `shard_map` when given a mesh with a ``data`` axis.

Sharded execution (the scale story — supports larger than one device's
HBM): `repro.gnn.packing.pack_support(n_shards=D)` splits the padded
support rows round-robin by CB-row superblock across the ``data`` axis
(shard-major layout, every shard the same static shapes). Each step the
frontier rows a shard reads are rebuilt across node shards (features
stay unsharded: serving feature dims are a few hundred, rows are the
memory axis), each shard updates only the row blocks it owns, computes
exit distances for its own batch rows, and the global
any-batch-node-live flag is reduced with a `psum`. Because the packer
permutes whole CB superblocks, every tile keeps its single-device
contents and in-row-block accumulation order, so sharded propagation is
bit-identical to single-device — the parity oracle the sharded tests
hold us to. Operand partition specs are expressed through the logical
axis system (`repro.sharding.logical.spec`, rules ``row_shard`` /
``halo_shard``) so the same backend lowers on any mesh that names a
``data`` axis (e.g. `repro.launch.mesh.make_serving_mesh`).

**Frontier exchange** (``gather_mode=``): a shard's tiles only read the
CB column blocks named in its ``tile_col``, and that set is static at
pack time, so the exchange compiles to fixed shapes:

* ``"dense"`` — the PR-4 reference: `all_gather` the full (S_pad, f)
  frontier every step; interconnect bytes scale with total support
  size. Operands must be packed WITHOUT halo metadata (global
  coordinates).
* ``"halo"`` — operands packed with ``pack_support(halo=True)``: the
  loop still all-gathers, then each shard assembles its (H_pad·CB, f)
  halo frame with a static block gather and every backend consumes the
  frame instead of the full frontier. Compute-side win everywhere (the
  kernels' x operand shrinks to the true boundary); the interconnect
  win needs the ragged exchange below.
* ``"alltoall"`` — same halo pack; each shard sends exactly the blocks
  its peers' frames reference via one `jax.lax.all_to_all` per step
  (uniform (D·B_pad, CB, f) send/recv buffers from the packer's
  per-pair send lists), so interconnect bytes scale with the true
  boundary size instead of S_pad·D.

Frame rows are bit-identical copies of the dense frontier rows and tile
slot order never moves, so all three modes produce BIT-identical
predictions and exit orders (tests/test_sharded_serving.py).

Per-order classification also runs under shard_map when
`run_propagation` is given a ``classify`` hook: each shard classifies
its own batch rows and only the (nb,) argmax class ids and exit orders
leave the sharded region — the (T_max+1, nb, f) series and (nb, C)
logits are never replicated.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.nap_step import nap_step_fused
from repro.kernels.spmm import spmm_block_ell
from repro.kernels.spmm.kernel import CB, RB
from repro.sharding.logical import spec

BACKENDS: Dict[str, "PropagationBackend"] = {}

GATHER_MODES = ("dense", "halo", "alltoall")

# halo-exchange operand specs (pack_support(halo=True) metadata): the
# leading axis is the owning shard, so every array block-slices to its
# shard exactly like the edge lists. These keys ride next to any
# backend's operand_logical — the backends themselves never see them
# (run_propagation pops them to build the frame gather).
HALO_LOGICAL: Dict[str, tuple] = {
    "halo_src_shard": ("halo_shard", None),
    "halo_src_block": ("halo_shard", None),
    "halo_send_block": ("halo_shard", None, None),
    "halo_frame_src": ("halo_shard", None),
}

# propagated-feature-cache seed operands (pack_support(seeds=...) packs):
# shard-stacked like the edge lists — the leading axis is the owning
# shard, seed row ids are shard-LOCAL. The NAP loop scatters
# `seed_vals[l-1]` over `seed_rows` after every step; backends never see
# these keys (`_masked_loop` pops them).
SEED_LOGICAL: Dict[str, tuple] = {
    "seed_rows": ("row_shard", None),
    "seed_vals": ("row_shard", None, None, None),
}


def operand_logical(backend: "PropagationBackend",
                    gather_mode: str = "dense",
                    seeds: bool = False) -> Dict[str, tuple]:
    """The backend's operand key -> logical dims table, grown with the
    halo specs for halo gather modes and the cache-seed specs for
    seeded packs — the ONE table the engine's device placement and
    `run_propagation`'s shard_map in_specs share."""
    table = dict(backend.operand_logical)
    if gather_mode != "dense":
        table.update(HALO_LOGICAL)
    if seeds:
        table.update(SEED_LOGICAL)
    return table


def register_backend(cls):
    """Class decorator: instantiate and register under ``cls.name``."""
    BACKENDS[cls.name] = cls()
    return cls


def get_backend(name: str) -> "PropagationBackend":
    if name not in BACKENDS:
        raise ValueError(f"unknown spmm_impl {name!r} "
                         f"(registered: {sorted(BACKENDS)})")
    return BACKENDS[name]


def normalize_mesh(mesh):
    """The ONE degenerate-mesh policy (every sharded entry point routes
    through here): None stays None, a mesh must name a ``data`` axis,
    and a data axis of size 1 collapses to None — the plain
    single-device path, so 1-device meshes cost no shard_map overhead
    and no CB*D batch padding."""
    if mesh is None:
        return None
    if "data" not in mesh.axis_names:
        raise ValueError(f"sharded propagation needs a 'data' mesh axis, "
                         f"got {mesh.axis_names}")
    return mesh if int(mesh.shape["data"]) > 1 else None


def _distance_exits(out, x_inf, ts2, n_batch):
    """Squared-f32 exit decision over the batch region — the arithmetic
    contract shared with the fused kernel (ts2 < 0 disables exits, since
    d2 >= 0 always)."""
    with jax.named_scope("nap.exit"):
        d2 = jnp.sum((out[:n_batch] - x_inf) ** 2, axis=1)
        return d2 < ts2


class PropagationBackend:
    """One NAP propagation step behind a uniform contract.

    Class attributes drive the rest of the stack generically:

    * ``uses_tiles`` — consumes block-ELL operands (``tiles``,
      ``tile_col``, ``valid``) plus the static ``step_active`` row-block
      predicate; the packer must build tiles.
    * ``uses_edges`` — consumes the bucket-padded edge list
      (``src``/``dst``/``coef``); the packer must build edges. Sharded,
      the edge arrays carry a leading shard axis and ``dst`` holds
      shard-LOCAL row ids.
    * ``uses_factors`` — consumes the rank-1 stationary-state factors
      (``c_inf``/``s_inf``) instead of a dense ``x_inf``.
    * ``uses_dense_x_inf`` — the exit distance is computed outside the
      kernel against the dense ``x_inf`` operand.
    * ``operand_logical`` — operand key -> logical dim names for the
      SHARDED layout (``row_shard`` = partitioned over the mesh's
      ``data`` axis, None = replicated); consumed by `run_propagation`'s
      shard_map specs and the engine's sharded device placement.
    """
    name: str = ""
    uses_tiles = False
    uses_edges = False
    uses_factors = False
    uses_dense_x_inf = True
    operand_logical: Dict[str, tuple] = {}

    def validate(self, operands: dict, x0, n_batch: int) -> None:
        """Raise ValueError on operand-contract violations (cheap, static
        shape checks only)."""

    def step(self, ops: dict, x_full, node_active, active_rb, ts2, *,
             n_batch: int, n_rows: int
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """One propagation + exit-decision step.

        ``x_full`` is the FULL (possibly all-gathered) feature state;
        ``node_active`` (n_batch,) int32 not-yet-exited flags;
        ``active_rb`` the (n_rb_local,) row-block predicate (None for
        backends without tiles); ``ts2`` the squared threshold (negative
        = exits disabled). Returns ``(x_out (n_rows, f), exits
        (n_batch,) bool)`` where ``x_out`` covers exactly the rows this
        shard owns.
        """
        raise NotImplementedError


@register_backend
class SegmentBackend(PropagationBackend):
    """jnp segment-sum over the edge list; every owned row updated every
    step (no tile predication — the baseline the kernels are measured
    against)."""
    name = "segment"
    uses_edges = True
    operand_logical = {
        "src": ("row_shard", None),
        "dst": ("row_shard", None),
        "coef": ("row_shard", None),
        "x_inf": ("row_shard", None),
    }

    def step(self, ops, x_full, node_active, active_rb, ts2, *,
             n_batch, n_rows):
        contrib = ops["coef"][:, None] * x_full[ops["src"]]
        out = jax.ops.segment_sum(contrib, ops["dst"], num_segments=n_rows)
        return out, _distance_exits(out, ops["x_inf"], ts2, n_batch)


@register_backend
class BlockEllBackend(PropagationBackend):
    """Pallas block-ELL SpMM kernel + separate jnp exit distance (one
    extra HBM read of the batch region per step)."""
    name = "block_ell"
    uses_tiles = True
    operand_logical = {
        "tiles": ("row_shard", None, None, None),
        "tile_col": ("row_shard", None),
        "valid": ("row_shard", None),
        "step_active": (None, "row_shard"),
        "x_inf": ("row_shard", None),
    }

    def step(self, ops, x_full, node_active, active_rb, ts2, *,
             n_batch, n_rows):
        out = spmm_block_ell(ops["tiles"], ops["tile_col"], ops["valid"],
                             active_rb, x_full)
        return out, _distance_exits(out, ops["x_inf"], ts2, n_batch)


@register_backend
class FusedBackend(PropagationBackend):
    """Fused NAP step kernel: SpMM accumulation, exit distance (rebuilt
    from the rank-1 stationary factors in VMEM) and per-node exit flags
    in one grid pass — the propagated block never round-trips HBM
    between matmul and distance check."""
    name = "fused"
    uses_tiles = True
    uses_factors = True
    uses_dense_x_inf = False
    operand_logical = {
        "tiles": ("row_shard", None, None, None),
        "tile_col": ("row_shard", None),
        "valid": ("row_shard", None),
        "step_active": (None, "row_shard"),
        "c_inf": ("row_shard",),
        "s_inf": (None,),
    }

    def validate(self, operands, x0, n_batch):
        S, f = x0.shape
        if n_batch % RB or S % CB:
            raise ValueError(
                f"fused path needs packed operands: n_batch {n_batch} "
                f"% RB, rows {S} % CB must be 0 (see repro.gnn.packing)")
        if "c_inf" not in operands or "s_inf" not in operands:
            raise ValueError("fused path needs x_inf_factors=(c, s), the "
                             "rank-1 stationary-state factors")
        c = operands["c_inf"].reshape(-1)
        s = operands["s_inf"].reshape(-1)
        if c.shape[0] != n_batch or s.shape[0] != f:
            raise ValueError(f"fused path needs factors padded to "
                             f"({n_batch},) and ({f},), got "
                             f"{c.shape} {s.shape}")

    def step(self, ops, x_full, node_active, active_rb, ts2, *,
             n_batch, n_rows):
        c_inf = ops["c_inf"].reshape(-1, 1).astype(x_full.dtype)
        s_inf = ops["s_inf"].reshape(1, -1).astype(x_full.dtype)
        out, exits, _blk_still = nap_step_fused(
            ops["tiles"], ops["tile_col"], ops["valid"], active_rb, x_full,
            c_inf, s_inf, node_active[:, None], ts2.reshape(1))
        # any(blk_still) == any(node_active & ~exits): the generic loop
        # recovers the live flag from exit_order, so blk_still is not
        # threaded out (it exists for two_launch parity of the raw kernel)
        return out, exits[:, 0] != 0


def pack_operands(backend: PropagationBackend, packed,
                  step_active=None) -> dict:
    """Host-side operand dict for a `repro.gnn.packing.PackedSupport`,
    keyed exactly as the backend's ``operand_logical`` (minus the dense
    ``x_inf``, which travels as its own argument through
    `make_compiled_infer`). One place instead of per-impl branches in
    every consumer (serving engine, distributed propagation, benches)."""
    ops = {}
    if backend.uses_tiles:
        if step_active is None:
            raise ValueError(f"{backend.name} needs the step_active "
                             f"row-block predicate")
        ops.update(tiles=packed.tiles, tile_col=packed.tile_col,
                   valid=packed.valid, step_active=step_active)
    if backend.uses_edges:
        ops.update(src=packed.src, dst=packed.dst, coef=packed.coef)
    if backend.uses_factors:
        ops.update(c_inf=packed.c_inf, s_inf=packed.s_inf)
    if packed.halo_src_shard is not None:
        ops.update(halo_src_shard=packed.halo_src_shard,
                   halo_src_block=packed.halo_src_block,
                   halo_send_block=packed.halo_send_block,
                   halo_frame_src=packed.halo_frame_src)
    if packed.seed_rows is not None:
        ops.update(seed_rows=packed.seed_rows, seed_vals=packed.seed_vals)
    return ops


# ------------------------------------------------------------ the loop
def _propagate(backend, ops, x_full, node_active, active_rb, ts2,
               n_batch, n_rows):
    """One backend step under the ``nap.propagate`` scope, so a device
    op of the trace maps to its NAP phase by the innermost ``nap.*``
    scope in its name: the exit distance the step computes outside a
    kernel is ``nap.exit`` (`_distance_exits`); the fused kernel decides
    exits inside ``nap.propagate``."""
    with jax.named_scope("nap.propagate"):
        return backend.step(ops, x_full, node_active, active_rb, ts2,
                            n_batch=n_batch, n_rows=n_rows)


def _masked_loop(backend, nai, ops, x0, n_batch, n_rows, gather, any_fn):
    """The ONE masked NAP fori-loop (previously triplicated per impl).

    Carries ``(x (n_rows, f), series (T_max+1, n_batch, f), exit_order
    (n_batch,), live ())`` where every row count is LOCAL to the shard
    when running under shard_map (`gather` rebuilds the full frontier,
    `any_fn` reduces the live flag across shards). Exit orders of 0
    after the loop mean never-exited and collapse to T_max.
    """
    tmax = nai.t_max
    f = x0.shape[1]
    ts2_on = jnp.float32(nai.t_s) ** 2
    sa = ops.get("step_active")
    seed_rows = ops.pop("seed_rows", None)
    seed_vals = ops.pop("seed_vals", None)
    if seed_rows is not None and seed_vals.shape[0] < tmax:
        # static guard: jnp dynamic indexing CLAMPS out-of-range, so a
        # too-short series would silently replay its last step
        raise ValueError(f"seed_vals covers {seed_vals.shape[0]} steps, "
                         f"loop needs {tmax}")

    def body(l, carry):
        x, series, exit_order, live = carry
        node_active = (exit_order == 0).astype(jnp.int32)
        # T_min/T_max gating via the threshold sentinel: a negative
        # squared threshold means nobody exits this step (shared with the
        # fused kernel, so gating arithmetic is identical across backends)
        ts2 = jnp.where((l >= nai.t_min) & (l < tmax), ts2_on,
                        jnp.float32(-1.0))
        active_rb = sa[l - 1] * live if sa is not None else None
        x, exits = _propagate(backend, ops, gather(x), node_active,
                              active_rb, ts2, n_batch, n_rows)
        with jax.named_scope("nap.exit"):
            exit_order = jnp.where((node_active != 0) & exits, l,
                                   exit_order)
            live = any_fn(exit_order == 0)
        # cache-hit rows: overwrite whatever the (edge-dropped) step left
        # there with the stored X^(l) values, so the NEXT step's gather
        # reads exact propagated features. Pad ids point one past the row
        # range — dropped. Batch rows are never seeded, so exits/series
        # (batch region only) are unaffected by scatter order.
        if seed_rows is not None:
            x = x.at[seed_rows].set(seed_vals[l - 1], mode="drop")
        # per-step history carries batch rows only (classification never
        # reads support rows; see ROADMAP "Pipelined serving")
        series = series.at[l].set(x[:n_batch])
        return x, series, exit_order, live

    series = jnp.zeros((tmax + 1, n_batch, f),
                       x0.dtype).at[0].set(x0[:n_batch])
    exit_order = jnp.zeros((n_batch,), jnp.int32)
    _, series, exit_order, _ = jax.lax.fori_loop(
        1, tmax + 1, body, (x0, series, exit_order, jnp.int32(1)))
    exit_order = jnp.where(exit_order == 0, tmax, exit_order)
    return exit_order, series


def _halo_gather(gather_mode: str, halo: dict, rows_loc: int):
    """Build the per-step frame-assembly `gather` from a shard's (local)
    halo metadata. Both modes return the (H_pad*CB, f) halo frame whose
    rows are bit-identical copies of the dense frontier rows the shard's
    frame-local tile_col/src indices name."""
    n_cb_loc = rows_loc // CB
    if gather_mode == "halo":
        # first implementation: the full frontier is still all-gathered,
        # then the frame is a static block gather out of it — the
        # kernels' x operand shrinks to the frame; the interconnect win
        # needs "alltoall"
        gblock = (halo["halo_src_shard"].astype(jnp.int32) * n_cb_loc
                  + halo["halo_src_block"].astype(jnp.int32))

        def gather(x):
            f = x.shape[-1]
            x_full = jax.lax.all_gather(x, "data", axis=0, tiled=True)
            return x_full.reshape(-1, CB, f)[gblock].reshape(-1, f)

        return gather

    # ragged exchange: each shard ships exactly the blocks its peers'
    # frames reference — one uniform (D*B_pad, CB, f) all_to_all; the
    # receive side drops into frame order via the packed recv slots
    send_idx = halo["halo_send_block"].astype(jnp.int32).reshape(-1)
    frame_src = halo["halo_frame_src"].astype(jnp.int32)

    def gather(x):
        f = x.shape[-1]
        send = x.reshape(n_cb_loc, CB, f)[send_idx]
        recv = jax.lax.all_to_all(send, "data", split_axis=0,
                                  concat_axis=0, tiled=True)
        return recv[frame_src].reshape(-1, f)

    return gather


def make_superstep(backend: PropagationBackend, nai, *, n_batch: int,
                   n_rows: int, mesh=None, gather_mode: str = "dense"):
    """One NAP propagation step as its own jitted callable — the unit
    of work of the offline full-graph driver
    (`repro.launch.full_graph_infer`), which checkpoints state between
    steps instead of running the whole fori-loop in one dispatch.

    Returns ``step(operands, x, exit_order, l) -> (x_new, exit_order)``
    replicating EXACTLY one iteration of `_masked_loop`'s body — the
    same threshold-sentinel T_min/T_max gating, the same row-block
    predicate (``step_active[l-1] * live``), the same exit-order
    update — so a chain of superstep calls from the same initial state
    is bit-identical to itself across interruption/resume (the driver's
    parity contract). The loop carry's ``live`` flag is recovered from
    the incoming ``exit_order`` (any batch row still at 0, psum-reduced
    across shards), which equals the value the fori-loop would carry in
    from the previous iteration. ``l`` is a traced int32 scalar, so ONE
    compilation serves every superstep of a run.

    Sharding follows `run_propagation`'s contract: with a mesh whose
    ``data`` axis is D > 1, operands must come from
    ``pack_support(n_shards=D)`` (plus halo metadata for non-dense
    ``gather_mode``), `x`/`exit_order` are in shard-major packed order,
    and outputs come back global in the same order. Cache-seed operands
    are not supported here (the offline driver packs without them).
    """
    if gather_mode not in GATHER_MODES:
        raise ValueError(f"unknown gather_mode {gather_mode!r} "
                         f"(one of {GATHER_MODES})")
    mesh = normalize_mesh(mesh)
    tmax = nai.t_max
    ts2_on = jnp.float32(nai.t_s) ** 2

    def body(ops, x, exit_order, l, gather, any_fn, nb, rows):
        node_active = (exit_order == 0).astype(jnp.int32)
        ts2 = jnp.where((l >= nai.t_min) & (l < tmax), ts2_on,
                        jnp.float32(-1.0))
        live = any_fn(exit_order == 0)
        sa = ops.get("step_active")
        active_rb = sa[l - 1] * live if sa is not None else None
        x, exits = _propagate(backend, ops, gather(x), node_active,
                              active_rb, ts2, nb, rows)
        with jax.named_scope("nap.exit"):
            exit_order = jnp.where((node_active != 0) & exits, l,
                                   exit_order)
        return x, exit_order

    if mesh is None:
        @jax.jit
        def step_single(operands, x, exit_order, l):
            backend.validate(operands, x, n_batch)
            return body(dict(operands), x, exit_order, l,
                        gather=lambda x: x,
                        any_fn=lambda m: jnp.any(m).astype(jnp.int32),
                        nb=n_batch, rows=n_rows)

        return step_single

    D = int(mesh.shape["data"])
    if n_batch % (CB * D) or n_rows % (CB * D):
        raise ValueError(
            f"sharded operands must be packed with n_shards={D}: "
            f"n_batch {n_batch} and rows {n_rows} must be multiples of "
            f"CB*D = {CB * D}")
    nb_loc, rows_loc = n_batch // D, n_rows // D
    logical = operand_logical(backend, gather_mode)
    keys = tuple(logical)
    in_specs = tuple(spec(*logical[k], mesh=mesh) for k in keys) + (
        spec("row_shard", None, mesh=mesh),   # x
        spec("row_shard", mesh=mesh),         # exit_order
        spec(mesh=mesh))                      # l (replicated scalar)
    out_specs = (spec("row_shard", None, mesh=mesh),
                 spec("row_shard", mesh=mesh))

    def local_fn(*args):
        (x, exit_order, l), args = args[-3:], args[:-3]
        ops = dict(zip(keys, args))
        if gather_mode == "dense":
            def gather(x):
                return jax.lax.all_gather(x, "data", axis=0, tiled=True)
        else:
            gather = _halo_gather(
                gather_mode, {k: ops.pop(k)[0] for k in HALO_LOGICAL},
                rows_loc)
        if backend.uses_edges:
            ops.update({k: ops[k][0] for k in ("src", "dst", "coef")})
        backend.validate(ops, x, nb_loc)
        return body(ops, x, exit_order, l, gather=gather,
                    any_fn=lambda m: (jax.lax.psum(
                        jnp.any(m).astype(jnp.int32), "data") > 0
                        ).astype(jnp.int32),
                    nb=nb_loc, rows=rows_loc)

    # check_vma=False for the same reason as run_propagation: parity
    # tests, not the replication checker, are the correctness oracle
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    @jax.jit
    def step_sharded(operands, x, exit_order, l):
        missing = [k for k in keys if k not in operands]
        if missing:
            raise ValueError(f"sharded superstep is missing operands "
                             f"{missing}")
        return fn(*(operands[k] for k in keys), x, exit_order, l)

    return step_sharded


def run_propagation(backend: PropagationBackend, nai, operands: dict,
                    x0, n_batch: int, *, mesh=None,
                    gather_mode: str = "dense",
                    classify=None, cls_params=None,
                    return_series: bool = False):
    """Run the masked NAP loop for any registered backend.

    ``operands`` holds the backend's packed arrays (including the dense
    ``x_inf`` for backends with ``uses_dense_x_inf``). Returns
    ``(exit_order (n_batch,), series (T_max+1, n_batch, f))`` — or
    ``(exit_order, preds (n_batch,))`` when ``classify`` is given:
    ``classify(cls_params, exit_order, series)`` runs right after the
    loop, INSIDE shard_map when sharded, so each shard classifies its
    own batch rows and only the argmax class ids are gathered (the
    series never leaves the sharded region). ``return_series=True``
    (with ``classify``) additionally returns the (T_max+1, n_batch, f)
    batch-row series as a third output — the propagated-feature cache's
    fill source; sharded it IS gathered off the mesh, in packed batch
    order like everything else.

    With ``mesh=None`` (or a ``data`` axis of size 1) this is the
    single-device path. Otherwise the loop runs under `shard_map`:
    operands must come from ``pack_support(..., n_shards=D)`` (row
    partition in shard-major superblock order) and the returned
    exit_order/series/preds are in the PACKED (permuted) batch order —
    undo with `repro.gnn.packing.shard_batch_perm`. ``gather_mode``
    picks the per-step frontier exchange (see the module docstring);
    halo modes require the halo metadata emitted by
    ``pack_support(halo=True)`` among the operands.
    """
    if gather_mode not in GATHER_MODES:
        raise ValueError(f"unknown gather_mode {gather_mode!r} "
                         f"(one of {GATHER_MODES})")
    mesh = normalize_mesh(mesh)
    has_halo = "halo_src_shard" in operands
    if mesh is None:
        if has_halo:
            raise ValueError("halo-packed operands (frame-local indices) "
                             "cannot run single-device — pack with "
                             "halo=False")
        backend.validate(operands, x0, n_batch)
        exit_order, series = _masked_loop(
            backend, nai, dict(operands), x0, n_batch, x0.shape[0],
            gather=lambda x: x,
            any_fn=lambda m: jnp.any(m).astype(jnp.int32))
        if classify is None:
            return exit_order, series
        preds = classify(cls_params, exit_order, series)
        if return_series:
            return exit_order, preds, series
        return exit_order, preds

    if (gather_mode != "dense") != has_halo:
        raise ValueError(
            f"gather_mode={gather_mode!r} and halo metadata disagree: "
            f"halo/alltoall need pack_support(halo=True) operands "
            f"(frame-local tile_col/src), dense needs global ones")
    D = int(mesh.shape["data"])
    S = x0.shape[0]
    if n_batch % (CB * D) or S % (CB * D):
        raise ValueError(
            f"sharded operands must be packed with n_shards={D}: n_batch "
            f"{n_batch} and rows {S} must be multiples of CB*D = {CB * D}")
    nb_loc, rows_loc = n_batch // D, S // D
    logical = operand_logical(backend, gather_mode,
                              seeds="seed_rows" in operands)
    keys = tuple(logical)
    arrays = [operands[k] for k in keys]
    in_specs = tuple(spec(*logical[k], mesh=mesh) for k in keys) \
        + (spec("row_shard", None, mesh=mesh),)
    series_spec = spec(None, "row_shard", None, mesh=mesh)
    out_specs = (spec("row_shard", mesh=mesh),
                 spec("row_shard", mesh=mesh) if classify is not None
                 else series_spec)
    if classify is not None and return_series:
        out_specs += (series_spec,)
    if classify is not None:
        in_specs += (spec(mesh=mesh),)   # replicated classifier tree

    def local_fn(*args):
        if classify is not None:
            args, params = args[:-1], args[-1]
        ops = dict(zip(keys, args[:-1]))
        x0_loc = args[-1]
        if gather_mode == "dense":
            def gather(x):
                return jax.lax.all_gather(x, "data", axis=0, tiled=True)
        else:
            # (D, ...) shard-stacked halo metadata block-slices to its
            # leading row — this shard's frame spec
            gather = _halo_gather(
                gather_mode, {k: ops.pop(k)[0] for k in HALO_LOGICAL},
                rows_loc)
        if backend.uses_edges:
            # (D, e) shard-stacked edge arrays block-slice to (1, e)
            ops.update({k: ops[k][0] for k in ("src", "dst", "coef")})
        if "seed_rows" in ops:
            # (D, k) / (D, L, k, f) shard-stacked seeds slice likewise
            ops.update(seed_rows=ops["seed_rows"][0],
                       seed_vals=ops["seed_vals"][0])
        backend.validate(ops, x0_loc, nb_loc)
        exit_order, series = _masked_loop(
            backend, nai, ops, x0_loc, nb_loc, rows_loc, gather=gather,
            any_fn=lambda m: (jax.lax.psum(jnp.any(m).astype(jnp.int32),
                                           "data") > 0).astype(jnp.int32))
        if classify is None:
            return exit_order, series
        preds = classify(params, exit_order, series)
        if return_series:
            return exit_order, preds, series
        return exit_order, preds

    # check_vma=False: the replication checker cannot see through the
    # fori_loop carry; correctness is covered by the bit-parity tests
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    if classify is not None:
        return fn(*arrays, x0, cls_params)
    return fn(*arrays, x0)
