"""Fused NAP propagation step: block-ELL SpMM + exit decision, one kernel.

The two-launch compiled path (`repro.kernels.spmm.spmm_block_ell` followed
by `repro.kernels.nap_exit.nap_exit`) writes the full padded (n_pad, F_pad)
propagated features to HBM and reads the batch region back just to compute
a distance — the VMEM round trip flagged in ROADMAP's "next steps". This
kernel does both in one grid pass: per row block it performs the block-ELL
accumulation, and while the freshly accumulated output block is still
resident in VMEM it folds the squared distance to the stationary state
(paper Eq. 8) into a VMEM scratch accumulator; the final feature block
turns the accumulator into per-node exit flags plus the per-row-block
`any node still active` predicate. The consumer collapses that predicate
to the GLOBAL any-batch-node-live flag before ANDing with the static hop
mask (repro.gnn.nai) — exited batch rows must keep propagating while any
neighbor is live, since their values feed other rows' aggregation, so
per-block gating of batch blocks would corrupt results. The propagated
block never leaves VMEM between the matmul and the distance check, and
Pallas's pipelined grid double-buffers the coefficient tiles exactly as
in the plain SpMM kernel.

The stationary state is rank-1 by construction (Eq. 7: Â^∞X = c ⊗ s), so
the kernel streams its FACTORS — c (nb, 1) per row block and s (1, F) per
feature block — instead of a dense (nb, F) x_inf operand: the stationary
state is never materialized in HBM at all, and the exit check's extra
operand traffic per step drops from nb*F to nb + F.

Grid: (row_blocks, feature_blocks, max_tiles_per_row_block); the tile loop
is innermost so the output block stays resident while accumulating, and
the (RB, 1) distance scratch lives outside the pipeline entirely — row
blocks are visited in order, so it is re-zeroed at each row block's first
cell. Batch blocks (rb < nb_rb) come first and are the only ones that
carry exit state. As in the plain SpMM kernel, the row blocks run in
chunks whose prefetched tables fit SMEM (`repro.kernels.spmm.kernel.
row_block_chunk`), one `pallas_call` per chunk.

Operand contract (all shapes bucket-padded by repro.gnn.packing):
  scalar prefetch (per chunk): row-block offset (1,), tile_col, active
                   and valid of the chunk's row blocks, ts2 (1,) — the
                   SQUARED threshold; pass a negative value to disable
                   exits for this step (l < T_min or l == T_max).
  inputs:  tiles (n_rb, tb, RB, CB) f32; x (n_cb*CB, F) with F % FB == 0;
           c_inf (nb, 1) f32 and s_inf (1, F) f32 — the rank-1 stationary
           state factors (x_inf = c_inf @ s_inf), nb % RB == 0 (the padded
           batch region; row blocks past nb//RB skip the distance section);
           node_active (nb, 1) int32 'not yet exited'.
  outputs: out (n_rb*RB, F); exit (nb, 1) int32;
           blk_still (n_rb, 1) int32 (zero for non-batch row blocks),
           reduced from the exit flags outside the kernel — a (1, 1)
           block per row block is not a legal TPU tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmm.kernel import (CB, FB, RB, map_row_chunks,
                                       row_block_chunk)
from repro.runtime import pallas_interpret


def _kernel(off_ref, tile_col_ref, active_ref, valid_ref,   # scalar prefetch
            ts2_ref, tiles_ref, x_ref, c_ref, s_ref, nact_ref,
            out_ref, exit_ref, dist_ref, *, nb_rb):
    rb = pl.program_id(0)
    fb = pl.program_id(1)
    t = pl.program_id(2)
    nfb = pl.num_programs(1)
    ntb = pl.num_programs(2)
    is_batch = off_ref[0] + rb < nb_rb
    last = (t == ntb - 1) & (fb == nfb - 1)

    @pl.when(t == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((t == 0) & (fb == 0) & is_batch)
    def _init_dist():
        dist_ref[...] = jnp.zeros_like(dist_ref)

    is_active = active_ref[rb] != 0
    is_valid = valid_ref[rb * ntb + t] != 0

    @pl.when(is_active & is_valid)
    def _acc():
        a = tiles_ref[0, 0]                      # (RB, CB)
        x = x_ref[...]                           # (CB, FB)
        out_ref[...] += jnp.dot(a, x, preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST
                                ).astype(out_ref.dtype)

    # the output block is complete once the tile loop finishes; fold its
    # contribution to ||x - x_inf||^2 while it is still in VMEM, with the
    # x_inf block rebuilt from its rank-1 factors (never read from HBM)
    @pl.when((t == ntb - 1) & is_batch)
    def _dist():
        x_inf = c_ref[...] * s_ref[...]          # (RB, 1) * (1, FB)
        diff = (out_ref[...] - x_inf).astype(jnp.float32)
        dist_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(last & is_batch)
    def _decide():
        was_active = nact_ref[...] != 0
        exits = was_active & (dist_ref[...] < ts2_ref[0])
        exit_ref[...] = exits.astype(jnp.int32)

    @pl.when(last & ~is_batch)
    def _no_exit_state():
        exit_ref[...] = jnp.zeros_like(exit_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def nap_step_fused(tiles, tile_col, valid, active, x, c_inf, s_inf,
                   node_active, ts2, *, interpret=None):
    """One fused NAP step. See the module docstring for the operand
    contract. `ts2` is a (1,) f32 array holding the squared exit threshold
    (negative disables exits). Returns (out, exit, blk_still).
    `interpret` defaults to the platform's mode
    (`repro.runtime.pallas_interpret`)."""
    if interpret is None:
        interpret = pallas_interpret()
    n_rb, max_tb = tile_col.shape
    n, F = x.shape
    c_inf = c_inf.reshape(-1, 1)
    s_inf = s_inf.reshape(1, -1)
    nb = c_inf.shape[0]
    assert n % CB == 0 and F % FB == 0, (n, F)
    assert nb % RB == 0 and nb >= RB and s_inf.shape[1] == F, (nb, F)
    assert node_active.shape == (nb, 1), node_active.shape
    nb_rb = nb // RB
    chunk = row_block_chunk(n_rb, max_tb)

    def batch_block(rb, off):
        return jnp.minimum(off[0] + rb, nb_rb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(chunk, F // FB, max_tb),
        in_specs=[
            pl.BlockSpec((1, 1, RB, CB),
                         lambda rb, fb, t, off, *_: (off[0] + rb, t, 0, 0)),
            pl.BlockSpec((CB, FB),
                         lambda rb, fb, t, off, cols, *_:
                         (cols[rb * max_tb + t], fb)),
            pl.BlockSpec((RB, 1),
                         lambda rb, fb, t, off, *_: (batch_block(rb, off), 0)),
            pl.BlockSpec((1, FB), lambda rb, fb, t, *_: (0, fb)),
            pl.BlockSpec((RB, 1),
                         lambda rb, fb, t, off, *_: (batch_block(rb, off), 0)),
        ],
        out_specs=(
            pl.BlockSpec((RB, FB), lambda rb, fb, t, *_: (rb, fb)),
            pl.BlockSpec((RB, 1), lambda rb, fb, t, *_: (rb, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((RB, 1), jnp.float32)],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, nb_rb=nb_rb), grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((chunk * RB, F), x.dtype),
                   jax.ShapeDtypeStruct((chunk * RB, 1), jnp.int32)),
        interpret=interpret)
    ts2 = jnp.asarray(ts2, jnp.float32).reshape(1)
    c_inf, s_inf = c_inf.astype(x.dtype), s_inf.astype(x.dtype)
    node_active = node_active.astype(jnp.int32)
    out, exits = map_row_chunks(
        lambda off, cols, act, val: call(off, cols, act, val, ts2, tiles,
                                         x, c_inf, s_inf, node_active),
        n_rb, chunk, (tile_col, active, valid))
    exits = exits.reshape(n_rb * RB, 1)[:nb]
    still = (node_active != 0) & (exits == 0)
    blk_still = jnp.zeros((n_rb, 1), jnp.int32).at[:nb_rb, 0].set(
        still.reshape(nb_rb, RB).any(axis=1).astype(jnp.int32))
    return out.reshape(n_rb * RB, F), exits, blk_still
