"""Block-ELL SpMM Pallas kernel with NAP row-block predication.

TPU adaptation of the paper's sparse feature propagation (DESIGN.md §3):
the adjacency is tiled into dense (RB, CB) coefficient tiles (block-ELL:
a fixed budget of `max_tb` tiles per row block, zero-padded). The kernel is
a block-sparse matmul driven by scalar-prefetched tile column indices — the
standard TPU pattern for data-dependent addressing (cf. megablox). NAP's
early exit feeds the `active` vector: a row block whose nodes have ALL
exited is skipped entirely (`@pl.when`), so saved compute scales with the
fraction of exited tiles — the paper's O(qmf) at tile granularity.

Grid: (row_blocks, feature_blocks, max_tiles_per_row_block); the tile loop
is innermost so the output block stays resident in VMEM while accumulating.

The scalar-prefetched tables live in SMEM, which holds about 1 MiB. A
support's tables grow with n_rb * max_tb (an arxiv-scale support at
T_max=3 needs 37.7 MB per table), so the row blocks are split into
chunks whose tables fit `SMEM_TABLE_BYTES` and one `pallas_call` runs per
chunk under `jax.lax.map`; the chunk's first row block arrives as one
more prefetched scalar that offsets the tile index map. Each row block is
accumulated exactly as in a single call, so chunking changes no bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret

RB = 8      # rows per adjacency tile (sublane-aligned)
CB = 128    # cols per adjacency tile (lane-aligned)
FB = 128    # feature block

# SMEM budget for one call's prefetched tables (tile_col, valid, active)
SMEM_TABLE_BYTES = 256 * 1024


def row_block_chunk(n_rb: int, max_tb: int) -> int:
    """Row blocks per `pallas_call`: the largest divisor of `n_rb` whose
    int32 tile tables (two of n_rb * max_tb, one of n_rb) fit
    `SMEM_TABLE_BYTES`."""
    cap = max(1, SMEM_TABLE_BYTES // (4 * (2 * max_tb + 1)))
    if n_rb <= cap:
        return n_rb
    return max(d for d in range(1, cap + 1) if n_rb % d == 0)


def map_row_chunks(call, n_rb: int, chunk: int, tables):
    """Run ``call(offset, *table_chunks)`` once per chunk of `chunk` row
    blocks and stack the results along a leading chunk axis. `tables` are
    arrays whose leading axis is the row block; each is cut into
    (n_chunks, chunk * rest) int32 slices."""
    n_chunks = n_rb // chunk
    flat = [t.astype(jnp.int32).reshape(n_chunks, -1) for t in tables]
    offs = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk).reshape(-1, 1)
    if n_chunks == 1:
        return jax.tree.map(lambda a: a[None],
                            call(offs[0], *(t[0] for t in flat)))
    return jax.lax.map(lambda a: call(*a), (offs, *flat))


def _kernel(off_ref, tile_col_ref, active_ref, valid_ref,   # scalar prefetch
            tiles_ref, x_ref, out_ref):
    rb = pl.program_id(0)
    t = pl.program_id(2)
    ntb = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    is_active = active_ref[rb] != 0
    is_valid = valid_ref[rb * ntb + t] != 0

    @pl.when(is_active & is_valid)
    def _acc():
        a = tiles_ref[0, 0]                      # (RB, CB)
        x = x_ref[...]                           # (CB, FB)
        out_ref[...] += jnp.dot(a, x, preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST
                                ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmm_block_ell(tiles, tile_col, valid, active, x, *, interpret=None):
    """tiles (n_rb, max_tb, RB, CB) f32 adjacency coefficient tiles;
    tile_col (n_rb, max_tb) int32 column-block index per tile;
    valid (n_rb, max_tb) int32 1 for real tiles, 0 for padding;
    active (n_rb,) int32 NAP row-block predicate;
    x (n_cb*CB, F) features (F % FB == 0).
    Returns out (n_rb*RB, F). `interpret` defaults to the platform's
    mode (`repro.runtime.pallas_interpret`)."""
    if interpret is None:
        interpret = pallas_interpret()
    n_rb, max_tb = tile_col.shape
    n, F = x.shape
    assert n % CB == 0 and F % FB == 0, (n, F)
    chunk = row_block_chunk(n_rb, max_tb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(chunk, F // FB, max_tb),
        in_specs=[
            pl.BlockSpec((1, 1, RB, CB),
                         lambda rb, fb, t, off, *_: (off[0] + rb, t, 0, 0)),
            pl.BlockSpec((CB, FB),
                         lambda rb, fb, t, off, cols, *_:
                         (cols[rb * max_tb + t], fb)),
        ],
        out_specs=pl.BlockSpec((RB, FB), lambda rb, fb, t, *_: (rb, fb)),
    )
    call = pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((chunk * RB, F), x.dtype),
        interpret=interpret)
    out = map_row_chunks(
        lambda off, cols, act, val: call(off, cols, act, val, tiles, x),
        n_rb, chunk, (tile_col, active, valid))
    return out.reshape(n_rb * RB, F)
