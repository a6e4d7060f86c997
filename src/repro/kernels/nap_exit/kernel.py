"""Fused NAP exit-decision Pallas kernel.

Computes, per node tile, the squared L2 distance to the stationary state
(paper Eq. 8) and the exit decision d < T_s in one pass over the feature
blocks — the propagated features are read once, no (n, f) temporary is
materialized. The wrapper also returns the per-row-block `any still
active` predicate that feeds the next SpMM step's block predication,
reduced from the exit flags outside the kernel (a (1, 1) block per node
tile is not a legal TPU tiling).

Grid: (node_blocks, feature_blocks); feature loop innermost accumulates the
squared distance in the output tile, the final feature block turns it into
{exit, active} flags in-place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret

NB = 8      # nodes per tile
FB = 128    # feature block


def _kernel(x_ref, xinf_ref, active_ref, ts2_ref, dist_ref, exit_ref):
    fb = pl.program_id(1)
    nfb = pl.num_programs(1)

    @pl.when(fb == 0)
    def _init():
        dist_ref[...] = jnp.zeros_like(dist_ref)

    diff = (x_ref[...] - xinf_ref[...]).astype(jnp.float32)
    dist_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(fb == nfb - 1)
    def _decide():
        was_active = active_ref[...] != 0
        exits = was_active & (dist_ref[...] < ts2_ref[0])
        exit_ref[...] = exits.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def nap_exit(x, x_inf, active, t_s, *, interpret=None):
    """x, x_inf: (n_pad, F_pad) propagated/stationary features;
    active: (n_pad, 1) int32 per-node 'not yet exited';
    t_s: scalar threshold (distance, not squared).
    Returns (dist2 (n_pad, 1) f32, exit (n_pad, 1) int32,
             blk_active (n_blocks, 1) int32). `interpret` defaults to the
    platform's mode (`repro.runtime.pallas_interpret`)."""
    if interpret is None:
        interpret = pallas_interpret()
    n, F = x.shape
    assert n % NB == 0 and F % FB == 0
    grid = (n // NB, F // FB)
    ts2 = jnp.asarray([t_s * t_s], jnp.float32)
    out_shape = (
        jax.ShapeDtypeStruct((n, 1), jnp.float32),
        jax.ShapeDtypeStruct((n, 1), jnp.int32),
    )
    fn = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((NB, FB), lambda nb, fb: (nb, fb)),
            pl.BlockSpec((NB, FB), lambda nb, fb: (nb, fb)),
            pl.BlockSpec((NB, 1), lambda nb, fb: (nb, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((NB, 1), lambda nb, fb: (nb, 0)),
            pl.BlockSpec((NB, 1), lambda nb, fb: (nb, 0)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )
    dist2, exits = fn(x, x_inf, active, ts2)
    still = (active != 0) & (exits == 0)
    blk_active = still.reshape(n // NB, NB).any(axis=1, keepdims=True)
    return dist2, exits, blk_active.astype(jnp.int32)
