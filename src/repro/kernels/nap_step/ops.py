"""Wrappers for the fused NAP step kernel.

`fused_step` is the convenience entry point (threshold given unsquared,
like `repro.kernels.nap_exit.exit_decision`). `two_launch_step` is the
reference composition this kernel fuses — `spmm_block_ell` followed by
`nap_exit` — with identical outputs, kept for parity tests and the
benchmark's side-by-side latency comparison. Both take the stationary
state as its rank-1 factors (c_inf, s_inf); the unfused path has to
materialize the dense x_inf = c ⊗ s to feed `nap_exit` (that is half of
what fusing saves), the fused kernel never does.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.nap_exit.kernel import nap_exit
from repro.kernels.nap_step.kernel import nap_step_fused
from repro.kernels.spmm.kernel import RB, spmm_block_ell


def fused_step(tiles, tile_col, valid, active, x, c_inf, s_inf,
               node_active, t_s):
    """One fused propagation + exit step; `t_s` is the (unsquared) exit
    threshold. Returns (out, exit, blk_still)."""
    ts2 = jnp.asarray([t_s * t_s], jnp.float32)
    return nap_step_fused(tiles, tile_col, valid, active, x, c_inf, s_inf,
                          node_active, ts2)


def two_launch_step(tiles, tile_col, valid, active, x, c_inf, s_inf,
                    node_active, t_s):
    """The unfused reference: SpMM kernel launch, propagated features round
    trip through HBM, dense stationary state materialized, then the
    exit-decision kernel launch over the batch region. Output contract
    matches `fused_step`."""
    x_inf = (c_inf.reshape(-1, 1) * s_inf.reshape(1, -1)).astype(x.dtype)
    nb = x_inf.shape[0]
    out = spmm_block_ell(tiles, tile_col, valid, active, x)
    _, exits, blk_batch = nap_exit(out[:nb], x_inf,
                                   node_active.astype(jnp.int32), t_s)
    n_rb = tile_col.shape[0]
    blk = jnp.zeros((n_rb, 1), jnp.int32).at[:nb // RB].set(blk_batch)
    return out, exits, blk
