"""jit'd wrapper for the fused NAP exit decision."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.nap_exit.kernel import FB, NB, nap_exit


def exit_decision(x, x_inf, active_nodes, t_s):
    """Convenience wrapper on unpadded inputs.
    x, x_inf (n, f); active_nodes (n,) bool. Returns (dist (n,), exit (n,)
    bool, blk_active (n_blocks,) int32) on the padded grid."""
    n, f = x.shape
    n_pad = -(-n // NB) * NB
    f_pad = -(-f // FB) * FB
    xp = jnp.zeros((n_pad, f_pad), x.dtype).at[:n, :f].set(x)
    ip = jnp.zeros((n_pad, f_pad), x.dtype).at[:n, :f].set(x_inf)
    ap = jnp.zeros((n_pad, 1), jnp.int32).at[:n, 0].set(
        active_nodes.astype(jnp.int32))
    dist2, exits, blk = nap_exit(xp, ip, ap, t_s)
    return jnp.sqrt(dist2[:n, 0]), exits[:n, 0] != 0, blk[:, 0]
