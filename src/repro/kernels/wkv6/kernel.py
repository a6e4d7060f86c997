"""RWKV6 WKV recurrence as a Pallas TPU kernel.

The GPU reference is a per-timestep CUDA loop (no TPU analogue); the
TPU-native form is the chunked linear-attention factorization used by
`repro.nn.rwkv._wkv_chunked`, here tiled so the (hd, hd) recurrent state
lives in VMEM scratch across the sequential chunk dimension of the grid:

    out_t = r_t · (S + u ⊙ k_t v_tᵀ + Σ_{s<t in chunk} decay(s,t) k_s v_sᵀ)
    S    <- diag(Πw) S + Σ_s decay(s, C) k_s v_sᵀ

Grid: (batch*heads, T/CHUNK) — the chunk dim iterates sequentially on TPU,
so scratch carries the state like a lax.scan carry, with no HBM round-trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret

CHUNK = 16  # matches repro.nn.rwkv.CHUNK (f32-safe decay factorization)


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_scr):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _reset():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0].astype(jnp.float32)          # (C, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)        # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)          # (1, hd) bonus

    Lc = jnp.cumsum(lw, axis=0)               # inclusive log cumprod
    P = jnp.exp(Lc - lw)                      # prod_{s<t} w_s
    rp = r * P
    kd = k * jnp.exp(-Lc)

    C = r.shape[0]
    tri = jnp.tril(jnp.ones((C, C), jnp.float32), k=-1)
    A = jnp.dot(rp, kd.T, preferred_element_type=jnp.float32) * tri
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)          # (C, 1)
    out = jnp.dot(A, v, preferred_element_type=jnp.float32) \
        + diag * v \
        + jnp.dot(rp, state_scr[...], preferred_element_type=jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)

    Dtot = jnp.exp(Lc[-1:])                                   # (1, hd)
    kscale = k * jnp.exp(Lc[-1:] - Lc)
    state_scr[...] = state_scr[...] * Dtot.T \
        + jnp.dot(kscale.T, v, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6(r, k, v, logw, u, *, interpret=None):
    """r/k/v/logw: (BH, T, hd) f32, T % CHUNK == 0; u: (BH, hd).
    Returns out (BH, T, hd) f32 with zero initial state. `interpret`
    defaults to the platform's mode (`repro.runtime.pallas_interpret`)."""
    if interpret is None:
        interpret = pallas_interpret()
    BH, T, hd = r.shape
    assert T % CHUNK == 0, (T, CHUNK)
    grid = (BH, T // CHUNK)
    io_spec = pl.BlockSpec((1, CHUNK, hd), lambda b, t: (b, t, 0))
    fn = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, 1, hd), lambda b, t: (b, 0, 0))],
        out_specs=io_spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )
    return fn(r, k, v, logw, u[:, None, :])
