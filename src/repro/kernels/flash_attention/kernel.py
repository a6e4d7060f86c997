"""Tiled online-softmax (flash) attention Pallas kernel.

Causal attention with optional sliding-window banding — the kernel behind
the `local` layers (RecurrentGemma) and the beyond-paper sliding-window
serving variant that lets full-attention architectures run long_500k.

Grid: (batch*heads, q_blocks, k_blocks), k innermost. Running max / sum /
accumulator live in VMEM scratch; fully-masked k blocks are skipped with
`@pl.when` (the flash-style compute saving — for a window W only ~W/S of
blocks are touched)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret

BQ = 128
BK = 128
NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale: float, causal: bool, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_first = qi * BQ                 # absolute first query position
    k_first = ki * BK
    # block-level skip: entirely above the diagonal or left of the window
    skip = False
    if causal:
        relevant = k_first <= q_first + BQ - 1
        if window > 0:
            relevant &= (k_first + BK - 1) > (q_first - window)
    else:
        relevant = True

    @pl.when(relevant)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale          # (BQ, hd)
        k = k_ref[0].astype(jnp.float32)                  # (BK, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
            kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
            mask = kpos <= qpos
            if window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret=None):
    """q, k, v: (BH, S, hd) with S % BQ == 0 == S % BK.
    Returns (BH, S, hd). `interpret` defaults to the platform's mode
    (`repro.runtime.pallas_interpret`)."""
    if interpret is None:
        interpret = pallas_interpret()
    BH, S, hd = q.shape
    Sk = k.shape[1]
    assert S % BQ == 0 and Sk % BK == 0, (S, Sk)
    scale = 1.0 / (hd ** 0.5)
    grid = (BH, S // BQ, Sk // BK)
    fn = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, BK, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, BK, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, hd), jnp.float32),
        ],
        interpret=interpret,
    )
    return fn(q, k, v)
