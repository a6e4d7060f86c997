"""jit'd GQA-aware wrapper around the flash attention kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import BK, BQ, flash_attention


def gqa_flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        interpret=None):
    """q (B, S, H, hd); k, v (B, S, KV, hd). Pads S to the block size,
    repeats KV heads to H, runs the kernel, unpads."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    S_pad = -(-S // max(BQ, BK)) * max(BQ, BK)
    pad = S_pad - S

    def prep(x, heads):
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if heads != H:
            x = jnp.repeat(x, G, axis=2)
        return x.transpose(0, 2, 1, 3).reshape(B * H, S_pad, hd)

    qf = prep(q, H)
    kf = prep(k, KV)
    vf = prep(v, KV)
    out = flash_attention(qf, kf, vf, causal=causal, window=window,
                          interpret=interpret)
    out = out.reshape(B, H, S_pad, hd).transpose(0, 2, 1, 3)
    return out[:, :S]
