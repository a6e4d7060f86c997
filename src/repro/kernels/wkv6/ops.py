"""jit'd wrapper: multi-head RWKV6 time-mix core via the Pallas kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.wkv6.kernel import CHUNK, wkv6


def wkv6_heads(r, k, v, logw, u, *, interpret=None):
    """r/k/v/logw (B, T, H, hd) f32; u (H, hd). Pads T to CHUNK; returns
    (B, T, H, hd). Padding steps use logw=0 (no decay), k=0 — state-neutral,
    matching repro.nn.rwkv's masking."""
    B, T, H, hd = r.shape
    pad = (-T) % CHUNK
    def prep(x, neutral=0.0):
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=neutral)
        return x.transpose(0, 2, 1, 3).reshape(B * H, T + pad, hd)
    rf, kf, vf, lwf = prep(r), prep(k), prep(v), prep(logw)
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd)
    out = wkv6(rf, kf, vf, lwf, uf, interpret=interpret)
    out = out.reshape(B, H, T + pad, hd).transpose(0, 2, 1, 3)
    return out[:, :T]
