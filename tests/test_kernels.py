"""Pallas kernel validation vs the pure-jnp oracles (interpret mode, which
the CPU platform selects): shape and dtype sweeps per kernel."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.spmm import (active_blocks_from_nodes, build_block_ell,
                                pad_features, ref_spmm_dense, ref_spmm_tiles,
                                spmm, RB)
from repro.kernels.nap_exit import exit_decision, nap_exit, ref_nap_exit
from repro.kernels.nap_exit import NB as EXIT_NB, FB as EXIT_FB
from repro.kernels.flash_attention import (flash_attention,
                                           gqa_flash_attention, ref_attention)


def _random_graph(rng, n, avg_deg):
    E = n * avg_deg
    src = rng.integers(0, n, E).astype(np.int32)
    dst = rng.integers(0, n, E).astype(np.int32)
    src = np.concatenate([src, np.arange(n, dtype=np.int32)])
    dst = np.concatenate([dst, np.arange(n, dtype=np.int32)])
    key = dst.astype(np.int64) * n + src
    uk = np.unique(key)
    dst, src = (uk // n).astype(np.int32), (uk % n).astype(np.int32)
    coef = rng.random(len(src)).astype(np.float32)
    return src, dst, coef


# ------------------------------------------------------------------- spmm
@pytest.mark.parametrize("n,deg,f", [(64, 3, 64), (200, 6, 100),
                                     (300, 2, 130), (128, 10, 256)])
def test_spmm_shapes(rng, n, deg, f):
    src, dst, coef = _random_graph(rng, n, deg)
    ell = build_block_ell(src, dst, coef, n)
    x = rng.standard_normal((n, f)).astype(np.float32)
    xp = jnp.asarray(pad_features(x, ell.n_pad))
    out = spmm(ell, xp)
    ref = ref_spmm_dense(src, dst, coef, ell.n_pad, xp,
                         np.ones(ell.tile_col.shape[0], np.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("frac_active", [0.0, 0.3, 1.0])
def test_spmm_nap_predication(rng, frac_active):
    src, dst, coef = _random_graph(rng, 192, 4)
    ell = build_block_ell(src, dst, coef, 192)
    n_rb = ell.tile_col.shape[0]
    active = (rng.random(n_rb) < frac_active).astype(np.int32)
    x = rng.standard_normal((192, 64)).astype(np.float32)
    xp = jnp.asarray(pad_features(x, ell.n_pad))
    out = spmm(ell, xp, jnp.asarray(active))
    ref = ref_spmm_tiles(ell.tiles, ell.tile_col, ell.valid, active, xp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # inactive row blocks are exactly zero
    for rb in np.flatnonzero(active == 0):
        assert float(jnp.abs(out[rb * RB:(rb + 1) * RB]).max()) == 0.0


def test_spmm_dtype_bf16(rng):
    src, dst, coef = _random_graph(rng, 128, 4)
    ell = build_block_ell(src, dst, coef, 128)
    x = rng.standard_normal((128, 128)).astype(np.float32)
    xp = jnp.asarray(pad_features(x, ell.n_pad)).astype(jnp.bfloat16)
    out = spmm(ell, xp)
    ref = ref_spmm_dense(src, dst, coef, ell.n_pad, xp.astype(jnp.float32),
                         np.ones(ell.tile_col.shape[0], np.int32))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)


def test_active_blocks_from_nodes():
    act = jnp.zeros(20, bool).at[9].set(True)
    blk = active_blocks_from_nodes(act, 24)
    assert blk.shape == (3,)
    assert list(np.asarray(blk)) == [0, 1, 0]


# ---------------------------------------------------------------- nap_exit
@pytest.mark.parametrize("n,f", [(40, 100), (100, 300), (8, 128), (256, 500)])
def test_nap_exit_shapes(rng, n, f):
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    xi = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    act = jnp.asarray(rng.random(n) < 0.7)
    t_s = float(np.sqrt(f) * 1.2)
    d, e, blk = exit_decision(x, xi, act, t_s)
    ref_d = jnp.linalg.norm(x - xi, axis=1)
    np.testing.assert_allclose(np.asarray(d), np.asarray(ref_d), rtol=1e-4)
    ref_e = np.asarray(act) & (np.asarray(ref_d) < t_s)
    assert np.array_equal(np.asarray(e), ref_e)


def test_nap_exit_vs_oracle_padded(rng):
    n, f = 100, 200
    n_pad = -(-n // EXIT_NB) * EXIT_NB
    f_pad = -(-f // EXIT_FB) * EXIT_FB
    x = jnp.zeros((n_pad, f_pad)).at[:n, :f].set(
        jnp.asarray(rng.standard_normal((n, f)), jnp.float32))
    xi = jnp.zeros((n_pad, f_pad)).at[:n, :f].set(
        jnp.asarray(rng.standard_normal((n, f)), jnp.float32))
    ap = jnp.zeros((n_pad, 1), jnp.int32).at[:n, 0].set(1)
    for out_k, out_r in zip(nap_exit(x, xi, ap, 15.0),
                            ref_nap_exit(x, xi, ap, 15.0)):
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("S,hd,causal,window",
                         [(128, 64, True, 0), (256, 64, True, 64),
                          (256, 128, False, 0), (384, 32, True, 128)])
def test_flash_attention_sweep(rng, S, hd, causal, window):
    q = jnp.asarray(rng.standard_normal((2, S, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, S, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, S, hd)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = ref_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.bfloat16)
    out = flash_attention(q, k, v)
    ref = ref_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


def test_gqa_wrapper_unpadded_seq(rng):
    q = jnp.asarray(rng.standard_normal((2, 100, 8, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 100, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 100, 2, 32)), jnp.float32)
    out = gqa_flash_attention(q, k, v)
    kr = jnp.repeat(k, 4, 2)
    vr = jnp.repeat(v, 4, 2)
    qf = q.transpose(0, 2, 1, 3).reshape(16, 100, 32)
    ref = ref_attention(qf, kr.transpose(0, 2, 1, 3).reshape(16, 100, 32),
                        vr.transpose(0, 2, 1, 3).reshape(16, 100, 32))
    ref = ref.reshape(2, 8, 100, 32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


# -------------------------------------------------------------------- wkv6
@pytest.mark.parametrize("T,hd,H", [(32, 16, 2), (40, 16, 3), (64, 32, 1)])
def test_wkv6_kernel_vs_sequential(rng, T, hd, H):
    from repro.kernels.wkv6 import ref_wkv6_sequential, wkv6_heads
    B = 2
    r = rng.standard_normal((B, T, H, hd)).astype(np.float32)
    k = rng.standard_normal((B, T, H, hd)).astype(np.float32)
    v = rng.standard_normal((B, T, H, hd)).astype(np.float32)
    logw = np.maximum(
        -np.exp(rng.standard_normal((B, T, H, hd)) * 0.5), -5.0
    ).astype(np.float32)
    u = (rng.standard_normal((H, hd)) * 0.1).astype(np.float32)
    out = wkv6_heads(jnp.asarray(r), jnp.asarray(k), jnp.asarray(v),
                     jnp.asarray(logw), jnp.asarray(u))
    flat = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    ref = ref_wkv6_sequential(
        flat(r), flat(k), flat(v), flat(logw),
        np.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd)
    ).reshape(B, H, T, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_wkv6_state_continuity_across_chunks(rng):
    """Outputs after the first chunk depend on earlier chunks' state."""
    from repro.kernels.wkv6 import CHUNK, wkv6
    BH, T, hd = 1, CHUNK * 2, 16
    r = jnp.asarray(rng.standard_normal((BH, T, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, T, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, T, hd)), jnp.float32)
    lw = jnp.full((BH, T, hd), -0.1, jnp.float32)
    u = jnp.zeros((BH, hd), jnp.float32)
    full = wkv6(r, k, v, lw, u)
    # zeroing the first chunk's k must change the second chunk's output
    k2 = k.at[:, :CHUNK].set(0.0)
    alt = wkv6(r, k2, v, lw, u)
    assert float(jnp.abs(full[:, CHUNK:] - alt[:, CHUNK:]).max()) > 1e-3
