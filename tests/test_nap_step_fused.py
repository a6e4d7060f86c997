"""Fused NAP step kernel (repro.kernels.nap_step) parity matrix: the one-
pass kernel must match the two-launch composition (spmm_block_ell then
nap_exit), the jnp oracle, and the numpy host semantics — including
non-uniform exit patterns (some nodes exit at order 1, some never), the
all-exited-row-block skip, and bit-equal exit orders end to end."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.gnn import load_dataset
from repro.gnn.nai import (NAIConfig, infer_batch_masked,
                           support_stationary_factors)
from repro.gnn.packing import pack_support, step_active_blocks
from repro.gnn.sampler import sample_support
from repro.kernels.nap_step import (fused_step, nap_step_fused,
                                    ref_nap_step, two_launch_step)
from repro.kernels.spmm import CB, RB, build_block_ell, pad_features
from repro.gnn.store import as_store


def _random_graph(rng, n, deg):
    E = n * deg
    src = np.concatenate([rng.integers(0, n, E),
                          np.arange(n)]).astype(np.int32)
    dst = np.concatenate([rng.integers(0, n, E),
                          np.arange(n)]).astype(np.int32)
    key = dst.astype(np.int64) * n + src
    uk = np.unique(key)
    dst, src = (uk // n).astype(np.int32), (uk % n).astype(np.int32)
    coef = rng.random(len(src)).astype(np.float32)
    return src, dst, coef


def _operands(rng, n=192, deg=5, f=100, nb=32):
    src, dst, coef = _random_graph(rng, n, deg)
    ell = build_block_ell(src, dst, coef, n)
    x = jnp.asarray(pad_features(rng.standard_normal((n, f)), ell.n_pad))
    f_pad = x.shape[1]
    c_inf = jnp.asarray(rng.random(nb).astype(np.float32) + 0.1)
    s_inf = jnp.asarray(np.pad(
        rng.standard_normal(f).astype(np.float32), (0, f_pad - f)))
    return ell, x, c_inf, s_inf


@pytest.mark.parametrize("frac_active,frac_nodes",
                         [(1.0, 1.0), (0.6, 0.5), (1.0, 0.0), (0.3, 1.0)])
def test_fused_matches_two_launch_and_oracle(rng, frac_active, frac_nodes):
    """Same operands through the fused kernel, the two-launch composition
    it replaces, and the jnp oracle — all outputs must agree, with mixed
    skipped row blocks and partially exited node masks."""
    ell, x, c_inf, s_inf = _operands(rng)
    nb = c_inf.shape[0]
    n_rb = ell.tile_col.shape[0]
    active = jnp.asarray(
        (rng.random(n_rb) < frac_active).astype(np.int32)
    ).at[:nb // RB].set(1)
    nact = jnp.asarray((rng.random(nb) < frac_nodes).astype(np.int32)
                       )[:, None]
    t_s = 9.0
    ops = (jnp.asarray(ell.tiles), jnp.asarray(ell.tile_col),
           jnp.asarray(ell.valid), active, x, c_inf, s_inf, nact, t_s)
    out_f = fused_step(*ops)
    out_t = two_launch_step(*ops)
    out_r = ref_nap_step(*ops[:8], t_s * t_s)
    for f_arr, t_arr, r_arr in zip(out_f, out_t, out_r):
        np.testing.assert_allclose(np.asarray(f_arr), np.asarray(t_arr),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(f_arr), np.asarray(r_arr),
                                   rtol=1e-4, atol=1e-4)
    # exit flags and block predicates are bit-exact, not just close
    assert np.array_equal(np.asarray(out_f[1]), np.asarray(out_t[1]))
    assert np.array_equal(np.asarray(out_f[2]), np.asarray(out_t[2]))


def test_all_exited_row_block_skip(rng):
    """active == 0 everywhere (whole batch exited) must touch zero tiles:
    propagated output exactly zero, no node exits, no block still live."""
    ell, x, c_inf, s_inf = _operands(rng)
    nb = c_inf.shape[0]
    n_rb = ell.tile_col.shape[0]
    out, exits, blk = fused_step(
        jnp.asarray(ell.tiles), jnp.asarray(ell.tile_col),
        jnp.asarray(ell.valid), jnp.zeros((n_rb,), jnp.int32), x,
        c_inf, s_inf, jnp.zeros((nb, 1), jnp.int32), 9.0)
    assert float(jnp.abs(out).max()) == 0.0
    assert int(exits.sum()) == 0 and int(blk.sum()) == 0


def test_negative_ts2_gates_exits(rng):
    """A negative squared threshold (how T_min/T_max gating reaches the
    kernel) must keep every active node active."""
    ell, x, c_inf, s_inf = _operands(rng)
    nb = c_inf.shape[0]
    n_rb = ell.tile_col.shape[0]
    nact = jnp.asarray((rng.random(nb) < 0.7).astype(np.int32))[:, None]
    _, exits, blk = nap_step_fused(
        jnp.asarray(ell.tiles), jnp.asarray(ell.tile_col),
        jnp.asarray(ell.valid), jnp.ones((n_rb,), jnp.int32), x,
        c_inf, s_inf, nact, jnp.asarray([-1.0], jnp.float32))
    assert int(exits.sum()) == 0
    expect_blk = np.asarray(nact)[:, 0].reshape(-1, RB).any(axis=1)
    assert np.array_equal(np.asarray(blk)[:nb // RB, 0],
                          expect_blk.astype(np.int32))
    assert int(np.asarray(blk)[nb // RB:].sum()) == 0


@pytest.mark.parametrize("kernel", ["spmm_block_ell", "nap_step_fused"])
def test_row_block_chunks_are_bit_identical(rng, monkeypatch, kernel):
    """Splitting the row blocks into several calls (what bounds the
    prefetched tables to SMEM on large supports) must not change a bit:
    a budget of four row blocks per call against a single call."""
    import jax
    from repro.kernels.spmm import kernel as spmm_kernel
    ell, x, c_inf, s_inf = _operands(rng, n=320, nb=40)
    n_rb, tb = ell.tile_col.shape
    nb = c_inf.shape[0]
    active = jnp.asarray((rng.random(n_rb) < 0.7).astype(np.int32)
                         ).at[:nb // RB].set(1)
    nact = jnp.asarray((rng.random(nb) < 0.6).astype(np.int32))[:, None]
    tiles = (jnp.asarray(ell.tiles), jnp.asarray(ell.tile_col),
             jnp.asarray(ell.valid), active, x)
    if kernel == "spmm_block_ell":
        run = functools.partial(spmm_kernel.spmm_block_ell, *tiles)
    else:
        run = functools.partial(nap_step_fused, *tiles, c_inf, s_inf, nact,
                                jnp.asarray([81.0], jnp.float32))
    whole = jax.tree.map(np.asarray, run())
    budget = 4 * 4 * (2 * tb + 1)
    with monkeypatch.context() as m:
        m.setattr(spmm_kernel, "SMEM_TABLE_BYTES", budget)
        assert spmm_kernel.row_block_chunk(n_rb, tb) == 4 < n_rb
        jax.clear_caches()
        chunked = jax.tree.map(np.asarray, run())
    jax.clear_caches()
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        assert np.array_equal(a, b)


# ------------------------------------------------ full NAP loop parity
@pytest.fixture(scope="module")
def packed_case():
    g = load_dataset("pubmed-like", scale=0.03, seed=1)
    rng = np.random.default_rng(0)
    batch = rng.choice(g.test_idx, size=37, replace=False)
    sup = sample_support(as_store(g), batch, 3, 0.5)
    x0 = g.features[sup.nodes][:, :64].astype(np.float32)
    c64, s64 = support_stationary_factors(g, sup, x0, 0.5)
    c32 = c64.astype(np.float32)
    s32 = s64.astype(np.float32)
    # dense x_inf from the f32 factors: the same arithmetic the fused
    # kernel performs in VMEM, so exit orders can be compared bit-wise
    packed = pack_support(sup, x0, np.outer(c32, s32),
                          x_inf_factors=(c32, s32))
    return g, sup, packed


def _dense_operator(packed):
    A = np.zeros((packed.n_pad, packed.n_pad), np.float32)
    for rb in range(packed.n_rb):
        for t in range(packed.tiles.shape[1]):
            if packed.valid[rb, t]:
                cb = int(packed.tile_col[rb, t])
                A[rb * RB:(rb + 1) * RB, cb * CB:(cb + 1) * CB] += \
                    packed.tiles[rb, t]
    return A


def _host_orders(packed, step_active, t_s, t_min, t_max):
    """Numpy reference for the masked-path semantics: dense padded
    operator, full propagation each (hop-masked) step, squared f32
    distance against the squared threshold — exactly the fused kernel's
    arithmetic contract."""
    n_pad, nb = packed.n_pad, packed.n_batch
    A = _dense_operator(packed)
    x_inf = packed.c_inf[:, None] * packed.s_inf[None, :]
    x = packed.x0.copy()
    orders = np.zeros(nb, np.int64)
    for l in range(1, t_max + 1):
        live = (orders == 0).any()
        row_active = np.repeat(step_active[l - 1] * int(live), RB
                               ).astype(bool)
        x = np.where(row_active[:, None], A @ x, 0.0).astype(np.float32)
        if not (t_min <= l < t_max):
            continue
        d2 = ((x[:nb] - x_inf) ** 2).sum(axis=1, dtype=np.float32)
        orders[(orders == 0) & (d2 < np.float32(t_s) ** 2)] = l
    orders[orders == 0] = t_max
    return orders


def _fused_orders(packed, nai, step_active):
    orders, series = infer_batch_masked(
        None, nai, None, None, None, None, jnp.asarray(packed.x0),
        jnp.asarray(packed.x_inf), packed.n_batch, spmm_impl="fused",
        ell=(jnp.asarray(packed.tiles), jnp.asarray(packed.tile_col),
             jnp.asarray(packed.valid)),
        step_active=jnp.asarray(step_active),
        x_inf_factors=(jnp.asarray(packed.c_inf),
                       jnp.asarray(packed.s_inf)))
    return np.asarray(orders), series


def _step_distances(packed, t_max):
    """Per-step batch distances d_l for l = 1..t_max-1 (the decision
    steps), full unmasked propagation — what both paths compare to T_s."""
    A = _dense_operator(packed)
    x_inf = packed.c_inf[:, None] * packed.s_inf[None, :]
    x = packed.x0.copy()
    out = []
    for l in range(1, t_max):
        x = (A @ x).astype(np.float32)
        out.append(np.linalg.norm(x[:packed.nb_real]
                                  - x_inf[:packed.nb_real], axis=1))
    return out


def _split_ts(packed, t_max=3) -> float:
    """A threshold that splits the step-1 distances (non-uniform exits)
    while keeping EVERY decision-step distance well away from the cut, so
    f32 rounding cannot flip an exit on either path."""
    dists = _step_distances(packed, t_max)
    d1 = np.unique(dists[0])
    d_all = np.concatenate(dists)
    cands = (d1[1:] + d1[:-1]) / 2
    margins = np.array([np.abs(d_all - c).min() for c in cands])
    return float(cands[margins.argmax()])


def test_fused_infer_matches_block_ell_infer(packed_case):
    """The fused loop must reproduce the two-kernel block_ell loop on a
    real packed support with a non-uniform exit pattern: identical exit
    orders (bit-equal) and matching propagated series."""
    g, sup, packed = packed_case
    sa = step_active_blocks(packed.hop_rb, 3)
    nai = NAIConfig(t_s=_split_ts(packed), t_min=1, t_max=3)
    of, series_f = _fused_orders(packed, nai, sa)
    ob, series_b = infer_batch_masked(
        None, nai, None, None, None, None, jnp.asarray(packed.x0),
        jnp.asarray(packed.x_inf), packed.n_batch, spmm_impl="block_ell",
        ell=(jnp.asarray(packed.tiles), jnp.asarray(packed.tile_col),
             jnp.asarray(packed.valid)),
        step_active=jnp.asarray(sa))
    assert np.array_equal(of, np.asarray(ob))
    np.testing.assert_allclose(np.asarray(series_f), np.asarray(series_b),
                               rtol=1e-4, atol=1e-4)
    # the pattern really is non-uniform on real rows
    real = of[:packed.nb_real]
    assert len(np.unique(real)) >= 2, real


def test_fused_infer_matches_host_orders(packed_case):
    """exit_order arrays are EQUAL (not close) between the fused Pallas
    loop and the numpy host reference across a threshold sweep covering
    all-exit-early, mixed, and never-exit patterns."""
    g, sup, packed = packed_case
    sa = step_active_blocks(packed.hop_rb, 3)
    mid = _split_ts(packed)
    for t_s in (1e-6, mid, 1e9):
        nai = NAIConfig(t_s=t_s, t_min=1, t_max=3)
        of, _ = _fused_orders(packed, nai, sa)
        oh = _host_orders(packed, sa, t_s, 1, 3)
        assert np.array_equal(of, oh), (t_s, of[:16], oh[:16])


def test_fused_skips_all_blocks_after_batch_exit(packed_case):
    """t_s huge => whole batch exits at T_min; the kernel-emitted block
    predicate then drives `live` to zero, so later series entries are
    exactly zero while exit orders stay 1."""
    g, sup, packed = packed_case
    sa = step_active_blocks(packed.hop_rb, 3)
    nai = NAIConfig(t_s=1e9, t_min=1, t_max=3)
    orders, series = _fused_orders(packed, nai, sa)
    assert (orders == 1).all()
    assert float(jnp.abs(series[1]).max()) > 0.0
    assert float(jnp.abs(series[2]).max()) == 0.0
    assert float(jnp.abs(series[3]).max()) == 0.0


# ------------------------------------------------------------ hypothesis
def test_property_fused_exit_order_equals_host():
    pytest.importorskip("hypothesis")
    from hypothesis import assume, given, settings, strategies as st

    @functools.lru_cache(maxsize=None)
    def graph_case(seed, n, deg, nb):
        rng = np.random.default_rng(seed)
        src, dst, coef = _random_graph(rng, n, deg)
        ell = build_block_ell(src, dst, coef, n)
        x0 = pad_features(rng.standard_normal((n, 4)).astype(np.float32),
                          ell.n_pad)
        f_pad = x0.shape[1]
        c = (rng.random(nb).astype(np.float32) * 0.5 + 0.1)
        s = np.zeros(f_pad, np.float32)
        s[:4] = rng.standard_normal(4).astype(np.float32)
        return ell, x0, c, s

    class _View:  # duck-typed PackedSupport view for _host_orders
        pass

    @given(st.integers(0, 2 ** 16), st.integers(24, 48), st.integers(2, 4),
           st.sampled_from([8, 16]), st.integers(2, 3),
           st.floats(0.05, 0.95))
    @settings(max_examples=10, deadline=None)
    def prop(seed, n, deg, nb, t_max, q):
        ell, x0, c, s = graph_case(seed, n, deg, nb)
        p = _View()
        p.n_pad, p.n_batch, p.nb_real = ell.n_pad, nb, nb
        p.n_rb = ell.tile_col.shape[0]
        p.tiles, p.tile_col, p.valid = ell.tiles, ell.tile_col, ell.valid
        p.x0 = x0
        p.c_inf, p.s_inf = c, s
        p.x_inf = c[:, None] * s[None, :]
        sa = np.ones((t_max, p.n_rb), np.int32)

        # threshold at a quantile of the step-1 distances, margin-guarded
        # over EVERY decision step so rounding cannot flip an exit
        dists = _step_distances(p, t_max)
        t_s = float(np.quantile(dists[0], q))
        d_all = np.concatenate(dists)
        assume(np.abs(d_all - t_s).min() > 1e-3 * max(t_s, 1.0))

        nai = NAIConfig(t_s=t_s, t_min=1, t_max=t_max)
        of, _ = _fused_orders(p, nai, sa)
        oh = _host_orders(p, sa, t_s, 1, t_max)
        assert np.array_equal(of, oh), (t_s, of, oh)

    prop()
