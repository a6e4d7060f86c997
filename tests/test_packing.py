"""Block-ELL packing round-trip: a packed `Support` pushed through the
Pallas kernel must match the host `_subgraph_spmm` and a COO-materialized
reference, including the all-exited row-block skip."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gnn import GNNConfig, load_dataset
from repro.gnn.nai import NAIConfig, _subgraph_spmm, infer_batch_masked
from repro.gnn.packing import (next_bucket, pack_support,
                               step_active_blocks)
from repro.gnn.sampler import sample_support
from repro.kernels.spmm import spmm_block_ell
from repro.gnn.store import as_store


@pytest.fixture(scope="module")
def packed_case():
    g = load_dataset("pubmed-like", scale=0.03, seed=1)
    rng = np.random.default_rng(0)
    batch = rng.choice(g.test_idx, size=37, replace=False)
    sup = sample_support(as_store(g), batch, 2, 0.5)
    x0 = g.features[sup.nodes][:, :64].astype(np.float32)
    x_inf = np.zeros((sup.n_batch, 64), np.float32)
    packed = pack_support(sup, x0, x_inf)
    return g, sup, x0, packed


def _real_rows(sup, packed):
    """Padded row ids of the real support rows, in support order."""
    nb = sup.n_batch
    return np.concatenate([np.arange(nb),
                           np.arange(packed.n_batch,
                                     packed.n_batch + len(sup) - nb)])


def _coo_dense_step(sup, packed, x0):
    """Scipy-style COO reference: materialize the padded subgraph operator
    and multiply."""
    rows = _real_rows(sup, packed)
    A = np.zeros((packed.n_pad, packed.n_pad), np.float32)
    A[rows[sup.dst], rows[sup.src]] = sup.coef
    xp = np.zeros((packed.n_pad, x0.shape[1]), np.float32)
    xp[rows] = x0
    return A @ xp, rows


def test_roundtrip_matches_host_and_coo(packed_case):
    g, sup, x0, packed = packed_case
    out = np.asarray(spmm_block_ell(
        jnp.asarray(packed.tiles), jnp.asarray(packed.tile_col),
        jnp.asarray(packed.valid), jnp.ones(packed.n_rb, jnp.int32),
        jnp.asarray(packed.x0)))
    host, _ = _subgraph_spmm(sup, x0, np.ones(len(sup), bool))
    coo, rows = _coo_dense_step(sup, packed, x0)
    np.testing.assert_allclose(out[rows][:, :x0.shape[1]], host,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out[:, :x0.shape[1]], coo,
                               rtol=1e-4, atol=1e-4)
    # padding rows and padding feature columns stay exactly zero
    pad_rows = np.setdiff1d(np.arange(packed.n_pad), rows)
    assert np.abs(out[pad_rows]).max(initial=0.0) == 0.0
    assert np.abs(out[:, x0.shape[1]:]).max(initial=0.0) == 0.0


def test_segment_operands_match_host(packed_case):
    """The bucket-padded edge list (segment-sum path) reproduces the same
    step: pad edges carry coefficient zero. build_tiles=False (what the
    segment-mode engine uses) must skip the tile tensor entirely while
    keeping the same edge operands."""
    g, sup, x0, packed = packed_case
    lean = pack_support(sup, x0, np.zeros((sup.n_batch, 64), np.float32),
                        build_tiles=False)
    assert lean.tiles.shape[1] == 0 and lean.valid.size == 0
    assert lean.n_pad == packed.n_pad and lean.n_batch == packed.n_batch
    np.testing.assert_array_equal(lean.src, packed.src)
    np.testing.assert_array_equal(lean.coef, packed.coef)
    assert lean.shape_key("segment") == packed.shape_key("segment")
    acc = np.zeros_like(lean.x0)
    np.add.at(acc, lean.dst, lean.coef[:, None] * lean.x0[lean.src])
    host, _ = _subgraph_spmm(sup, x0, np.ones(len(sup), bool))
    rows = _real_rows(sup, packed)
    np.testing.assert_allclose(acc[rows][:, :x0.shape[1]], host,
                               rtol=1e-4, atol=1e-4)


def test_all_exited_row_block_skip(packed_case):
    """active == 0 everywhere (the whole batch has exited) must touch zero
    tiles: the kernel output is exactly zero."""
    g, sup, x0, packed = packed_case
    out = spmm_block_ell(
        jnp.asarray(packed.tiles), jnp.asarray(packed.tile_col),
        jnp.asarray(packed.valid), jnp.zeros(packed.n_rb, jnp.int32),
        jnp.asarray(packed.x0))
    assert float(jnp.abs(out).max()) == 0.0


def test_masked_block_ell_skips_after_batch_exit(packed_case):
    """With T_s huge everyone exits at T_min=1; the dynamic live flag then
    deactivates every block, so later series entries are exactly zero
    while exit orders remain 1."""
    g, sup, x0, packed = packed_case
    cfg = GNNConfig("sgc", 64, g.num_classes, k=3)
    nai = NAIConfig(t_s=1e9, t_min=1, t_max=3)
    step_active = step_active_blocks(packed.hop_rb, nai.t_max)
    orders, series = infer_batch_masked(
        cfg, nai, None, None, None, None, jnp.asarray(packed.x0),
        jnp.asarray(packed.x_inf), packed.n_batch,
        spmm_impl="block_ell",
        ell=(jnp.asarray(packed.tiles), jnp.asarray(packed.tile_col),
             jnp.asarray(packed.valid)),
        step_active=jnp.asarray(step_active))
    o = np.asarray(orders)
    assert (o == 1).all()
    assert float(jnp.abs(series[2]).max()) == 0.0
    assert float(jnp.abs(series[3]).max()) == 0.0
    # step 1 itself did run
    assert float(jnp.abs(series[1]).max()) > 0.0


def test_bucket_floors_are_respected(packed_case):
    """Explicit buckets act as floors (the engine's high-water marks): the
    packed shapes equal the floor when it exceeds the need."""
    g, sup, x0, packed = packed_case
    bigger = pack_support(sup, x0, np.zeros((sup.n_batch, 64), np.float32),
                          s_bucket=packed.n_pad * 2,
                          tb_bucket=packed.tiles.shape[1] * 2,
                          e_bucket=len(packed.src) * 2)
    assert bigger.n_pad == packed.n_pad * 2
    assert bigger.tiles.shape[1] == packed.tiles.shape[1] * 2
    assert len(bigger.src) == len(packed.src) * 2
    # and the padded operator is unchanged on real rows
    out_a = np.asarray(spmm_block_ell(
        jnp.asarray(packed.tiles), jnp.asarray(packed.tile_col),
        jnp.asarray(packed.valid), jnp.ones(packed.n_rb, jnp.int32),
        jnp.asarray(packed.x0)))
    out_b = np.asarray(spmm_block_ell(
        jnp.asarray(bigger.tiles), jnp.asarray(bigger.tile_col),
        jnp.asarray(bigger.valid), jnp.ones(bigger.n_rb, jnp.int32),
        jnp.asarray(bigger.x0)))
    rows_a = _real_rows(sup, packed)
    rows_b = _real_rows(sup, bigger)
    np.testing.assert_allclose(out_a[rows_a], out_b[rows_b],
                               rtol=1e-5, atol=1e-5)


def test_next_bucket_series():
    assert [next_bucket(x) for x in (1, 2, 3, 4, 5, 7, 9, 13, 25)] == \
        [1, 2, 3, 4, 6, 8, 12, 16, 32]
    assert next_bucket(37, 8) == 48      # {1,2,3}*2^k multiples of 8
    assert next_bucket(1, 8) == 8
    # ratio bound: never more than 1.5x overshoot (above the minimum)
    for x in range(1, 2000):
        b = next_bucket(x)
        assert x <= b < 2 * x
