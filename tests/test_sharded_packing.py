"""Sharded packing is a pure partition + permutation of single-device
packing: same tiles (bitwise), each landing on exactly one shard, row
order moved by the shard-major superblock round-robin — so per-shard
SpMM over the gathered frontier reassembles to the single-device kernel
output BIT-exactly (no multi-device runtime needed: shards are plain
slices of the leading axis)."""
import numpy as np
import pytest

from repro.gnn import load_dataset
from repro.gnn.nai import support_stationary_factors
from repro.gnn.packing import (CB, RB, batch_bucket, pack_support,
                               shard_batch_perm, shard_block_perm,
                               shard_row_perm)
from repro.gnn.sampler import sample_support
from repro.gnn.store import as_store


@pytest.fixture(scope="module")
def graph():
    return load_dataset("pubmed-like", scale=0.03, seed=1)


def _packs(g, batch_size, seed, n_shards, **kw):
    """(sharded, single-device-with-identical-geometry) pack pair."""
    rng = np.random.default_rng(seed)
    batch = rng.choice(g.test_idx, size=batch_size, replace=False)
    sup = sample_support(as_store(g), batch, 2, 0.5)
    x0 = g.features[sup.nodes][:, :64].astype(np.float32)
    c, s = support_stationary_factors(g, sup, x0, 0.5)
    c, s = c.astype(np.float32), s.astype(np.float32)
    x_inf = c[:, None] * s[None, :]
    sh = pack_support(sup, x0, x_inf, n_shards=n_shards,
                      x_inf_factors=(c, s), **kw)
    base = pack_support(sup, x0, x_inf, nb_bucket=sh.n_batch,
                        s_bucket=sh.n_pad, tb_bucket=sh.tiles.shape[1],
                        x_inf_factors=(c, s), **kw)
    assert (base.n_pad, base.n_batch) == (sh.n_pad, sh.n_batch)
    return sup, sh, base


def _rb_perm(n_pad, n_shards):
    """Original row block -> packed row block (blocks move in CB-sized
    groups of CB//RB)."""
    spb = CB // RB
    rb = np.arange(n_pad // RB)
    return shard_block_perm(n_pad // CB, n_shards)[rb // spb] * spb \
        + rb % spb


def _check_partition(sup, sh, base):
    D = sh.n_shards
    rbp = _rb_perm(sh.n_pad, D)
    cbp = shard_block_perm(sh.n_pad // CB, D)
    rowp = shard_row_perm(sh.n_pad, D)

    # tiles are the SAME tiles (bitwise), row-block axis permuted, column
    # ids remapped to packed superblocks — slot order untouched
    np.testing.assert_array_equal(sh.tiles[rbp], base.tiles)
    np.testing.assert_array_equal(sh.valid[rbp], base.valid)
    np.testing.assert_array_equal(
        np.where(base.valid == 1, sh.tile_col[rbp], 0),
        np.where(base.valid == 1, cbp[base.tile_col], 0))
    # every real tile lands on exactly one shard (row blocks partition)
    n_rb_loc = sh.n_rb // D
    per_shard = [int(sh.valid[s * n_rb_loc:(s + 1) * n_rb_loc].sum())
                 for s in range(D)]
    assert sum(per_shard) == int(base.valid.sum())

    # rows, hops, batch-region operands follow their permutations
    np.testing.assert_array_equal(sh.x0[rowp], base.x0)
    np.testing.assert_array_equal(sh.hop_rb[rbp], base.hop_rb)
    bp = shard_batch_perm(sh.n_batch, D)
    np.testing.assert_array_equal(sh.x_inf[bp], base.x_inf)
    np.testing.assert_array_equal(sh.c_inf[bp], base.c_inf)
    np.testing.assert_array_equal(sh.s_inf, base.s_inf)

    # batch rows sit at the FRONT of every shard's row range, in both
    # the full row space and the batch-only space (what lets shard_map
    # slice exits/series with a plain contiguous spec)
    nb_loc, rows_loc = sh.n_batch // D, sh.n_pad // D
    r = np.arange(sh.n_batch)
    np.testing.assert_array_equal(rowp[r] // rows_loc, bp // nb_loc)
    np.testing.assert_array_equal(rowp[r] % rows_loc, bp % nb_loc)


def test_sharded_pack_is_permuted_partition(graph):
    for D, bs, seed in ((2, 37, 0), (4, 24, 1), (8, 16, 2), (3, 40, 3)):
        sup, sh, base = _packs(graph, bs, seed, D)
        _check_partition(sup, sh, base)


def test_sharded_pack_hypothesis(graph):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(bs=st.integers(4, 48), seed=st.integers(0, 31),
           D=st.sampled_from([2, 4]))
    def prop(bs, seed, D):
        sup, sh, base = _packs(graph, bs, seed, D)
        _check_partition(sup, sh, base)

    prop()


def test_sharded_edges_partition(graph):
    """Segment-path edge arrays: every original edge appears on exactly
    one shard (the one owning its destination row), same coefficient,
    original relative order preserved within the shard."""
    for D in (2, 4):
        sup, sh, base = _packs(graph, 30, 5, D, build_tiles=False)
        rowp = shard_row_perm(sh.n_pad, D)
        rows_loc = sh.n_pad // D
        got = []
        for s in range(D):
            real = sh.coef[s] != 0.0
            gdst = sh.dst[s][real] + s * rows_loc   # local -> packed
            assert (gdst // rows_loc == s).all()
            got.append(np.stack([sh.src[s][real], gdst,
                                 sh.coef[s][real]]))
        got = np.concatenate(got, axis=1)
        real_b = base.coef != 0.0
        want = np.stack([rowp[base.src[real_b]], rowp[base.dst[real_b]],
                         base.coef[real_b]])
        # same multiset of (packed src, packed dst, coef)
        assert got.shape == want.shape
        order_g = np.lexsort(got)
        order_w = np.lexsort(want)
        np.testing.assert_array_equal(got[:, order_g], want[:, order_w])
        # per-destination-row contribution order is the original edge
        # order (what keeps sharded segment-sum accumulation identical)
        for s in range(D):
            real = sh.coef[s] != 0.0
            assert (np.diff(np.flatnonzero(real)) > 0).all()


def test_sharded_spmm_reassembles_bit_equal(graph):
    """Slice each shard's tiles, run the kernel against the permuted
    frontier, concatenate, un-permute: bitwise equal to the
    single-device kernel output."""
    import jax.numpy as jnp
    from repro.kernels.spmm import spmm_block_ell

    for D in (2, 4):
        sup, sh, base = _packs(graph, 37, 7, D)
        out_base = np.asarray(spmm_block_ell(
            jnp.asarray(base.tiles), jnp.asarray(base.tile_col),
            jnp.asarray(base.valid), jnp.ones(base.n_rb, jnp.int32),
            jnp.asarray(base.x0)))
        n_rb_loc = sh.n_rb // D
        parts = []
        for s in range(D):
            sl = slice(s * n_rb_loc, (s + 1) * n_rb_loc)
            parts.append(np.asarray(spmm_block_ell(
                jnp.asarray(sh.tiles[sl]), jnp.asarray(sh.tile_col[sl]),
                jnp.asarray(sh.valid[sl]),
                jnp.ones(n_rb_loc, jnp.int32),
                jnp.asarray(sh.x0))))
        out_sh = np.concatenate(parts, axis=0)
        rowp = shard_row_perm(sh.n_pad, D)
        np.testing.assert_array_equal(out_sh[rowp], out_base)


def _halo_packs(g, batch_size, seed, n_shards, **kw):
    """(dense-sharded, halo-sharded) pack pair with identical geometry
    (the halo pack pins the dense pack's buckets, so tiles/valid/rows are
    byte-identical and only the coordinate systems differ)."""
    rng = np.random.default_rng(seed)
    batch = rng.choice(g.test_idx, size=batch_size, replace=False)
    sup = sample_support(as_store(g), batch, 2, 0.5)
    x0 = g.features[sup.nodes][:, :64].astype(np.float32)
    x_inf = np.zeros((sup.n_batch, 64), np.float32)
    dense = pack_support(sup, x0, x_inf, n_shards=n_shards, **kw)
    halo = pack_support(sup, x0, x_inf, n_shards=n_shards, halo=True,
                        nb_bucket=dense.n_batch, s_bucket=dense.n_pad,
                        tb_bucket=dense.tiles.shape[1],
                        e_bucket=dense.src.shape[-1], **kw)
    assert (halo.n_pad, halo.n_batch) == (dense.n_pad, dense.n_batch)
    return dense, halo


def _check_halo_cover(dense, halo):
    """Every shard's halo frame is EXACTLY the sorted union of the global
    CB blocks its tiles/edges reference: no missing block (coverage), no
    dead entry (minimality); frame-local coordinates round-trip to the
    dense pack's global ones; the all_to_all send/recv plan reassembles
    each frame."""
    D = halo.n_shards
    n_cb = halo.n_pad // CB
    n_cb_loc = n_cb // D
    bpad = halo.halo_send_pad
    has_tiles = dense.tiles.shape[1] > 0
    has_edges = dense.src.shape[-1] > 0 and dense.coef.size
    if has_tiles:
        np.testing.assert_array_equal(halo.tiles, dense.tiles)
        np.testing.assert_array_equal(halo.valid, dense.valid)
    n_rb_loc = halo.n_rb // D
    rows_loc = halo.n_pad // D
    for s in range(D):
        c = int(halo.halo_count[s])
        full_frame = (halo.halo_src_shard[s].astype(np.int64) * n_cb_loc
                      + halo.halo_src_block[s])
        frame = full_frame[:c]
        # frames are strictly sorted global block ids (grouped by owner)
        assert (np.diff(frame) > 0).all(), s
        assert c <= n_cb and halo.n_halo_pad >= c
        referenced = []
        if has_tiles:
            sl = slice(s * n_rb_loc, (s + 1) * n_rb_loc)
            v = dense.valid[sl] == 1
            referenced.append(dense.tile_col[sl][v])
            # frame-local tile_col maps back to the dense global blocks
            np.testing.assert_array_equal(
                full_frame[halo.tile_col[sl][v]], dense.tile_col[sl][v])
        if has_edges:
            real = dense.coef[s] != 0.0
            referenced.append(dense.src[s][real] // CB)
            src_h = halo.src[s][real].astype(np.int64)
            np.testing.assert_array_equal(
                full_frame[src_h // CB] * CB + src_h % CB,
                dense.src[s][real])
            # padding edges stay inside the frame
            assert halo.src[s].max() < halo.n_halo_pad * CB
        want = np.unique(np.concatenate(referenced))
        # coverage AND minimality in one shot
        np.testing.assert_array_equal(frame, want)
        # the exchange plan reassembles the frame: sender t's list to s
        # holds exactly s's frame entries owned by t, in frame order
        recv = (np.arange(D, dtype=np.int64)[:, None] * n_cb_loc
                + halo.halo_send_block[:, s, :])        # (D, B_pad) global
        np.testing.assert_array_equal(
            recv.reshape(-1)[halo.halo_frame_src[s, :c]], frame)
        assert bpad == halo.halo_send_block.shape[2]
    # every send-list slot is a legal local block id
    assert halo.halo_send_block.min() >= 0
    assert halo.halo_send_block.max() < max(n_cb_loc, 1)
    assert rows_loc % CB == 0


def test_halo_frame_covers_tile_cols(graph):
    for D, bs, seed in ((2, 37, 0), (4, 24, 1), (8, 16, 2), (3, 40, 3)):
        dense, halo = _halo_packs(graph, bs, seed, D)
        _check_halo_cover(dense, halo)
    # segment-path (edges-only) packs get the same guarantee
    for D in (2, 4):
        dense, halo = _halo_packs(graph, 30, 5, D, build_tiles=False)
        _check_halo_cover(dense, halo)


def test_halo_frame_hypothesis(graph):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(bs=st.integers(4, 48), seed=st.integers(0, 31),
           D=st.sampled_from([2, 4]))
    def prop(bs, seed, D):
        dense, halo = _halo_packs(graph, bs, seed, D)
        _check_halo_cover(dense, halo)

    prop()


def test_halo_shrinks_frame_on_padded_batches(graph):
    """The batch region pads to CB*D, so pure-padding superblocks exist
    and are never referenced — the halo frame must be strictly smaller
    than the dense frontier here (the --check guarantee)."""
    for D in (2, 4):
        _, halo = _halo_packs(graph, 24, 9, D)
        assert halo.halo_frac < 1.0, (D, halo.halo_frac)
        assert halo.halo_rows <= halo.n_halo_pad * CB <= halo.n_pad


def test_batch_bucket_alignment():
    assert batch_bucket(32) == 32            # RB-aligned single-device
    assert batch_bucket(32, 2) == CB * 2     # CB*D-aligned sharded
    assert batch_bucket(500, 4) == 512
    assert batch_bucket(CB * 4 + 1, 4) % (CB * 4) == 0


def test_sharded_bucket_floor_validation(graph):
    rng = np.random.default_rng(0)
    batch = rng.choice(graph.test_idx, size=16, replace=False)
    sup = sample_support(as_store(graph), batch, 2, 0.5)
    x0 = graph.features[sup.nodes][:, :64].astype(np.float32)
    x_inf = np.zeros((sup.n_batch, 64), np.float32)
    with pytest.raises(ValueError):
        pack_support(sup, x0, x_inf, n_shards=2,
                     s_bucket=CB * 3)            # not a CB*2 multiple
    with pytest.raises(ValueError):
        pack_support(sup, x0, x_inf, n_shards=2, nb_bucket=CB * 5)
