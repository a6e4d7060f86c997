"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e (no chip attached): the TPU compiler refuses what interpret mode
cannot show — illegal block tilings, scalar-prefetched tables larger than
SMEM, programs larger than HBM.

Shapes are the arxiv-like deployment's (169,343 nodes, f=128): the block-ELL
tile tables of a T_max=2 support at batch 64 — (4096, 128), 2.15 GB of
tiles, one chip — and at batch 512 — (16384, 256), 17.2 GB of tiles, more
than one chip's 16 GB of HBM, so compiled row-sharded over the 2x2 mesh as
sharded serving runs it. Widths are 128 (arxiv) and 512 (pubmed/flickr
f=500, padded to the feature block).

The topology is described inside a module-scope fixture, never while the
module is imported: only one process may hold the TPU library, and the
tests run under several workers."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.nap_exit.kernel import nap_exit
from repro.kernels.nap_step.kernel import nap_step_fused
from repro.kernels.spmm.kernel import CB, RB, spmm_block_ell

WIDTHS = (128, 512)
BATCH = 512          # batch-region rows of the fused kernel's exit state


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _tile_operands(n_rb, tb, f, sharding_of):
    """Shapes of (tiles, tile_col, valid, active, x) with each array's
    sharding from `sharding_of(name)`."""
    shapes = {"tiles": ((n_rb, tb, RB, CB), jnp.float32),
              "tile_col": ((n_rb, tb), jnp.int32),
              "valid": ((n_rb, tb), jnp.int32),
              "active": ((n_rb,), jnp.int32),
              "x": ((n_rb * RB, f), jnp.float32)}
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding_of(k))
            for k, (s, dt) in shapes.items()]


def _fused_extras(f, sharding):
    return [jax.ShapeDtypeStruct((BATCH, 1), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((1, f), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((BATCH, 1), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((1,), jnp.float32, sharding=sharding)]


def _spmm(*a):
    return spmm_block_ell(*a, interpret=False)


def _fused(*a):
    return nap_step_fused(*a, interpret=False)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("f", WIDTHS)
@pytest.mark.parametrize("kernel", ["spmm_block_ell", "nap_step_fused"])
def test_tile_kernels_compile_one_chip(one_chip, kernel, f):
    args = _tile_operands(4096, 128, f, lambda _: one_chip)
    fn = _spmm
    if kernel == "nap_step_fused":
        args += _fused_extras(f, one_chip)
        fn = _fused
    _assert_kernel(jax.jit(fn).lower(*args).compile())


@pytest.mark.parametrize("f", WIDTHS)
@pytest.mark.parametrize("kernel", ["spmm_block_ell", "nap_step_fused"])
def test_tile_kernels_compile_row_sharded(mesh, kernel, f):
    """(16384, 256) tile tables row-sharded over four chips; each shard's
    kernel reads the gathered (replicated) frontier, as in
    `repro.gnn.backends.run_propagation`."""
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    n_rb = 16384
    args = _tile_operands(n_rb, 256, f, lambda k: rep if k == "x" else rows)
    args[-1] = jax.ShapeDtypeStruct((n_rb * RB, f), jnp.float32,
                                    sharding=rep)
    in_specs = (P("data"),) * 4 + (P(),)
    fn = _spmm
    if kernel == "nap_step_fused":
        args += _fused_extras(f, rows)
        args[-1] = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
        args[-3] = jax.ShapeDtypeStruct((1, f), jnp.float32, sharding=rep)
        in_specs += (P("data"), P(), P("data"), P())
        fn = _fused
    out_specs = (P("data") if kernel == "spmm_block_ell"
                 else (P("data"), P("data"), P("data")))
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    _assert_kernel(jax.jit(sharded).lower(*args).compile())


@pytest.mark.parametrize("f", WIDTHS)
def test_nap_exit_compiles(one_chip, f):
    x = jax.ShapeDtypeStruct((BATCH, f), jnp.float32, sharding=one_chip)
    active = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda a, b, c: nap_exit(a, b, c, 3.0,
                                                interpret=False)
                       ).lower(x, x, active).compile()
    _assert_kernel(compiled)
