"""Compile every sparse Pallas kernel for a described TPU v5e.

Nothing here runs on a chip: each test lowers a kernel at paper-gnn
widths (N = 16384, D = 256 / 128 / 16, K = 256 / 2) with the default
64x64 blocks and compiles it for a v5e that is described, not attached.
The TPU compiler refuses what interpret mode accepts — a tile whose minor
dimension is neither a multiple of 128 nor the whole array dimension —
so these compiles guard the chip path at no chip time.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

N = 16384          # paper-gnn node count
BM = BN = 64       # the repo's default block geometry
NBR = N // BM      # block rows
W = 224            # Block-ELL width of the 16384-node, degree-8 graph
T = 65536          # live tiles of a 99%-sparse SELL pack at this size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _lower(fn, *args, **static):
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args)


def test_spmm_blockell_compiles(one_chip):
    from repro.kernels.spmm.kernel import spmm_blockell_kernel

    _assert_kernel(_lower(
        spmm_blockell_kernel,
        _sds((NBR, W), jnp.int32, one_chip),
        _sds((NBR, W, BM, BN), jnp.float32, one_chip),
        _sds((N, 256), jnp.float32, one_chip), bd=256))


def test_spmm_blockell_epilogue_compiles(one_chip):
    from repro.kernels.fused.epilogue import Epilogue
    from repro.kernels.fused.spmm import spmm_blockell_epilogue_kernel

    epi = Epilogue(act="relu", has_bias=True)
    _assert_kernel(_lower(
        spmm_blockell_epilogue_kernel,
        _sds((NBR, W), jnp.int32, one_chip),
        _sds((NBR, W, BM, BN), jnp.float32, one_chip),
        _sds((N, 128), jnp.float32, one_chip),
        _sds((1, 128), jnp.float32, one_chip), None, epi=epi, bd=128))


def test_spmm_sell_compiles(one_chip):
    from repro.kernels.spmm.sell import spmm_sell_kernel

    _assert_kernel(_lower(
        spmm_sell_kernel,
        _sds((T,), jnp.int32, one_chip), _sds((T,), jnp.int32, one_chip),
        _sds((T, BM, BN), jnp.float32, one_chip),
        _sds((N, 256), jnp.float32, one_chip),
        n_live_block_rows=NBR, bd=256))


def test_spmm_sell_epilogue_compiles(one_chip):
    from repro.kernels.fused.epilogue import Epilogue
    from repro.kernels.fused.spmm import spmm_sell_epilogue_kernel

    epi = Epilogue(act="relu", has_bias=True)
    _assert_kernel(_lower(
        spmm_sell_epilogue_kernel,
        _sds((T,), jnp.int32, one_chip), _sds((T,), jnp.int32, one_chip),
        _sds((T, BM, BN), jnp.float32, one_chip),
        _sds((N, 16), jnp.float32, one_chip),
        _sds((1, 16), jnp.float32, one_chip), None,
        epi=epi, n_live_block_rows=NBR, bd=16))


@pytest.mark.parametrize("k", [256, 2])
def test_sddmm_blockcoo_compiles(one_chip, k):
    from repro.kernels.sddmm.kernel import sddmm_blockcoo_kernel

    _assert_kernel(_lower(
        sddmm_blockcoo_kernel,
        _sds((T,), jnp.int32, one_chip), _sds((T,), jnp.int32, one_chip),
        _sds((T, BM, BN), jnp.float32, one_chip),
        _sds((N, k), jnp.float32, one_chip),
        _sds((N, k), jnp.float32, one_chip), bk=k))


@pytest.mark.parametrize("k", [256, 2])
def test_sddmm_sell_compiles(one_chip, k):
    from repro.kernels.sddmm.sell import sddmm_sell_kernel

    _assert_kernel(_lower(
        sddmm_sell_kernel,
        _sds((T,), jnp.int32, one_chip), _sds((T,), jnp.int32, one_chip),
        _sds((T, BM, BN), jnp.float32, one_chip),
        _sds((N, k), jnp.float32, one_chip),
        _sds((N, k), jnp.float32, one_chip), bk=k))


def test_fused_attention_blockell_compiles(one_chip):
    from repro.kernels.fused.attention import fused_attn_blockell_kernel

    _assert_kernel(_lower(
        fused_attn_blockell_kernel,
        _sds((NBR, W), jnp.int32, one_chip),
        _sds((NBR, W, BM, BN), jnp.float32, one_chip),
        _sds((N, 2), jnp.float32, one_chip),
        _sds((N, 2), jnp.float32, one_chip),
        _sds((N, 128), jnp.float32, one_chip)))


def test_fused_attention_sell_compiles(one_chip):
    from repro.kernels.fused.attention import fused_attn_sell_kernel

    _assert_kernel(_lower(
        fused_attn_sell_kernel,
        _sds((T,), jnp.int32, one_chip), _sds((T,), jnp.int32, one_chip),
        _sds((T, BM, BN), jnp.float32, one_chip),
        _sds((N, 2), jnp.float32, one_chip),
        _sds((N, 2), jnp.float32, one_chip),
        _sds((N, 128), jnp.float32, one_chip), n_live_block_rows=NBR))


def test_spmm_1p5d_compiles_on_four_chips(topo):
    from repro.core.distributed import spmm_1p5d
    from repro.core.formats import BlockELL

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))

    def sharded(shape, dtype, spec):
        return _sds(shape, dtype, NamedSharding(mesh, spec))

    ell = BlockELL(
        indices=sharded((NBR, 256), jnp.int32, P("data", None)),
        blocks=sharded((NBR, 256, BM, BN), jnp.float32,
                       P("data", None, None, None)),
        nblocks=sharded((NBR,), jnp.int32, P("data")),
        shape=(N, N))
    h = sharded((N, 256), jnp.float32, P("data", None))
    # the described devices are not the running backend, so the kernel
    # is requested explicitly (the backend rule would pick the CPU path)
    lowered = jax.jit(
        lambda e, x: spmm_1p5d(e, x, mesh, use_kernel=True)).lower(ell, h)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
