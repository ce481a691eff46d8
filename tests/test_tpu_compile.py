"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is only
described (``jax.experimental.topologies``), so these tests catch what
interpret mode cannot — block shapes the TPU lowering refuses, ops Mosaic
cannot lower, kernels that overflow VMEM — at StableLM-3B widths, with no
chip attached.  Nothing runs; numerics are covered by the interpret-mode
tests (test_kernels.py).

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import codec
from repro.core.api import CompressedTensor
from repro.core.dtypes import BF16
from repro.core.params import EnecParams
from repro.kernels.decompress_matmul import decompress_matmul, tiled_matmul
from repro.kernels.enec_decode import decode_blocks_pallas
from repro.kernels.enec_encode import encode_blocks_pallas
from repro.kernels.idd_scan import idd_scan

# what the codec's search picks for randomly initialised bf16 weights
PARAMS = EnecParams(b=122, n=8, m=3, L=16, l=0)
BLOCK = 16384
K, N = 2560, 6912            # StableLM-3B d_model x d_ff
TILES = (K // 128) * (N // 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out while this
    # module runs, and give later modules in the worker their setting back
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _streams(nblocks, sharding):
    w = codec.stream_shapes(BLOCK, BF16, PARAMS)
    return codec.BlockStreams(
        mask=_sds((nblocks, w["mask"]), jnp.uint8, sharding),
        low=_sds((nblocks, w["low"]), jnp.uint8, sharding),
        high=_sds((nblocks, w["high"]), jnp.uint8, sharding),
        high_len=_sds((nblocks,), jnp.int32, sharding),
        raw=_sds((nblocks, w["raw"]), jnp.uint8, sharding))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
    return compiled


def test_encode_kernel_compiles(one_chip):
    _compile(lambda b: encode_blocks_pallas(b, BF16, PARAMS,
                                            interpret=False),
             _sds((TILES, BLOCK), jnp.uint16, one_chip))


def test_decode_kernel_compiles(one_chip):
    _compile(lambda s: decode_blocks_pallas(s, BLOCK, BF16, PARAMS,
                                            interpret=False),
             _streams(TILES, one_chip))


def test_idd_scan_kernel_compiles(one_chip):
    _compile(lambda x: idd_scan(x, interpret=False),
             _sds((TILES, 1024), jnp.int32, one_chip))


@pytest.mark.parametrize("m", [4, 4 * 128], ids=["decode_batch",
                                                "prompt_x_batch"])
def test_fused_decompress_matmul_compiles(one_chip, m):
    def fn(x, streams):
        ct = CompressedTensor(
            streams=streams, raw_bytes=None, fmt_name="bf16", params=PARAMS,
            shape=(TILES * BLOCK,), dtype_str="bfloat16", block_elems=BLOCK,
            shards=1, mode="enec")
        return decompress_matmul(x, ct, K, N, interpret=False)

    _compile(fn, _sds((m, K), jnp.bfloat16, one_chip),
             _streams(TILES, one_chip))


# the dense serve matmul at its widest weights: one strip per step for the
# 6912-row w_down, four for wq, three for the 50304-column head
@pytest.mark.parametrize("kn", [(K, K), (N, K), (K, 50304)],
                         ids=["wq", "w_down", "head"])
@pytest.mark.parametrize("m", [4, 128], ids=["decode_batch", "prompt"])
def test_tiled_matmul_compiles(one_chip, m, kn):
    k, n = kn
    _compile(lambda x, w: tiled_matmul(x, w, interpret=False),
             _sds((m, k), jnp.bfloat16, one_chip),
             _sds((k, n), jnp.bfloat16, one_chip))
