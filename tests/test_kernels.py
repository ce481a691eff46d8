"""Pallas kernels vs pure-jnp oracles: shape/dtype/parameter sweeps.

These run the kernels under interpret=True (kernel body executed on CPU);
codecs must be element-EXACT, the fused GEMM matches to f32
accumulation-order tolerance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BF16, FP16, FP32, EnecParams, codec
from repro.core import search_for_array
from repro.kernels import ops, ref
from repro.kernels.ops import decompress_matmul, tile_weights_for_fusion
from conftest import make_realistic_bf16

FMTS = {"bf16": (BF16, jnp.bfloat16), "fp16": (FP16, jnp.float16),
        "fp32": (FP32, jnp.float32)}


def _blocks_for(fmt_key, n_elems, nblocks, seed=0):
    fmt, dt = FMTS[fmt_key]
    rng = np.random.default_rng(seed)
    w = rng.standard_normal(nblocks * n_elems) * 0.02
    w[rng.random(w.size) < 3e-3] *= 32
    x = jnp.asarray(w.astype("float32")).astype(dt)
    p = search_for_array(np.asarray(jax.device_get(x)), fmt,
                         block_elems=n_elems)
    return codec.to_blocks(x, fmt, n_elems), fmt, p


@pytest.mark.parametrize("shape", [(1, 128), (4, 1024), (2, 4096), (3, 2048)])
def test_idd_scan_matches_cumsum(shape):
    rng = np.random.default_rng(shape[1])
    x = jnp.asarray((rng.random(shape) < 0.3).astype(np.int32))
    # use_pallas=True pins the kernel path (the default defers to the
    # pipeline backend selection, which is "reference" here)
    np.testing.assert_array_equal(np.asarray(ops.idd_scan(x, use_pallas=True)),
                                  np.asarray(ref.idd_scan_ref(x)))


@pytest.mark.parametrize("fmt_key", list(FMTS))
@pytest.mark.parametrize("n_elems", [2048, 16384])
def test_encode_decode_kernels_exact(fmt_key, n_elems):
    bits, fmt, p = _blocks_for(fmt_key, n_elems, nblocks=2)
    s_ref = codec.encode_blocks(bits, fmt, p)
    s_ker = ops.encode_blocks(bits, fmt, p)
    for name in s_ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(s_ker, name)),
            np.asarray(getattr(s_ref, name)), err_msg=f"stream {name}")
    out = ops.decode_blocks(s_ref, n_elems, fmt, p)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(bits))


@pytest.mark.parametrize("m,n_width,L", [(1, 4, 16), (3, 6, 16), (5, 6, 32),
                                         (2, 7, 64), (6, 6, 16)])
def test_decode_kernel_param_grid(m, n_width, L):
    n_elems = 4096
    rng = np.random.default_rng(m * 10 + n_width)
    w = rng.standard_normal(2 * n_elems) * 0.02
    x = jnp.asarray(w.astype("float32")).astype(jnp.bfloat16)
    host = np.asarray(jax.device_get(x)).view(np.uint16)
    exp = (host >> 7) & 0xFF
    p = EnecParams(b=int(exp.max()), n=n_width, m=min(m, n_width), L=L,
                   l=int(exp.min()))
    if (int(exp.max()) - int(exp.min())) >= (1 << n_width):
        pytest.skip("params not injective for this draw")
    bits = codec.to_blocks(x, BF16, n_elems)
    s = codec.encode_blocks(bits, BF16, p)
    out = ops.decode_blocks(s, n_elems, BF16, p)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(bits))


def _fused_case(m, k, n, dt, seed):
    rng = np.random.default_rng(seed)
    wm = jnp.asarray((rng.standard_normal((k, n)) * 0.02
                      ).astype("float32")).astype(dt)
    ct = tile_weights_for_fusion(wm)   # per-stack searched params (pipeline)
    x = jnp.asarray(rng.standard_normal((m, k)).astype("float32")).astype(dt)
    return wm, ct, x


def _assert_fused_exact(x, ct, wm, k, n):
    got = decompress_matmul(x, ct, k, n)
    # the kernel realizes tiled_matmul_ref's exact schedule: bit-identical
    want = ref.decompress_matmul_ref(x, ct, k, n)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
    want2 = ref.tiled_matmul_ref(x, wm)  # decompression is lossless
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want2).view(np.uint32))
    # and against the plain uncompressed matmul (accumulation-order tol)
    direct = np.asarray(jnp.dot(x.astype(jnp.float32),
                                wm.astype(jnp.float32)))
    np.testing.assert_allclose(np.asarray(got), direct, rtol=2e-2, atol=1e-2)


# non-square tile counts (2x3, 4x2) and ragged K/N that ride the
# zero-padded tile layout (250 -> 256, 120 -> 128)
@pytest.mark.parametrize("mkn", [(8, 256, 384), (16, 128, 128),
                                 (4, 512, 256), (8, 250, 384),
                                 (4, 128, 120)])
def test_fused_decompress_matmul(mkn):
    m, k, n = mkn
    wm, ct, x = _fused_case(m, k, n, jnp.bfloat16, seed=k + n)
    _assert_fused_exact(x, ct, wm, k, n)


@pytest.mark.parametrize("fmt_key", ["fp16", "fp32"])
def test_fused_decompress_matmul_formats(fmt_key):
    _, dt = FMTS[fmt_key]
    m, k, n = 4, 256, 128
    wm, ct, x = _fused_case(m, k, n, dt, seed=11)
    assert ct.fmt_name == fmt_key
    _assert_fused_exact(x, ct, wm, k, n)


def test_fused_matmul_no_high_stream_edge():
    # m == n: every exponent fits the low stream, the high stream has zero
    # width and the kernel substitutes a dummy byte
    rng = np.random.default_rng(7)
    k, n = 256, 128
    wm = jnp.asarray((rng.standard_normal((k, n)) * 0.02
                      ).astype("float32")).astype(jnp.bfloat16)
    exp = ((np.asarray(jax.device_get(wm)).view(np.uint16) >> 7) & 0xFF)
    lo, hi = int(exp.min()), int(exp.max())
    nb = max((hi - lo).bit_length() + 1, 2)
    p = EnecParams(b=hi, n=nb, m=nb, L=16, l=lo)
    ct = tile_weights_for_fusion(wm, p)
    assert codec.stream_shapes(128 * 128, BF16, ct.params)["high"] == 0
    x = jnp.asarray(rng.standard_normal((4, k)).astype("float32"))
    _assert_fused_exact(x, ct, wm, k, n)


def test_fused_matmul_stacked_streams_slice_in_scan():
    # (L, K, N) weights compress as one stacked dispatch; lax.scan slices
    # the tile streams per layer and feeds the kernel unmodified
    import dataclasses as dc
    from repro.core.api import tile_weights_for_fusion_many
    rng = np.random.default_rng(3)
    L, k, n = 3, 256, 128
    ws = jnp.asarray((rng.standard_normal((L, k, n)) * 0.02
                      ).astype("float32")).astype(jnp.bfloat16)
    ct = tile_weights_for_fusion_many([ws])[0]
    assert ct is not None and ct.streams.mask.shape[0] == L
    x = jnp.asarray(rng.standard_normal((4, k)).astype("float32"))

    def body(carry, streams):
        out = decompress_matmul(carry, dc.replace(ct, streams=streams), k, n)
        return carry, out

    _, outs = jax.jit(lambda c, s: jax.lax.scan(body, c, s))(x, ct.streams)
    for i in range(L):
        want = ref.tiled_matmul_ref(x, ws[i])
        np.testing.assert_array_equal(np.asarray(outs[i]).view(np.uint32),
                                      np.asarray(want).view(np.uint32))


# dense tile grid of the serve matmul: one and several strips per grid
# step (N tiles 2, 3, 4), M above the 128-row block, ragged K/N
@pytest.mark.parametrize("mkn", [(8, 256, 384), (4, 384, 512),
                                 (130, 256, 256), (8, 250, 120)])
def test_tiled_matmul_kernel_matches_schedule(mkn):
    from repro.kernels.decompress_matmul import tiled_matmul
    m, k, n = mkn
    wm, ct, x = _fused_case(m, k, n, jnp.bfloat16, seed=m + k + n)
    got = np.asarray(tiled_matmul(x, wm, interpret=True)).view(np.uint32)
    want = np.asarray(ref.tiled_matmul_ref(x, wm)).view(np.uint32)
    np.testing.assert_array_equal(got, want)
    # and the fused kernel on the compressed tiles of the same weight
    fused = np.asarray(decompress_matmul(x, ct, k, n)).view(np.uint32)
    np.testing.assert_array_equal(got, fused)


def test_kernel_jit_wrappers():
    bits, fmt, p = _blocks_for("bf16", 2048, nblocks=1)
    s = ops.encode_blocks(bits, fmt, p, use_pallas=False)
    out = ops.decode_blocks(s, 2048, fmt, p, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(bits))
