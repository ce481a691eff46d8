"""Pure-jnp oracles for every Pallas kernel (the ground truth for tests).

Each function mirrors the exact signature/layout of its kernel counterpart
in ops.py; tests sweep shapes/dtypes/params and assert element-exact
equality (these are *lossless* codecs — allclose with atol=0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import codec
from repro.core.api import CompressedTensor
from repro.core.dtypes import FloatFormat
from repro.core.params import EnecParams


def idd_scan_ref(x):
    """Inclusive prefix sum along the last axis, int32."""
    return jnp.cumsum(x.astype(jnp.int32), axis=-1, dtype=jnp.int32)


def encode_blocks_ref(bits, fmt: FloatFormat, p: EnecParams):
    return codec.encode_blocks(bits, fmt, p)


def decode_blocks_ref(streams, n_elems: int, fmt: FloatFormat, p: EnecParams):
    return codec.decode_blocks(streams, n_elems, fmt, p)


def tiled_matmul_ref(x, w):
    """Canonical serve matmul: x (M, K) @ w (K, N) -> (M, N) f32 realizing
    the fused kernel's exact schedule — the K axis cut into 128-wide
    tiles, every (M, 128) @ (128, N) tile product computed by the kernel's
    own :func:`~repro.kernels.decompress_matmul.tile_dot`, then summed in
    k order (ragged edges zero padded).

    This is the numeric contract of the weight-execution abstraction
    (runtime/weights.py): every mode's ``matmul`` is either this function on
    a materialized weight or the Pallas kernel on compressed tiles, and the
    two are bit-identical by construction (same dot shapes, same values,
    same accumulation order) — which is what makes dense / stream / fused
    serve logits bit-identical.  The tile products are one batched dot, so
    the program stays small at any width.
    """
    from .decompress_matmul import TILE, tile_dot
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    kp, np_ = -(-k // TILE) * TILE, -(-n // TILE) * TILE
    if kp != k:
        x = jnp.pad(x, ((0, 0), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    kt = kp // TILE
    parts = tile_dot(x.reshape(m, kt, TILE).transpose(1, 0, 2),
                     w.reshape(kt, TILE, np_))          # (kt, M, N)
    out = parts[0]
    for i in range(1, kt):                             # the kernel's k order
        out = out + parts[i]
    return out[:, :n] if np_ != n else out


def decompress_matmul_ref(x, ct: CompressedTensor, k: int, n: int):
    """Decompress-untile-then-matmul: the fused kernel must match this
    *bit-exactly* (both sides realize :func:`tiled_matmul_ref`)."""
    from repro.core.codec_api import current_codec
    return tiled_matmul_ref(x, current_codec().untile_matmul_weight(ct, k, n))
