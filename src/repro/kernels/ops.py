"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; everywhere else they run under
``interpret=True`` (Pallas executes the kernel body in Python/XLA on CPU),
which is how this container validates them.  ``use_pallas=False`` falls back
to the pure-jnp reference path — the serving runtime uses that switch so the
same model code runs on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import codec
from repro.core.api import CompressedTensor
from repro.core.dtypes import FloatFormat
from repro.core.params import EnecParams

from . import ref
from .decompress_matmul import decompress_matmul as _fused
from .decompress_matmul import tiled_matmul as _tiled
from .decompress_matmul import tile_weights_for_fusion  # re-export  # noqa: F401
from .enec_decode import decode_blocks_pallas
from .enec_encode import encode_blocks_pallas
from .idd_scan import idd_scan as _idd_scan


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _idd_scan_jit(x, use_pallas: bool):
    if not use_pallas:
        return ref.idd_scan_ref(x)
    return _idd_scan(x, interpret=_interpret())


def idd_scan(x, use_pallas=None):
    """Batched inclusive prefix sum (B, N) -> (B, N) int32.

    ``use_pallas=None`` (default) defers to the ambient codec's encode
    backend (``repro.core.current_codec()``), like the codec entries the
    batched pipeline caches — the seed hard-defaulted to the Pallas path in
    interpreter mode regardless of backend.
    """
    if use_pallas is None:
        from repro.core.codec_api import current_codec  # lazy: avoids cycle
        use_pallas = current_codec().config.encode_backend == "pallas"
    return _idd_scan_jit(x, use_pallas)


def encode_blocks(bits, fmt: FloatFormat, p: EnecParams,
                  use_pallas: bool = True) -> codec.BlockStreams:
    if not use_pallas:
        return ref.encode_blocks_ref(bits, fmt, p)
    return encode_blocks_pallas(bits, fmt, p, interpret=_interpret())


def pipeline_encoder(fmt: FloatFormat, p: EnecParams, use_pallas: bool = True):
    """Encoder callable for the batched compression pipeline (core.api).

    ``core.api`` jit-caches the result per (fmt, params, block-count bucket),
    so the Pallas kernel drives the stacked single-dispatch encode path the
    same way the reference codec does.
    """
    return jax.jit(functools.partial(encode_blocks, fmt=fmt, p=p,
                                     use_pallas=use_pallas))


def decode_blocks(streams: codec.BlockStreams, n_elems: int,
                  fmt: FloatFormat, p: EnecParams,
                  use_pallas: bool = True):
    if not use_pallas:
        return ref.decode_blocks_ref(streams, n_elems, fmt, p)
    return decode_blocks_pallas(streams, n_elems, fmt, p,
                                interpret=_interpret())


def pipeline_decoder(fmt: FloatFormat, p: EnecParams, n_elems: int,
                     use_pallas: bool = True):
    """Decoder callable for the batched decompression pipeline (core.api).

    Mirror of :func:`pipeline_encoder`: ``core.api`` jit-caches the result
    per (fmt, params, block-count bucket), so the Pallas kernel drives the
    stacked single-dispatch decode path the same way the reference codec
    does.  The kernel accepts the stacked ``(L, B)`` stream layout directly
    (flattened on entry) and bakes ``(b, l)`` in statically, so the cache
    keys the full param tuple on this backend.
    """
    return jax.jit(functools.partial(decode_blocks, n_elems=n_elems,
                                     fmt=fmt, p=p, use_pallas=use_pallas))


def tiled_matmul(x, w):
    """The canonical serve matmul ``x (M, K) @ w (K, N) -> (M, N) f32`` of a
    dense weight: the Pallas kernel on a TPU (where XLA's contraction
    depends on the program around it), ``ref.tiled_matmul_ref`` elsewhere —
    bit-identical to each other and to :func:`decompress_matmul`."""
    if _interpret():
        return ref.tiled_matmul_ref(x, w)
    return _tiled(x, w, interpret=False)


def decompress_matmul(x, ct: CompressedTensor, k: int, n: int,
                      use_pallas: bool = True):
    """x @ W with W resident only in ENEC-compressed form."""
    if not use_pallas:
        return ref.decompress_matmul_ref(x, ct, k, n)
    return _fused(x, ct, k, n, interpret=_interpret())
