"""ENEC block compression as a Pallas TPU kernel.

``lanes.ROWS`` blocks per grid step, one per sublane row (the layout of
``enec_decode``).  Mirrors ``codec.encode_blocks``:

  split fields -> branch-free linear map -> group OR (replaces reduction
  max, §V-B) -> anomaly mask -> prefix count of anomalous groups ->
  compaction network moving the anomalous groups' high bits into rank
  order -> hierarchical halving pack.

The compaction is the inverse of the decode expansion: every anomalous
group moves left by the number of clean groups before it, one bit of that
displacement per stage (``kernels/lanes.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bitio, codec, transform
from repro.core.dtypes import FloatFormat, split_fields
from repro.core.params import EnecParams

from . import lanes


def encode_rows(bits, *, n_elems: int, fmt: FloatFormat, p: EnecParams):
    """Encode one block per row: ``(R, n_elems)`` int32 bit patterns ->
    int32 byte rows ``(mask, low, high, raw)`` and ``(R, 1)`` high_len."""
    L = p.L
    exp, raw = split_fields(bits, fmt, dtype=jnp.int32)
    y = transform.forward(exp, p.b, p.n)
    flag = (lanes.group_or(y >> p.m, L) != 0).astype(jnp.int32)
    mask = lanes.mask_bytes(flag, L)
    low = bitio.pack_fixed(y & ((1 << p.m) - 1), p.m, lane_dtype=jnp.int32)
    if p.n > p.m:
        lane = lanes.lane_iota(y.shape)
        count = lanes.group_count(flag, L)
        word = lanes.routed_word(flag, y >> p.m, lane // L - (count - flag))
        dense = lanes.routed_payload(lanes.compact(word, L, n_elems // L))
        high = bitio.pack_fixed(dense, p.n - p.m, lane_dtype=jnp.int32)
        high_len = count[:, -1:] * (L * (p.n - p.m))
    else:
        high = jnp.zeros((y.shape[0], 1), jnp.int32)
        high_len = jnp.zeros((y.shape[0], 1), jnp.int32)
    rawp = bitio.pack_fixed(raw, fmt.raw_bits, lane_dtype=jnp.int32)
    return mask, low, high, high_len, rawp


def _encode_kernel(bits_ref, mask_ref, low_ref, high_ref, hlen_ref, raw_ref,
                   *, n_elems, fmt, p):
    mask, low, high, high_len, rawp = encode_rows(
        lanes.to_int32(bits_ref[...]), n_elems=n_elems, fmt=fmt, p=p)
    mask_ref[...] = mask.astype(jnp.uint8)
    low_ref[...] = low.astype(jnp.uint8)
    high_ref[...] = high.astype(jnp.uint8)
    hlen_ref[...] = high_len
    raw_ref[...] = rawp.astype(jnp.uint8)


def encode_blocks_pallas(bits, fmt: FloatFormat, p: EnecParams, *,
                         interpret: bool = True) -> codec.BlockStreams:
    """Pallas counterpart of ``codec.encode_blocks`` (same layout)."""
    nblocks, n_elems = bits.shape
    widths = codec.stream_shapes(n_elems, fmt, p)
    rows = lanes.step_rows(nblocks)

    def spec(nbytes):
        return pl.BlockSpec((rows, max(nbytes, 1)), lambda i: (i, 0))

    out_shape = (
        jax.ShapeDtypeStruct((nblocks, widths["mask"]), jnp.uint8),
        jax.ShapeDtypeStruct((nblocks, widths["low"]), jnp.uint8),
        jax.ShapeDtypeStruct((nblocks, max(widths["high"], 1)), jnp.uint8),
        jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
        jax.ShapeDtypeStruct((nblocks, widths["raw"]), jnp.uint8),
    )
    fn = pl.pallas_call(
        functools.partial(_encode_kernel, n_elems=n_elems, fmt=fmt, p=p),
        grid=(pl.cdiv(nblocks, rows),),
        in_specs=[pl.BlockSpec((rows, n_elems), lambda i: (i, 0))],
        out_specs=(spec(widths["mask"]), spec(widths["low"]),
                   spec(widths["high"]), spec(1), spec(widths["raw"])),
        out_shape=out_shape,
        interpret=interpret,
        name="enec_encode",
    )
    mask, low, high, hlen, raw = fn(bits)
    return codec.BlockStreams(
        mask=mask, low=low, high=high[:, :widths["high"]],
        high_len=hlen[:, 0], raw=raw)
