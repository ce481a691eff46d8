"""ENEC block decompression as a Pallas TPU kernel.

Each grid step decodes ``lanes.ROWS`` blocks, one per sublane row: the
stream tiles arrive as ``(8, nbytes)`` slabs of the flat ``(B, nbytes)``
uint8 streams (no relayout in HBM; the last step may be partial and its
extra rows are never written back), and every intermediate is an
``(8, N)`` int32 array on the block's flat element order.

TPU adaptations inside the body (see ``kernels/lanes.py``):
  * anomaly mask -> per-lane flags: one lane broadcast per mask byte
  * prefix count over the anomaly mask (the paper's IDD-Scan target) ->
    log-step scan over group-strided lane shifts
  * reverse gather of the rank-ordered high bits -> a shift network: a
    compaction routes each anomalous group's displacement to its rank
    slot, an expansion carries the high bits back out to the group
  * exponent inverse mapping -> branch-free linear transform (VPU add/and)
  * bit-unpacking -> static halving un-fold on the lane axis, in int32
    lanes (the TPU VPU has no 16-bit shifts)

The pure-jnp oracle is ``repro.core.codec.decode_blocks`` (see ref.py); the
kernel is verified element-exact against it across shape/dtype/param sweeps
(including all-anomaly, zero-anomaly, and tail-padded blocks).

Streams may carry the batched pipeline's stacked ``(L, [shards,] B, ...)``
leading layout — it is flattened to one block axis on entry, so
``kernels.ops.pipeline_decoder`` drives whole-stack decode exactly like
``pipeline_encoder`` does for encode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bitio, codec, transform
from repro.core.dtypes import FloatFormat, combine_fields
from repro.core.params import EnecParams

from . import lanes


def decode_rows(mask, low, high, raw, *, n_elems: int, fmt: FloatFormat,
                p: EnecParams):
    """Decode one block per row: int32 stream rows ``(R, nbytes)`` ->
    ``(R, n_elems)`` int32 bit patterns."""
    L = p.L
    y = bitio.unpack_fixed(low, n_elems, p.m, out_dtype=jnp.int32)
    if p.n > p.m:
        lane = lanes.lane_iota(y.shape)
        byte = lanes.byte_per_lane(mask, L, n_elems)
        flag = (byte >> ((lane // L) & 7)) & 1
        rank = lanes.group_count(flag, L) - flag
        home = lanes.compact(lanes.routed_word(flag, 0, lane // L - rank),
                             L, n_elems // L)
        dense = bitio.unpack_fixed(high, n_elems, p.n - p.m,
                                   out_dtype=jnp.int32)
        word = jnp.where((home & 1) != 0, home | (dense << 1), 0)
        gathered = lanes.routed_payload(
            lanes.expand(word, L, n_elems // L))
        y = y | (gathered << p.m)
    exp = transform.inverse(y, p.b, p.n, p.l)
    rawv = bitio.unpack_fixed(raw, n_elems, fmt.raw_bits, out_dtype=jnp.int32)
    return combine_fields(exp, rawv, fmt, dtype=jnp.int32)


def decode_block_body(mask_b, low_b, high_b, raw_b, *, n_elems: int,
                      fmt: FloatFormat, p: EnecParams):
    """Decode one block. 1-D uint8 stream slices -> (n_elems,) uint bits."""
    rows = [s[None, :].astype(jnp.int32) for s in (mask_b, low_b, high_b,
                                                    raw_b)]
    bits = decode_rows(*rows, n_elems=n_elems, fmt=fmt, p=p)[0]
    return lanes.to_uint(bits, fmt.uint_dtype)


def _decode_kernel(mask_ref, low_ref, high_ref, raw_ref, out_ref, *,
                   n_elems, fmt, p):
    bits = decode_rows(mask_ref[...].astype(jnp.int32),
                       low_ref[...].astype(jnp.int32),
                       high_ref[...].astype(jnp.int32),
                       raw_ref[...].astype(jnp.int32),
                       n_elems=n_elems, fmt=fmt, p=p)
    out_ref[...] = lanes.to_uint(bits, out_ref.dtype)


def decode_blocks_pallas(streams: codec.BlockStreams, n_elems: int,
                         fmt: FloatFormat, p: EnecParams, *,
                         interpret=None):
    """Pallas counterpart of ``codec.decode_blocks`` (same layout).

    Accepts flat ``(B, ...)`` streams or the stacked ``(L, [shards,] B,
    ...)`` pipeline layout (flattened on entry); returns ``(total_blocks,
    n_elems)`` decoded bits either way.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if streams.mask.ndim > 2:  # stacked pipeline layout: flatten block dims
        streams = codec.flatten_blocks(streams)
    nblocks = streams.mask.shape[0]
    widths = codec.stream_shapes(n_elems, fmt, p)
    rows = lanes.step_rows(nblocks)

    def spec(nbytes):
        return pl.BlockSpec((rows, max(nbytes, 1)), lambda i: (i, 0))

    high = streams.high
    if widths["high"] == 0:  # m == n: no high stream; feed a dummy byte
        high = jnp.zeros((nblocks, 1), jnp.uint8)

    fn = pl.pallas_call(
        functools.partial(_decode_kernel, n_elems=n_elems, fmt=fmt, p=p),
        grid=(pl.cdiv(nblocks, rows),),
        in_specs=[spec(widths["mask"]), spec(widths["low"]),
                  spec(widths["high"]), spec(widths["raw"])],
        out_specs=pl.BlockSpec((rows, n_elems), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, n_elems), fmt.uint_dtype),
        interpret=interpret,
        name="enec_decode",
    )
    return fn(streams.mask, streams.low, high, streams.raw)
