"""ENEC block codec (paper §IV-B basic design + §V optimizations), pure JAX.

A tensor is flattened, zero-padded to a multiple of the 16,384-element block
size, and encoded block-by-block:

  exponent --linear map--> y --group (L)--> 1-bit anomaly mask per group
  low  stream: low ``m`` bits of EVERY element        (fixed length)
  high stream: high ``n-m`` bits of anomalous groups  (block-level variable,
               stored rank-ordered & zero-padded to its static bound)
  raw  stream: sign|mantissa lanes                    (fixed length)

Only block-level variability remains (the key ENEC idea) — every array here
has a static shape, so the codec jits, shards and Pallas-lowers cleanly.

This module is the *reference* path (also used on CPU); the Pallas kernels
in ``repro.kernels`` implement the same layout for the TPU hot path and are
verified against this module element-for-element.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import bitio, transform
from .dtypes import FloatFormat, combine_fields, split_fields
from .params import DEFAULT_BLOCK_ELEMS, EnecParams


class BlockStreams(NamedTuple):
    """Static-shape per-block streams for one tensor (leading dim = blocks)."""
    mask: jax.Array      # (B, G/8)  uint8 — per-group anomaly bits
    low: jax.Array       # (B, packed(N, m)) uint8
    high: jax.Array      # (B, packed(N, n-m)) uint8 — rank-ordered, padded
    high_len: jax.Array  # (B,) int32 — true high-stream length in BITS
    raw: jax.Array       # (B, packed(N, raw_bits)) uint8


def stream_shapes(n_elems: int, fmt: FloatFormat, p: EnecParams):
    """Static byte widths of each stream for an N-element block."""
    g = n_elems // p.L
    return {
        "mask": g // 8,
        "low": bitio.packed_nbytes(n_elems, p.m),
        "high": bitio.packed_nbytes(n_elems, p.n - p.m),
        "raw": bitio.packed_nbytes(n_elems, fmt.raw_bits),
    }


def encode_blocks(bits, fmt: FloatFormat, p: EnecParams,
                  b_vec=None) -> BlockStreams:
    """bits: (B, N) unsigned int view of the floats.

    Shapes are static in (N, p.n, p.m, p.L); the linear-map parameter enters
    only the arithmetic, so ``b_vec`` (a traced (B,) per-block vector) can
    override the static ``p.b`` — the batched pipeline uses this to encode
    stacks with different searched ``b`` in one compiled dispatch.
    """
    nblocks, n = bits.shape
    g = n // p.L
    assert n % p.L == 0 and g % 8 == 0, (n, p.L)

    exp, raw = split_fields(bits, fmt)
    b_sel = p.b if b_vec is None else b_vec
    y = transform.forward(exp.astype(jnp.uint16), b_sel, p.n)  # (B, N), < 2**n

    yg = y.reshape(nblocks, g, p.L)
    # §V-B: bitwise-OR replaces reduction-max — group is anomalous iff any
    # element has a bit at position >= m.
    gor = jax.lax.reduce(yg, jnp.uint16(0), jnp.bitwise_or, (2,))
    anom = (gor >> p.m) != 0  # (B, G)

    mask = bitio.pack_bool_mask(anom)

    low = bitio.pack_fixed(y & jnp.uint16((1 << p.m) - 1), p.m)

    # Rank-ordered dense scatter of anomalous groups' high bits.  Non-anomalous
    # groups have y >> m == 0 everywhere, so their (colliding) writes into the
    # overflow row G are all zeros — deterministic by construction.
    rank = jnp.cumsum(anom, axis=1, dtype=jnp.int32) - anom.astype(jnp.int32)
    target = jnp.where(anom, rank, g)  # (B, G)
    y_high = (yg >> p.m).astype(jnp.uint16)  # (B, G, L)
    batch_ix = jnp.arange(nblocks, dtype=jnp.int32)[:, None]
    high_dense = (
        jnp.zeros((nblocks, g + 1, p.L), jnp.uint16)
        .at[batch_ix, target].set(y_high)[:, :g]
    )
    high = bitio.pack_fixed(high_dense.reshape(nblocks, n), p.n - p.m)
    high_len = (jnp.sum(anom, axis=1, dtype=jnp.int32) * (p.L * (p.n - p.m)))

    rawp = bitio.pack_fixed(raw, fmt.raw_bits)
    return BlockStreams(mask=mask, low=low, high=high, high_len=high_len, raw=rawp)


def decode_blocks(streams: BlockStreams, n_elems: int, fmt: FloatFormat,
                  p: EnecParams, b_vec=None, l_vec=None):
    """Inverse of :func:`encode_blocks` -> (B, N) unsigned int view.

    Shapes are static in (N, p.n, p.m, p.L); the inverse transform's
    ``(b, l)`` only enter the arithmetic, so ``b_vec`` / ``l_vec`` (traced
    (B,) per-block vectors) can override the static ``p.b`` / ``p.l`` — the
    batched decode pipeline uses this to decode tensors with different
    searched params in one compiled dispatch.
    """
    nblocks = streams.mask.shape[0]
    g = n_elems // p.L

    anom = bitio.unpack_bool_mask(streams.mask, g)  # (B, G)
    # Prefix sum over the mask — the paper's IDD-Scan target (§V-D).  The
    # Pallas kernel computes this with the MXU triangular-matmul scan; the
    # reference uses cumsum.
    rank = jnp.cumsum(anom, axis=1, dtype=jnp.int32) - anom.astype(jnp.int32)

    y_low = bitio.unpack_fixed(streams.low, n_elems, p.m).reshape(nblocks, g, p.L)
    high_dense = bitio.unpack_fixed(streams.high, n_elems, p.n - p.m)
    high_dense = high_dense.reshape(nblocks, g, p.L)

    # Reverse gather (paper Alg. 1 line 21): group g reads rank[g]'s row.
    gathered = jnp.take_along_axis(high_dense, rank[:, :, None], axis=1)
    gathered = jnp.where(anom[:, :, None], gathered, jnp.uint16(0))

    y = (y_low | (gathered << p.m)).reshape(nblocks, n_elems)
    b_sel = p.b if b_vec is None else b_vec
    l_sel = p.l if l_vec is None else l_vec
    exp = transform.inverse(y, b_sel, p.n, l_sel)

    raw = bitio.unpack_fixed(streams.raw, n_elems, fmt.raw_bits,
                             out_dtype=fmt.uint_dtype)
    return combine_fields(exp.astype(fmt.uint_dtype), raw, fmt)


def reshape_rows(a, lead):
    """Reshape a stream array ``(..., w)`` to ``lead + (w,)``: only the
    leading (block) dims change.

    On a TPU a uint8 array is tiled 32 rows deep, so splitting or merging
    its row dims at a count that is not a multiple of 32 is a relayout.
    XLA compiles that copy to code proportional to the array: about 20 s
    and 13 MB of code for one stream of StableLM-3B's embedding
    (ahead-of-time compile for a v5e).  Viewed as uint32 words the same
    bytes reshape in well under a second of compiling, but the byte <->
    word views cost shift-and-or passes over the array when they run (the
    compiler's estimate for decoding the embedding grew eightfold).  So a
    concrete array — set-up code, run once per reshape — takes the word
    route, and a traced one — a step program, compiled once and run every
    step — reshapes directly.  Bit-exact either way; numpy arrays reshape
    directly."""
    lead = tuple(int(d) for d in lead)
    w = a.shape[-1]
    if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer) \
            and a.dtype == jnp.uint8 and w % 4 == 0 and w > 0 \
            and a.shape[:-1] != lead:
        words = jax.lax.bitcast_convert_type(
            a.reshape(a.shape[:-1] + (w // 4, 4)), jnp.uint32)
        words = words.reshape(lead + (w // 4,))
        return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(
            lead + (w,))
    return a.reshape(lead + (w,))


def reshape_blocks(s: BlockStreams, lead) -> BlockStreams:
    """Every stream of ``s`` with its block dims reshaped to ``lead``
    (:func:`reshape_rows`; ``high_len`` has no width dim)."""
    return BlockStreams(
        mask=reshape_rows(s.mask, lead), low=reshape_rows(s.low, lead),
        high=reshape_rows(s.high, lead),
        high_len=s.high_len.reshape(tuple(lead)),
        raw=reshape_rows(s.raw, lead))


def flatten_blocks(s: BlockStreams) -> BlockStreams:
    """Collapse every leading ``(L, [shards,] B)`` stream layout to one
    flat block axis — the layout the per-block decoder consumes.  The
    single definition keeps the device pipeline, the Pallas kernel entry,
    and the host wire path on one layout contract (works on numpy arrays
    too).  The block count is explicit (not -1): the high stream has zero
    width when m == n."""
    nblocks = 1
    for d in s.mask.shape[:-1]:
        nblocks *= int(d)
    return reshape_blocks(s, (nblocks,))


# ---------------------------------------------------------------------------
# whole-array helpers (flatten / pad / reshape to blocks)
# ---------------------------------------------------------------------------

def pad_count(size: int, block_elems: int = DEFAULT_BLOCK_ELEMS) -> int:
    return (-size) % block_elems


def to_blocks(x, fmt: FloatFormat, block_elems: int = DEFAULT_BLOCK_ELEMS):
    """float array -> (B, N) bits with zero padding."""
    flat = jnp.ravel(x)
    pad = pad_count(flat.size, block_elems)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
    bits = flat.view(fmt.uint_dtype)
    return bits.reshape(-1, block_elems)


def bits_to_blocks(flat_bits, block_elems: int = DEFAULT_BLOCK_ELEMS,
                   shards: int = 1, pad_value: int = 0):
    """(size,) uint bit view -> ((B, N) blocks, B) — the L=1 case of
    :func:`stacked_blocks` (single definition keeps the per-layer and
    stacked padding rules bit-identical by construction)."""
    return stacked_blocks(flat_bits[None, :], block_elems, shards, pad_value)


def stacked_blocks(bits, block_elems: int = DEFAULT_BLOCK_ELEMS,
                   shards: int = 1, pad_value: int = 0):
    """(L, ...) uint bit view of a layer stack -> ((L*Bs, N) blocks, Bs).

    Row ``l*Bs + b`` equals block ``b`` of layer ``l``, each layer padded to
    the block size and (when ``shards > 1``) to a block count divisible by
    ``shards``, so a single encode of the stacked array is bit-identical to
    L per-layer encodes.  ``pad_value`` should be the bit pattern of the
    modal exponent (the encoder passes ``b << mant_bits``): padding with
    zeros would make every padded group anomalous (exponent 0 is far from
    ``b``) and charge full high-stream bits for data that decode slices
    away.  Device-only: the input is never copied to the host.

    The layer's trailing dims may be kept (``(L, K, N)``) or flat
    (``(L, K*N)``).  An unpadded stack is cut into blocks by reshaping to
    ``(L, Bs, N)`` and then merging the two leading dims, two separate
    eager reshapes: on a TPU, the one-step ``(L, K*N) -> (L*Bs, N)``
    reshape of a full-width weight compiles to about 100 MB of relayout
    code in minutes, the two steps in under a second (ahead-of-time
    compile for a v5e).
    """
    n_layers = bits.shape[0]
    per = 1
    for d in bits.shape[1:]:
        per *= int(d)
    nblocks = (per + block_elems - 1) // block_elems
    if shards > 1:
        nblocks += (-nblocks) % shards
    total_pad = nblocks * block_elems - per
    if total_pad:
        bits = jnp.pad(bits.reshape(n_layers, per), ((0, 0), (0, total_pad)),
                       constant_values=pad_value)
    blocks = bits.reshape(n_layers, nblocks, block_elems)
    return blocks.reshape(n_layers * nblocks, block_elems), nblocks


def from_blocks(bits, shape, fmt: FloatFormat):
    """(B, N) decoded bits -> the float array of ``shape``.  Without block
    padding the bits reshape straight to ``shape`` (a flat 1-D
    intermediate is a slow relayout on a TPU, see :func:`stacked_blocks`)."""
    size = 1
    for s in shape:
        size *= s
    if bits.size == size:
        return bits.view(fmt.float_dtype).reshape(shape)
    flat = bits.reshape(-1).view(fmt.float_dtype)[:size]
    return flat.reshape(shape)
