"""Fused ENEC-decompress + GEMM Pallas kernel (beyond-paper, DESIGN.md §8).

Decode-phase LLM inference is weight-bandwidth bound: every step streams the
full weight matrix HBM -> VMEM for a tiny number of MACs.  Storing weights
ENEC-compressed in HBM and decompressing *inside* the matmul kernel's VMEM
tiles raises effective HBM bandwidth by the compression ratio (~1.35x for
BF16) — the TPU analogue of the paper's CPU->NPU transfer win, one level
down the memory hierarchy.  Decompressed weights never exist in HBM.

Tiling: the weight matrix (K, N) is cut into 128x128 tiles; one tile
(16,384 elements) == exactly one ENEC block, so the paper's preferred block
size doubles as the MXU-aligned tile.  The tile streams are n-major
(``t = n_tile * k_tiles + k_tile``); each grid step decodes ``lanes.ROWS``
consecutive tiles at once (one per sublane, ``enec_decode.decode_rows``)
and feeds them to the MXU in order, accumulating each into its 128-column
strip of the VMEM-resident (M, N) f32 output — so every strip still sums
its K tiles in k order.  Ragged K/N ride the zero-padded tile layout of
``core.api.matmul_tiles``.

The grid schedule (tile order + f32 accumulation) is the *numeric contract*
of the serving stack: ``kernels.ref.tiled_matmul_ref`` realizes the same
schedule in pure jnp, and every weight-execution mode (runtime/weights.py)
routes its matmuls through one of the two — which is what makes dense /
stream / fused serve logits bit-identical.

Oracle: decompress-untile-then-tiled-matmul in pure jnp (ref.py), exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import codec
from repro.core.api import (MATMUL_TILE, CompressedTensor,  # noqa: F401
                            tile_weights_for_fusion,
                            tile_weights_for_fusion_many,
                            untile_matmul_weight)
from repro.core.dtypes import from_bits

from .enec_decode import decode_rows
from .lanes import step_rows, to_uint

TILE = MATMUL_TILE
BLOCK_ELEMS = TILE * TILE  # one ENEC block == one MXU weight tile


def tile_dot(x, w):
    """The one dot of the serve matmul: ``(M, 128) @ (128, N)`` tiles
    (batched over a leading dim when both carry one), f32 accumulation.

    Operands of one 16-bit dtype (bf16 activations against bf16 weights,
    the serving case) contract natively: every product is exact in f32.
    Anything else contracts as f32 at HIGHEST precision.  Either way the
    Mosaic dot here and the XLA dot of ``kernels.ref.tiled_matmul_ref``
    ask the TPU for the same contraction (an f32 dot at default precision
    would be one bf16 pass in XLA but full f32 in Mosaic).
    """
    precision = None
    if x.dtype != w.dtype or jnp.dtype(w.dtype).itemsize != 2:
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
        precision = jax.lax.Precision.HIGHEST
    batch = ((0,), (0,)) if x.ndim == 3 else ((), ())
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (w.ndim - 2,)), batch), precision=precision,
        preferred_element_type=jnp.float32)


# activation rows per grid step: the whole (rows, K) activation block and
# the (rows, N) f32 accumulator stay resident in VMEM across the tile sweep
MAX_ROWS = 128


def _accumulate(o_ref, cols, x_ref, ki, w_tile):
    """``o[:, cols] (+)= x[:, k tile ki] @ w_tile``: the one accumulation
    step of both serve kernels (fused and :func:`tiled_matmul`), so a strip
    sums its K tiles in k order through the same Mosaic ops whether its
    weight tiles were just decoded or read from a dense weight."""
    part = tile_dot(
        x_ref[:, pl.ds(pl.multiple_of(ki * TILE, TILE), TILE)], w_tile)
    if isinstance(ki, int):
        if ki == 0:
            o_ref[:, cols] = part
        else:
            o_ref[:, cols] += part
        return

    @pl.when(ki == 0)
    def _init():
        o_ref[:, cols] = part

    @pl.when(ki != 0)
    def _acc():
        o_ref[:, cols] += part


def _fused_kernel(mask_ref, low_ref, high_ref, raw_ref, x_ref, o_ref, *,
                  fmt, p, k_tiles, n_blocks):
    step = pl.program_id(1)
    rows = mask_ref.shape[0]
    bits = decode_rows(mask_ref[...].astype(jnp.int32),
                       low_ref[...].astype(jnp.int32),
                       high_ref[...].astype(jnp.int32),
                       raw_ref[...].astype(jnp.int32),
                       n_elems=BLOCK_ELEMS, fmt=fmt, p=p)
    for j in range(rows):
        t = step * rows + j   # weight tile t = n_tile * k_tiles + k_tile

        @pl.when(t < n_blocks)
        def _tile(j=j, t=t):
            ni, ki = t // k_tiles, t % k_tiles
            w_tile = from_bits(to_uint(bits[j:j + 1].reshape(TILE, TILE),
                                       fmt.uint_dtype), fmt)
            _accumulate(o_ref, pl.ds(pl.multiple_of(ni * TILE, TILE), TILE),
                        x_ref, ki, w_tile)


def decompress_matmul(x, ct: CompressedTensor, k: int, n: int, *,
                      interpret: bool = True):
    """out = x @ W where W (k, n) is stored only in ENEC tile streams.

    ``x``: (M, K) activations — M is B*T tokens (prefill) or B (decode);
    the serving layers flatten (B, T, K) to (B*T, K) before calling in.
    ``ct``: per-layer tile streams (leading dim = tiles).  A stacked
    ``(L, ...)`` tensor from :func:`tile_weights_for_fusion` must be sliced
    to one layer first — ``lax.scan`` does exactly that when the streams
    ride the scanned params, so the kernel works unmodified inside the
    decode scan.  Ragged k/n are handled by the zero-padded tile layout:
    x is zero-padded to the tile multiple and the output sliced back.

    TP-sharded tile streams (``ct.shards > 1``, layout ``(S, B/S, w)``) are
    accepted: the flat tile order is n-major (``t = n_tile * k_tiles +
    k_tile``) and the shard split is a contiguous partition of that flat
    axis, so collapsing the shard dim restores the exact unsharded layout —
    no data movement, just a reshape.  The streams must be gathered
    (replicated) before the call; ``FusedWeight.matmul`` does this through
    ``collectives.maybe_gather_ct`` under an ambient serving mesh.
    """
    m = x.shape[0]
    assert x.shape[1] == k, (x.shape, k)
    assert ct.mode == "enec", "fused kernel requires enec tile streams"
    kp, np_ = -(-k // TILE) * TILE, -(-n // TILE) * TILE
    k_tiles, n_tiles = kp // TILE, np_ // TILE
    s = ct.streams
    assert s.mask.ndim == (3 if ct.shards > 1 else 2), \
        "stacked streams: slice one layer first"
    if ct.shards > 1:
        # (S, B/S, ...) -> (B, ...): contiguous shard ranges of the n-major
        # flat tile axis — the encode split (stacked_blocks) never pads a
        # fused stream (enforced by tile_weights_for_fusion_many /
        # streaming.fused_shards), so this is the bit-exact inverse
        s = codec.flatten_blocks(s)
    assert s.mask.shape[0] == k_tiles * n_tiles, \
        (s.mask.shape, k_tiles, n_tiles)
    if kp != k:
        x = jnp.pad(x, ((0, 0), (0, kp - k)))
    fmt, p = ct.fmt, ct.params
    widths = codec.stream_shapes(BLOCK_ELEMS, fmt, p)
    high, high_w = s.high, widths["high"]
    if high_w == 0:  # m == n: no high stream; feed a dummy byte
        high = jnp.zeros((s.mask.shape[0], 1), jnp.uint8)
        high_w = 1

    n_blocks = k_tiles * n_tiles
    rows = step_rows(n_blocks)
    bm = m if m <= MAX_ROWS else MAX_ROWS
    mp = -(-m // bm) * bm
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))

    def wspec(nbytes):
        # weight-stream tiles t = n_tile * k_tiles + k_tile, 8 per step
        return pl.BlockSpec((rows, nbytes), lambda mi, si: (si, 0))

    fn = pl.pallas_call(
        functools.partial(_fused_kernel, fmt=fmt, p=p, k_tiles=k_tiles,
                          n_blocks=n_blocks),
        grid=(mp // bm, pl.cdiv(n_blocks, rows)),
        in_specs=[
            wspec(widths["mask"]), wspec(widths["low"]),
            wspec(high_w), wspec(widths["raw"]),
            pl.BlockSpec((bm, kp), lambda mi, si: (mi, 0)),
        ],
        out_specs=pl.BlockSpec((bm, np_), lambda mi, si: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="enec_decompress_matmul",
    )
    out = fn(s.mask, s.low, high, s.raw, x)
    return out[:m, :n]


# VMEM a dense weight block of :func:`tiled_matmul` may take (it is double
# buffered, so twice this is resident)
_DENSE_BLOCK_BYTES = 4 << 20


def _dense_kernel(x_ref, w_ref, o_ref, *, k_tiles, strips):
    for c in range(strips):
        cols = pl.ds(c * TILE, TILE)
        for ki in range(k_tiles):
            _accumulate(o_ref, cols, x_ref, ki,
                        w_ref[pl.ds(ki * TILE, TILE), cols])


def tiled_matmul(x, w, *, interpret: bool = True):
    """The canonical serve matmul of a dense (K, N) weight as a Pallas
    kernel: ``x (M, K) @ w -> (M, N) f32`` with the fused kernel's exact
    arithmetic — 128x128 weight tiles, each 128-column strip summing its K
    tiles in k order through :func:`_accumulate` — so it is bit-identical
    to :func:`decompress_matmul` on the same weight and to
    ``kernels.ref.tiled_matmul_ref``.

    Why a kernel and not the XLA contraction of ``tiled_matmul_ref``: on a
    TPU, XLA compiles that contraction differently depending on the program
    around it (the same weights gave bit-identical dots in isolation but
    different hidden states inside the serve step once the fused kernel
    replaced the XLA dots or the decode-prefetch pipeline moved the weights
    into the loop carry).  A Mosaic kernel is compiled on its own, so every
    weight mode runs the same instructions wherever it is called.

    A grid step takes ``strips`` 128-column strips of the weight (all K
    rows) and up to :data:`MAX_ROWS` activation rows.  Ragged K/N are
    zero-padded like the fused tile layout.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    kp, np_ = -(-k // TILE) * TILE, -(-n // TILE) * TILE
    if kp != k:
        x = jnp.pad(x, ((0, 0), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    k_tiles, n_tiles = kp // TILE, np_ // TILE
    col_bytes = kp * TILE * jnp.dtype(w.dtype).itemsize
    strips = max(s for s in range(1, 5)
                 if n_tiles % s == 0
                 and (s == 1 or s * col_bytes <= _DENSE_BLOCK_BYTES))
    bm = m if m <= MAX_ROWS else MAX_ROWS
    mp = -(-m // bm) * bm
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    bn = strips * TILE
    out = pl.pallas_call(
        functools.partial(_dense_kernel, k_tiles=k_tiles, strips=strips),
        grid=(mp // bm, n_tiles // strips),
        in_specs=[pl.BlockSpec((bm, kp), lambda mi, nj: (mi, 0)),
                  pl.BlockSpec((kp, bn), lambda mi, nj: (0, nj))],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, nj: (mi, nj)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="tiled_matmul",
    )(x, w)
    return out[:m, :n]
