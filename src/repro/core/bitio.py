"""Hierarchical halving bit-packing with byte normalization (paper §V-B, Alg. 2).

Packs fixed-width integer lanes into a byte stream using only vector
shift/OR and power-of-two slicing — no multiplies, divides, branches or
per-lane gathers.  The fold step merges the upper half of the lanes into
the lower half (``data[i] |= data[i + len/2] << width``), doubling the
effective width; once the width crosses the byte boundary the low byte of
every lane is emitted ("byte normalization") and the overflow recurses.

All functions operate on the LAST axis and broadcast over leading batch
dimensions, and all shapes/offsets are static functions of ``(N, width)``
— the whole codec is jit/pallas friendly.  The fixed-width pack/unpack pair
also accepts ``xp=numpy`` so the checkpoint/wire path can convert between
the exact and padded layouts entirely on the host (no device round-trip on
save/load).

Widths up to 32 are supported by peeling whole byte planes first and
running the halving fold on the sub-byte residue (the paper's Alg. 2 covers
``0 < a <= 8``; byte planes are its natural extension and are what the
paper itself does for the raw sign|mantissa stream).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["pack_fixed", "unpack_fixed", "packed_nbytes"]


def _mask(width: int, dtype, xp=jnp):
    return xp.asarray((1 << width) - 1, dtype)


# ---------------------------------------------------------------------------
# sub-byte halving fold
# ---------------------------------------------------------------------------

def _fold_plan(a: int, n: int):
    """Replay Alg. 2's fold loop: (width, length) at the emit point."""
    width, length = a, n
    while width < 8 and length > 1:
        width *= 2
        length //= 2
    return width, length


def _halving_pack(vals, a: int, xp=jnp, lane_dtype=None):
    """vals: (..., N) uint16 lanes each < 2**a, 1 <= a < 8, N power of two.

    Returns a list of uint8 byte-plane arrays (concatenated by the caller).
    ``lane_dtype`` (int32 inside the TPU kernels, whose VPU has no 16-bit
    shifts) replaces both the uint16 working lanes and the uint8 planes;
    the planes then hold one byte value per int32 lane.
    """
    assert 1 <= a < 8
    work, out = lane_dtype or jnp.uint16, lane_dtype or jnp.uint8
    n = vals.shape[-1]
    width, length = a, n
    while width < 8 and length > 1:
        half = length // 2
        vals = vals[..., :half] | (vals[..., half:] << width)
        width *= 2
        length = half
    if width < 8:  # degenerate tiny input: single partial byte
        return [vals.astype(out)]
    emitted = (vals & 0xFF).astype(out)
    residual_width = width - 8
    if residual_width == 0:
        return [emitted]
    residual = (vals >> 8).astype(work)
    return [emitted] + _halving_pack(residual, residual_width, xp, lane_dtype)


def _halving_unpack(stream, offset: int, a: int, n: int, xp=jnp,
                    work=jnp.uint16):
    """Inverse of :func:`_halving_pack`. Returns (vals (..., N) ``work``,
    offset)."""
    width, length = _fold_plan(a, n)
    if width < 8:
        vals = stream[..., offset : offset + 1].astype(work)
        offset += 1
    else:
        emitted = stream[..., offset : offset + length].astype(work)
        offset += length
        residual_width = width - 8
        if residual_width:
            residual, offset = _halving_unpack(stream, offset, residual_width,
                                               length, xp, work)
            vals = emitted | (residual << 8)
        else:
            vals = emitted
    while width > a:
        w2 = width // 2
        lo = vals & _mask(w2, vals.dtype, xp)
        hi = vals >> w2
        vals = xp.concatenate([lo, hi], axis=-1)
        width = w2
        length *= 2
    return vals, offset


def _halving_nbytes(a: int, n: int) -> int:
    width, length = _fold_plan(a, n)
    if width < 8:
        return 1
    total = length
    if width - 8:
        total += _halving_nbytes(width - 8, length)
    return total


# ---------------------------------------------------------------------------
# public fixed-width API (byte planes + sub-byte fold)
# ---------------------------------------------------------------------------

def packed_nbytes(n: int, width: int) -> int:
    """Exact byte length of ``pack_fixed`` output for N lanes of ``width`` bits."""
    if width == 0:
        return 0
    total = (width // 8) * n
    sub = width % 8
    if sub:
        total += _halving_nbytes(sub, n)
    return total


def pack_fixed(vals, width: int, xp=jnp, lane_dtype=None):
    """Pack (..., N) unsigned lanes of ``width`` significant bits into uint8.

    N must be a power of two (pad upstream).  Output shape:
    (..., packed_nbytes(N, width)).  ``xp=numpy`` runs the identical layout
    on the host (wire/checkpoint path).  ``lane_dtype=jnp.int32`` computes
    in int32 lanes and returns the bytes as int32 values (the TPU kernels
    narrow to uint8 only at the store).
    """
    vals = xp.asarray(vals)
    n = vals.shape[-1]
    assert n & (n - 1) == 0, f"lane count must be a power of two, got {n}"
    out = lane_dtype or jnp.uint8
    if width == 0:
        return xp.zeros(vals.shape[:-1] + (0,), out)
    planes = []
    w = width
    while w >= 8:
        planes.append((vals & _mask(8, vals.dtype, xp)).astype(out))
        vals = vals >> 8
        w -= 8
    if w:
        sub = (vals & _mask(w, vals.dtype, xp)).astype(lane_dtype
                                                         or jnp.uint16)
        planes.extend(_halving_pack(sub, w, xp, lane_dtype))
    return xp.concatenate(planes, axis=-1)


def unpack_fixed(stream, n: int, width: int, out_dtype=jnp.uint16, xp=jnp):
    """Inverse of :func:`pack_fixed`.

    stream: (..., packed_nbytes(n, width)) uint8 -> (..., n) ``out_dtype``.
    With ``out_dtype=jnp.int32`` the stream may already hold its bytes as
    int32 lanes and every intermediate stays int32 (the TPU kernels' form).
    """
    lanes32 = jnp.dtype(out_dtype) == jnp.int32
    stream = xp.asarray(stream)
    if not (lanes32 and stream.dtype == jnp.int32):
        stream = stream.astype(jnp.uint8)
    if width == 0:
        return xp.zeros(stream.shape[:-1] + (n,), out_dtype)
    vals = xp.zeros(stream.shape[:-1] + (n,), out_dtype)
    offset = 0
    shift = 0
    w = width
    while w >= 8:
        plane = stream[..., offset : offset + n].astype(out_dtype)
        vals = vals | (plane << shift)
        offset += n
        shift += 8
        w -= 8
    if w:
        sub, offset = _halving_unpack(stream, offset, w, n, xp,
                                      jnp.int32 if lanes32 else jnp.uint16)
        vals = vals | (sub.astype(out_dtype) << shift)
    return vals


# ---------------------------------------------------------------------------
# boolean mask <-> byte packing (for the per-group anomaly mask)
# ---------------------------------------------------------------------------

def pack_bool_mask(bits):
    """(..., G) bool -> (..., G//8) uint8, G multiple of 8, little-endian bits.

    Uses iota (not a captured constant) so it can trace inside Pallas kernels.
    """
    import jax

    g = bits.shape[-1]
    assert g % 8 == 0
    b = bits.astype(jnp.uint8).reshape(bits.shape[:-1] + (g // 8, 8))
    shifts = jax.lax.broadcasted_iota(jnp.uint8, b.shape, b.ndim - 1)
    return jax.lax.reduce(b << shifts, jnp.uint8(0), jnp.bitwise_or,
                          (b.ndim - 1,))


def unpack_bool_mask(bytes_, g: int):
    """Inverse of :func:`pack_bool_mask` -> (..., G) bool."""
    import jax

    expanded = bytes_[..., :, None]
    shifts = jax.lax.broadcasted_iota(
        jnp.uint8, expanded.shape[:-1] + (8,), expanded.ndim - 1)
    bits = (expanded >> shifts) & jnp.uint8(1)
    return bits.reshape(bytes_.shape[:-1] + (g,)).astype(jnp.bool_)


# ---------------------------------------------------------------------------
# host-side exact bit stream (wire format for the variable-length high stream)
# ---------------------------------------------------------------------------

def np_pack_bits_exact(vals: np.ndarray, width: int) -> bytes:
    """Host-only: straight little-endian bit concatenation, exact length."""
    if width == 0 or vals.size == 0:
        return b""
    vals = vals.astype(np.uint64)
    nbits = int(vals.size) * width
    out = np.zeros((nbits + 7) // 8, np.uint8)
    bitpos = np.arange(vals.size, dtype=np.uint64) * np.uint64(width)
    for k in range(width):
        bit = ((vals >> np.uint64(k)) & np.uint64(1)).astype(np.uint8)
        pos = bitpos + np.uint64(k)
        np.bitwise_or.at(out, (pos >> np.uint64(3)).astype(np.int64),
                         bit << (pos & np.uint64(7)).astype(np.uint8))
    return out.tobytes()


def np_unpack_bits_exact(buf: bytes, count: int, width: int) -> np.ndarray:
    """Host-only inverse of :func:`np_pack_bits_exact`.

    Raises ``ValueError`` when ``buf`` is shorter than the ``count * width``
    bits it claims to hold (a truncated wire record must fail loudly, not
    read out of bounds or silently return zeros).
    """
    if width == 0 or count == 0:
        return np.zeros(count, np.uint32)
    need = (count * width + 7) // 8
    if len(buf) < need:
        raise ValueError(
            f"bit stream truncated: need {need} bytes for {count} lanes of "
            f"{width} bits, got {len(buf)}")
    raw = np.frombuffer(buf, np.uint8)
    vals = np.zeros(count, np.uint64)
    bitpos = np.arange(count, dtype=np.uint64) * np.uint64(width)
    for k in range(width):
        pos = bitpos + np.uint64(k)
        bit = (raw[(pos >> np.uint64(3)).astype(np.int64)] >>
               (pos & np.uint64(7)).astype(np.uint8)) & np.uint8(1)
        vals |= bit.astype(np.uint64) << np.uint64(k)
    return vals.astype(np.uint32)
