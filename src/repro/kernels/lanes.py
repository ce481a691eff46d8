"""Row-batched lane primitives shared by the ENEC codec kernels.

The codec kernels hold ``ROWS`` blocks per grid step, one block per sublane
row, with the block's flat element order on the lane axis: an ``(8, N)``
int32 array fills every vreg and needs no lane-splitting reshape (which
Mosaic refuses for group-shaped views such as ``(N/L, L)``).  Everything
group-structured is therefore done per element, with each group's value
replicated over its ``L`` lanes, using only static lane shifts, selects,
lane broadcasts and lane sums — all of which lower on the TPU and run
unchanged under ``interpret=True``.

  * :func:`group_or`          per-group OR, butterfly over ``log2 L`` shifts
  * :func:`group_count`       inclusive count of flagged groups (Hillis-Steele
                              over group-strided shifts, the log-step stage
                              of the paper's IDD-Scan)
  * :func:`compact` /
    :func:`expand`            the rank-order scatter / gather of anomalous
                              groups as a shift network: every group moves by
                              its displacement one bit per stage; the
                              displacements are monotone, so no stage
                              collides
  * :func:`mask_bytes` /
    :func:`byte_per_lane`     anomaly flags <-> the packed mask stream
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 8  # blocks per grid step: one per sublane of an int32 vreg

# a routed word: bit 0 valid, bits 1..15 payload, bits 16.. displacement
_PAYLOAD_MASK = 0x7FFF
_DISP_SHIFT = 16


def step_rows(nrows: int) -> int:
    """Rows (blocks) per grid step: ``ROWS``, or all of them when there are
    fewer (a block dim equal to the array's is always legal)."""
    return min(ROWS, nrows)


def lane_iota(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def to_int32(bits):
    """uint16/uint32 bit patterns -> int32 lanes (32-bit: reinterpreted)."""
    if jnp.dtype(bits.dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(bits, jnp.int32)
    return bits.astype(jnp.int32)


def to_uint(bits, dtype):
    """Inverse of :func:`to_int32`."""
    if jnp.dtype(dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(bits, dtype)
    return bits.astype(dtype)


def shift_lanes(x, s: int):
    """out[..., e] = x[..., e - s], zero filled (``s < 0`` shifts left)."""
    n = x.shape[-1]
    if s == 0:
        return x
    if abs(s) >= n:
        return jnp.zeros_like(x)
    z = jnp.zeros(x.shape[:-1] + (abs(s),), x.dtype)
    if s > 0:
        return jnp.concatenate([z, x[..., :n - s]], axis=-1)
    return jnp.concatenate([x[..., -s:], z], axis=-1)


def group_or(x, group: int):
    """Every lane gets the OR over its aligned ``group``-lane group."""
    lane = lane_iota(x.shape)
    k = 1
    while k < group:
        x = x | jnp.where((lane & k) == 0, shift_lanes(x, -k),
                          shift_lanes(x, k))
        k *= 2
    return x


def group_count(flag, group: int):
    """``flag`` is 0/1, constant over each ``group``-lane group.  Returns,
    per lane, how many flagged groups precede or hold it."""
    s, n = group, flag.shape[-1]
    while s < n:
        flag = flag + shift_lanes(flag, s)
        s *= 2
    return flag


def routed_word(valid, payload, disp):
    return jnp.where(valid != 0,
                     1 | (payload << 1) | (disp << _DISP_SHIFT), 0)


def routed_payload(word):
    return jnp.where((word & 1) != 0, (word >> 1) & _PAYLOAD_MASK, 0)


def _stages(n_groups: int) -> range:
    return range(max(n_groups - 1, 0).bit_length())


def _route_stage(word, k: int, s: int):
    bit = (word >> (_DISP_SHIFT + k)) & word & 1
    moving = jnp.where(bit != 0, word, 0)
    incoming = shift_lanes(moving, s)
    return jnp.where((incoming & 1) != 0, incoming, word - moving)


def compact(word, group: int, n_groups: int):
    """Move every valid group ``disp`` groups left (LSB-first stages): the
    anomalous groups land in rank order, the rest of the row is zero."""
    for k in _stages(n_groups):
        word = _route_stage(word, k, -(group << k))
    return word


def expand(word, group: int, n_groups: int):
    """Inverse of :func:`compact` (MSB-first stages, moving right)."""
    for k in reversed(_stages(n_groups)):
        word = _route_stage(word, k, group << k)
    return word


def byte_per_lane(mask, group: int, n: int):
    """(R, G/8) mask bytes -> (R, n): each lane gets the byte holding its
    group's bit.  One byte spans ``8 * group >= 128`` lanes, so this is one
    lane broadcast per byte."""
    span = 8 * group
    r = mask.shape[0]
    return jnp.concatenate(
        [jnp.broadcast_to(mask[:, j:j + 1], (r, span))
         for j in range(n // span)], axis=-1)


def mask_bytes(flag, group: int):
    """Per-lane 0/1 group flags (R, n) -> (R, G/8) little-endian bytes."""
    r, n = flag.shape
    span = 8 * group
    nb = n // span
    lane = lane_iota(flag.shape)
    w = jnp.where(lane % group == 0, flag << ((lane // group) & 7), 0)
    out_lane = lane_iota((r, nb))
    out = jnp.zeros((r, nb), jnp.int32)
    for j in range(nb):
        byte = jnp.sum(w[:, j * span:(j + 1) * span], axis=1, keepdims=True)
        out = out + jnp.where(out_lane == j, byte, 0)
    return out
