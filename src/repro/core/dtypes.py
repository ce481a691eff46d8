"""Float-format bit layouts used by the ENEC codec.

ENEC splits a float into its exponent field (compressed) and the
sign|mantissa residue (stored raw, paper §IV-B).  Everything here is pure
bit arithmetic on the unsigned integer view of the float buffer so the
round trip is exact for every encoding, including NaN payloads, infinities,
zeros and subnormals.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class FloatFormat:
    name: str
    total_bits: int
    exp_bits: int
    mant_bits: int

    @property
    def raw_bits(self) -> int:
        """Width of the stored-raw residue: sign bit + mantissa bits."""
        return 1 + self.mant_bits

    @property
    def uint_dtype(self):
        return jnp.uint16 if self.total_bits == 16 else jnp.uint32

    @property
    def np_uint_dtype(self):
        return np.uint16 if self.total_bits == 16 else np.uint32

    @property
    def float_dtype(self):
        return {"bf16": jnp.bfloat16, "fp16": jnp.float16, "fp32": jnp.float32}[self.name]

    @property
    def exp_mask(self) -> int:
        return (1 << self.exp_bits) - 1

    @property
    def mant_mask(self) -> int:
        return (1 << self.mant_bits) - 1


BF16 = FloatFormat("bf16", 16, 8, 7)
FP16 = FloatFormat("fp16", 16, 5, 10)
FP32 = FloatFormat("fp32", 32, 8, 23)

FORMATS = {"bf16": BF16, "fp16": FP16, "fp32": FP32}


def format_for(dtype) -> FloatFormat:
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bfloat16:
        return BF16
    if dtype == jnp.float16:
        return FP16
    if dtype == jnp.float32:
        return FP32
    raise ValueError(f"ENEC supports bf16/fp16/fp32, got {dtype}")


def to_bits(x):
    """Bit-cast a float array to its unsigned integer view."""
    fmt = format_for(x.dtype)
    return jnp.asarray(x).view(fmt.uint_dtype)


def from_bits(bits, fmt: FloatFormat):
    return jnp.asarray(bits, fmt.uint_dtype).view(fmt.float_dtype)


def split_fields(bits, fmt: FloatFormat, dtype=None):
    """bits -> (exponent, raw) where raw = sign<<mant_bits | mantissa.

    ``dtype`` overrides the lane type (the TPU kernels pass int32: the sign
    bit is masked after the shift, so a 32-bit pattern held in int32 splits
    exactly too)."""
    bits = jnp.asarray(bits, dtype or fmt.uint_dtype)
    exp = (bits >> fmt.mant_bits) & fmt.exp_mask
    sign = (bits >> (fmt.total_bits - 1)) & 1
    raw = (bits & fmt.mant_mask) | (sign << fmt.mant_bits)
    return exp, raw


def combine_fields(exp, raw, fmt: FloatFormat, dtype=None):
    """Inverse of :func:`split_fields`."""
    exp = jnp.asarray(exp, dtype or fmt.uint_dtype)
    raw = jnp.asarray(raw, dtype or fmt.uint_dtype)
    sign = raw >> fmt.mant_bits
    mant = raw & fmt.mant_mask
    return (sign << (fmt.total_bits - 1)) | (exp << fmt.mant_bits) | mant
