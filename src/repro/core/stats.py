"""Device-side tensor statistics for the batched compression pipeline.

The parameter search (params.py) only needs the exponent *histogram* — a
256-entry table for bf16/fp32, 32 for fp16 — yet the seed pipeline moved the
full tensor to the host to compute it with numpy.  This module computes the
histogram, the exact exponent min/max, and the per-layer const-tensor flags
in ONE jit'd reduction on device; only those few hundred bytes ever cross to
the host.  The existing O(256^2) search then runs on the histogram unchanged.

Correctness/speed split: scatter-add histograms are slow on backends without
fast scatters (XLA CPU serializes the updates), so above ``HIST_SAMPLE_CAP``
elements the histogram is taken over a strided sample.  That is safe by
construction: the histogram only drives parameter *quality*, while
losslessness depends on the exponent bounds — and those come from exact
vectorized min/max reductions over the full tensor, which the caller feeds
into ``params.widen_for_range`` after the search.

Everything here operates on the ``(L, per_layer_elems)`` unsigned-integer
view of a layer stack; a single tensor is the ``L == 1`` case.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dtypes import FORMATS, FloatFormat

# histogram sample cap: 2**16 samples of a <=256-way histogram leave the
# searched parameters statistically indistinguishable from the full pass
# (XLA CPU serializes scatter updates at ~75ns each, so the cap directly
# bounds the per-stack stats latency; exactness below the cap is what the
# search-parity tests rely on)
HIST_SAMPLE_CAP = 1 << 16


@dataclasses.dataclass(frozen=True)
class StackStats:
    """Host-side summary of one ``(L, ...)`` stack (a few hundred bytes)."""
    hist: np.ndarray       # (2**exp_bits,) int64 — whole-stack exponent
    #                        histogram (strided sample above HIST_SAMPLE_CAP)
    lo: int                # exact min exponent over the whole stack
    hi: int                # exact max exponent over the whole stack
    is_const: np.ndarray   # (L,) bool — layer is a single repeated bit pattern
    first: np.ndarray      # (L,) uint — first element's bit pattern per layer

    def bounds(self) -> Tuple[int, int]:
        """Exact (min, max) exponent present — from the full-tensor
        reduction, never the (possibly sampled) histogram."""
        return self.lo, self.hi


@functools.lru_cache(maxsize=None)
def _stats_fn(fmt_name: str):
    fmt = FORMATS[fmt_name]

    def f(bits):
        # (L, ...) in: the flat (L, N) view is taken here, inside the jit,
        # where it feeds reductions and is never materialized
        bits2d = bits.reshape(bits.shape[0], -1)
        exp = (bits2d >> fmt.mant_bits) & jnp.asarray(fmt.exp_mask,
                                                      bits2d.dtype)
        flat = exp.reshape(-1)
        # static at trace time; forced odd so the stride never divides
        # power-of-two weight dims (an even stride equal to the row length
        # would sample a few columns instead of the whole tensor)
        stride = max(1, flat.size // HIST_SAMPLE_CAP) | 1
        sample = flat[::stride].astype(jnp.int32)
        hist = jnp.zeros((1 << fmt.exp_bits,), jnp.int32).at[sample].add(1)
        is_const = jnp.all(bits2d == bits2d[:, :1], axis=1)
        return hist, flat.min(), flat.max(), is_const, bits2d[:, 0]

    return jax.jit(f)


def exponent_histogram_device(x, fmt: FloatFormat) -> jax.Array:
    """EXACT exponent histogram of a float array, computed on device (jit'd).

    Matches ``params.exponent_histogram`` bin-for-bin (no sampling — the
    pipeline's :func:`stack_stats_device` may sample, this function never
    does).  The result stays on device so callers can batch the transfer.
    """
    bits = jnp.ravel(jnp.asarray(x)).view(fmt.uint_dtype)

    @functools.partial(jax.jit, static_argnames=("bins", "mant", "mask"))
    def f(b, bins, mant, mask):
        exp = ((b >> mant) & jnp.asarray(mask, b.dtype)).astype(jnp.int32)
        return jnp.zeros((bins,), jnp.int32).at[exp].add(1)

    return f(bits, bins=1 << fmt.exp_bits, mant=fmt.mant_bits,
             mask=fmt.exp_mask)


def stack_stats_device(bits2d, fmt: FloatFormat):
    """(hist, lo, hi, is_const, first) as device arrays for an ``(L, ...)``
    bit view (per layer: its flattened elements).  One fused jit dispatch; pair with :func:`fetch_stats` to batch the
    host transfer across many stacks."""
    return _stats_fn(fmt.name)(bits2d)


def fetch_stats(device_stats: Sequence) -> list:
    """Move many ``stack_stats_device`` results to host in ONE transfer."""
    if not device_stats:
        return []
    host = jax.device_get(list(device_stats))
    return [StackStats(hist=np.asarray(h, np.int64), lo=int(lo), hi=int(hi),
                       is_const=np.asarray(c, bool), first=np.asarray(f))
            for h, lo, hi, c, f in host]


def stack_stats(bits2d, fmt: FloatFormat) -> StackStats:
    """Single-stack convenience wrapper (one dispatch + one tiny transfer)."""
    return fetch_stats([stack_stats_device(bits2d, fmt)])[0]
