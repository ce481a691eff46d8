"""Deterministic double-buffered decode-prefetch pipeline for streamed
serving (paper §VI-C; ROADMAP item "overlapped serving decode").

The serial stream-mode layer loop pays ``decode(l) + matmul(l)`` per
layer: every :class:`~repro.runtime.weights.StreamedWeight` decodes inside
the layer that consumes it.  This module restructures the loop into a
software pipeline with an explicit schedule:

    prologue:  decode layer 0                      (1 batched dispatch set)
    step j:    issue decode of layer min(j+1, P-1) ─┐ independent dataflow,
               run layer j on decoded j            ─┘ the backend overlaps
    (the clamped last-step prefetch keeps every layer inside the scan body
    so logits stay bit-identical to the serial scan — see pipeline_scan)

The scan carry holds exactly ONE layer's decoded weights while the next
layer's decode is in flight — two layers' dense weights live at once
(double-buffering; the carry buffer is reused in place by ``lax.scan``),
never the whole stack.  Steady-state per-layer cost on an asynchronous
backend is ``max(decode, matmul)`` instead of ``decode + matmul``;
benchmarks/bench_overlap.py measures both terms and the achieved ratio
instead of asserting the overlap in a docstring.

Each step's prefetch is ONE batched decode over every streamed leaf of the
layer — O(#decoder buckets per layer) dispatches via
:meth:`Codec.plan_decode` (``exact=True``: the same leaf set decodes every
step, so the block count is padded by zero instead of bucket-rounded) —
never one dispatch per leaf.  Decoded bits are bit-identical to the serial
per-leaf path, and the consumption point is the same canonical tiled
contraction (``resolve`` with ``prefetched=``), so logits with overlap
on/off are bit-identical in every serving mode: only scheduling moves.

Drivers mirror the two layer-loop shapes of ``models/lm.py``:
:func:`pipeline_scan` (compact HLO; compressed streams are closed over in
full and indexed per step with ``dynamic_index_in_dim`` — a shifted-xs scan
would copy the whole compressed stack every step) and
:func:`pipeline_unrolled` (static slices, exact cost_analysis).  The scan
driver modulo-unrolls the pipeline by :data:`SCAN_UNROLL_WINDOW` layers:
inside an unrolled window the prefetch handoff is straight-line dataflow
(the backend fuses decode j+1 with layer j's compute and drops the final
window's dead prefetch), and the decoded-weight carry crosses only window
boundaries — without it, every layer pays a carry round-trip that costs
more than the decode it hides on a synchronous single-stream backend.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.codec_api import current_codec
from repro.runtime.weights import StreamedWeight, is_handle, resolve

OVERLAP_MODES = ("off", "on", "auto")

# Modulo-unroll window of the pipelined scan: layers per merged scan body.
# Within a window the prefetch handoff compiles as straight-line dataflow;
# the decoded double-buffer crosses the loop carry only once per window.
SCAN_UNROLL_WINDOW = 8


def default_unroll(n_periods: int) -> int:
    """Layers per pipelined scan body: :data:`SCAN_UNROLL_WINDOW` (capped
    at the stack), except on a TPU, where it is one.  XLA:TPU rounds layer
    math (norms, softmax) differently once several layers are inlined into
    one body: on a v5e an unrolled pipeline moved nearly every logit bit
    away from the serial scan, while one layer per body keeps the layer
    math in the same loop-body form as the serial scan."""
    if jax.default_backend() == "tpu":
        return 1
    return min(n_periods, SCAN_UNROLL_WINDOW)


def overlap_enabled(mode: str, period) -> bool:
    """Should the layer loop over ``period`` run pipelined?  "off" never;
    "on"/"auto" whenever there is a stream to prefetch (a tree with no
    StreamedWeight leaves has nothing to overlap — dense and fused handles
    decode inside the matmul kernel or not at all)."""
    if mode not in OVERLAP_MODES:
        raise ValueError(f"unknown overlap mode {mode!r}; "
                         f"expected one of {OVERLAP_MODES}")
    if mode == "off":
        return False
    return any(isinstance(leaf, StreamedWeight)
               for leaf in jax.tree.leaves(period, is_leaf=is_handle))


@dataclasses.dataclass
class OverlapSchedule:
    """The static prefetch schedule of one period stack: which flatten
    slots hold streamed weights (the prefetch set), the period structure to
    rebuild slices into, and the per-layer decode-dispatch count the
    pipeline will pay each step (``buckets_per_layer`` — asserted by
    tests/test_overlap.py against the codec's measured dispatch counters).
    """
    leaves: list                 # full-period flatten (is_leaf=is_handle)
    treedef: Any
    slots: Tuple[int, ...]       # indices of StreamedWeight leaves
    n_periods: int
    buckets_per_layer: int


def build_schedule(period, n_periods: int, codec=None) -> OverlapSchedule:
    """Flatten ``period`` (handles as leaves) and record the prefetch
    slots.  Slot indices are computed on the full stacked structure, which
    is identical to every per-layer slice's structure, so the same indices
    address ``resolve(..., prefetched=)`` later."""
    codec = codec or current_codec()
    leaves, treedef = jax.tree_util.tree_flatten(period, is_leaf=is_handle)
    slots = tuple(i for i, leaf in enumerate(leaves)
                  if isinstance(leaf, StreamedWeight))
    keys = {codec._decoder_key(leaves[s].ct.fmt_name, leaves[s].ct.params,
                               leaves[s].ct.block_elems) for s in slots}
    return OverlapSchedule(leaves=leaves, treedef=treedef, slots=slots,
                           n_periods=n_periods,
                           buckets_per_layer=len(keys))


def _take(a, index):
    """Layer ``index`` of a leading-(L,) array: a static slice for Python
    ints, ``dynamic_index_in_dim`` for the traced scan counter."""
    if isinstance(index, int):
        return a[index]
    return jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False)


def decode_layer(schedule: OverlapSchedule, index, codec=None) -> tuple:
    """ONE batched decode of every streamed leaf's layer ``index`` —
    the per-step prefetch dispatch.  Returns the finished dense weights
    (un-permuted, target dtype) in slot order, bit-identical to
    ``StreamedWeight.materialize`` on the same slice."""
    from repro.runtime.collectives import maybe_gather_ct, replicated_local
    codec = codec or current_codec()
    handles = [schedule.leaves[s] for s in schedule.slots]
    # slice layer `index`, then (under an ambient serving mesh) gather the
    # layer's compressed shards over the mesh axis — the prefetch step's
    # interconnect traffic is this layer's wire payloads, never its dense
    # weights, so overlap composes with sharding
    cts = [maybe_gather_ct(
               dataclasses.replace(
                   h.ct, streams=jax.tree.map(lambda a: _take(a, index),
                                              h.ct.streams)),
               codec)
           for h in handles]
    decs = replicated_local(
        lambda cts: codec.decompress_stacked_many(cts, exact=True), cts)
    return tuple(
        jnp.moveaxis(d, 0, h.tp_axis).astype(jnp.dtype(h.dtype_str))
        for h, d in zip(handles, decs))


def _resolved_slice(schedule: OverlapSchedule, rest_leaves, decoded,
                    codec=None):
    """Rebuild one period slice from the non-streamed sliced leaves and the
    prefetched decode results, resolved for the layer functions."""
    leaves = list(rest_leaves)
    for s in schedule.slots:
        leaves[s] = schedule.leaves[s]
    tree = jax.tree_util.tree_unflatten(schedule.treedef, leaves)
    return resolve(tree, codec,
                   prefetched=dict(zip(schedule.slots, decoded)))


def _rest_leaves(schedule: OverlapSchedule, index: int) -> list:
    """Static layer slice of every NON-streamed period leaf (plain stacked
    arrays and dense/fused handles alike); prefetch slots stay ``None``."""
    slots = set(schedule.slots)
    return [None if i in slots else jax.tree.map(lambda a: a[index], leaf)
            for i, leaf in enumerate(schedule.leaves)]


def pipeline_scan(schedule: OverlapSchedule, apply_fn: Callable, carry0, *,
                  xs_extra=None, codec=None, wrap: Optional[Callable] = None,
                  unroll: Optional[int] = None):
    """Pipelined ``lax.scan`` over the period stack.

    ``apply_fn(carry, resolved_slice, extra_slice, index) -> (carry, y)``
    runs one period; ``xs_extra`` is an optional per-layer pytree (leading
    ``(P,)`` — e.g. the decode cache entries) sliced alongside; ``wrap``
    (e.g. ``jax.checkpoint``) wraps the scan body.

    The scan runs ALL P layers with the carry holding the CURRENT layer's
    decoded weights and a counter; each body issues layer ``j+1``'s batched
    decode before applying layer ``j``.  The prefetch index is clamped to
    ``P-1`` — the final step re-issues layer P-1's decode (its result is
    discarded with the carry, and dropped as dead code when the window is
    fully unrolled) so that EVERY layer's compute compiles inside the scan
    body: XLA fuses (and therefore rounds) scan-body math differently from
    eagerly inlined math, so an eager epilogue layer would break bit-parity
    with the serial scan.

    The loop is modulo-unrolled by ``unroll`` layers (default
    :func:`default_unroll`): inside a window the decode→consume
    handoff is ordinary dataflow the backend schedules and fuses freely;
    the double-buffer rides the loop carry only across window boundaries.
    Returns ``(carry, ys)`` with ``ys`` stacked over all P layers like a
    plain scan's.
    """
    codec = codec or current_codec()
    P = schedule.n_periods
    if unroll is None:
        unroll = default_unroll(P)
    dec = decode_layer(schedule, 0, codec)
    slots = set(schedule.slots)
    xs_rest = [None if i in slots else leaf
               for i, leaf in enumerate(schedule.leaves)]

    def body(c, xs_j):
        carry, dec_cur, j = c
        rest_j, extra_j = xs_j
        # issue layer j+1's decode BEFORE layer j's compute: the two are
        # independent dataflow, free to overlap on an async backend
        dec_next = decode_layer(schedule, jnp.minimum(j + 1, P - 1), codec)
        carry, y = apply_fn(
            carry, _resolved_slice(schedule, rest_j, dec_cur, codec),
            extra_j, j)
        return (carry, dec_next, j + 1), y

    if wrap is not None:
        body = wrap(body)
    (carry, _, _), ys = jax.lax.scan(
        body, (carry0, dec, jnp.int32(0)), (xs_rest, xs_extra),
        unroll=unroll)
    return carry, ys


def pipeline_unrolled(schedule: OverlapSchedule, apply_fn: Callable, carry0,
                      *, xs_extra=None, codec=None,
                      wrap: Optional[Callable] = None):
    """Pipelined statically-unrolled layer loop (same contract as
    :func:`pipeline_scan`); returns ``(carry, [y_0, ..., y_{P-1}])`` — the
    caller stacks, mirroring the serial unrolled driver."""
    codec = codec or current_codec()
    body = apply_fn if wrap is None else wrap(apply_fn)
    carry, ys = carry0, []
    dec = decode_layer(schedule, 0, codec)
    for i in range(schedule.n_periods):
        dec_next = (decode_layer(schedule, i + 1, codec)
                    if i + 1 < schedule.n_periods else None)
        extra = (None if xs_extra is None
                 else jax.tree.map(lambda a: a[i], xs_extra))
        carry, y = body(
            carry, _resolved_slice(schedule, _rest_leaves(schedule, i),
                                   dec, codec), extra, i)
        ys.append(y)
        dec = dec_next
    return carry, ys
