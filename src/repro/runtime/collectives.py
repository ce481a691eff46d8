"""Compressed-bytes collectives: mesh placement and gathering for ENEC
stream bundles (ROADMAP item 3; paper thesis extended from PCIe to the
interconnect).

The sharded serving model is FSDP-of-compressed-bytes:

  * At rest each device owns ONLY its TP shard's wire records — the stream
    arrays' shard dim (``CompressedTensor.shards``) is placed on the mesh
    ``"model"`` axis (:func:`place_serving_tree`, or straight from the
    checkpoint via :func:`stream_placer` + ``from_wire(stream_place=)``).
  * When a layer is consumed, the missing shards are gathered as
    FIXED-LENGTH WIRE PAYLOADS over the mesh axis (:func:`gather_ct`) —
    the interconnect only ever carries compressed bytes — and then ONE
    batched decode runs locally on every device
    (``StreamedWeight.materialize`` / the overlap prefetch drivers call
    :func:`maybe_gather_ct` first, so overlap composes with sharding).
  * Dense math then runs replicated, so sharded serve logits are
    bit-identical to single-device serve in every mode: only the *storage*
    and the *bytes on the wire* are distributed, never the rounding.

:func:`shard_local_decode` is the zero-traffic variant — each device
decodes only its own block shard under ``shard_map`` (per-block decode is
independent, so the result is bit-identical to a full decode); the parity
tests drive it across every format.

Every gather is attributed to the codec's ``d2d_allgather`` ledger link
(:meth:`Codec.count_link`).  Gathers that happen inside a jit trace are
counted once per trace, not once per executed step — the schedule is
static, so per-step traffic is ``counted_bytes`` x steps (see
docs/DISTRIBUTED.md).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import codec as block_codec
from repro.core.api import CompressedTensor
from repro.core.codec_api import current_codec
from repro.runtime import sharding

MODEL_AXIS = "model"


# ---------------------------------------------------------------------------
# the ambient serving mesh
# ---------------------------------------------------------------------------

_mesh_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "repro_serving_mesh", default=None)


def serving_mesh():
    """The ambient ``(mesh, axis)`` installed by :func:`use_serving_mesh`,
    or ``None`` — read by :func:`maybe_gather_ct` at trace time so handle
    materialization gathers without threading a mesh through every
    signature."""
    return _mesh_ctx.get()


@contextlib.contextmanager
def use_serving_mesh(mesh: Mesh, axis: str = MODEL_AXIS):
    """Install ``mesh`` as the ambient serving mesh for the block: every
    ``StreamedWeight.materialize`` / ``FusedWeight.matmul`` / overlap
    prefetch inside gathers its compressed shards over ``axis`` first."""
    token = _mesh_ctx.set((mesh, axis))
    try:
        yield mesh
    finally:
        _mesh_ctx.reset(token)


# ---------------------------------------------------------------------------
# placement: each device holds only its shard's wire records
# ---------------------------------------------------------------------------

def _axis_count(mesh: Mesh, axis) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


def stream_placer(mesh: Mesh, axis: str = MODEL_AXIS):
    """The ``from_wire(stream_place=)`` hook for mesh restores: uploads
    each stream leaf with its TP-shard dim placed on ``axis``, so shard
    ``s``'s wire bytes land on the devices that own mesh coordinate ``s``
    only — the per-shard pack never fans out over h2d.  Leaves without a
    shard dim (or with an indivisible one) upload replicated."""
    def place(host_arr, shard_dim):
        names = [None] * host_arr.ndim
        if shard_dim is not None and _axis_count(mesh, axis) > 1 \
                and host_arr.shape[shard_dim] % mesh.shape[axis] == 0:
            names[shard_dim] = axis
        return jax.device_put(host_arr, NamedSharding(mesh, P(*names)))
    return place


def serving_pspecs(tree, mesh: Mesh, axis: str = MODEL_AXIS):
    """PartitionSpecs for a serving tree: handles/CompressedTensors get
    metadata-derived stream specs (:func:`sharding.handle_pspecs`), every
    plain leaf replicates — the bit-parity compute model shards only the
    compressed storage, never the dense math."""
    from repro.runtime.weights import is_handle

    def _special(x):
        return is_handle(x) or isinstance(x, CompressedTensor)

    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_special)
    specs = []
    for leaf in leaves:
        if is_handle(leaf):
            specs.append(sharding.handle_pspecs(leaf, mesh, axis))
        elif isinstance(leaf, CompressedTensor):
            specs.append(sharding.ct_pspecs(leaf, mesh, axis))
        else:
            specs.append(P(*((None,) * jnp.ndim(leaf))))
    return jax.tree_util.tree_unflatten(treedef, specs)


def place_serving_tree(tree, mesh: Mesh, axis: str = MODEL_AXIS):
    """``device_put`` a serving tree onto ``mesh`` per
    :func:`serving_pspecs`: stream shards distributed over ``axis``,
    everything else replicated."""
    return jax.device_put(tree, sharding.to_named(
        serving_pspecs(tree, mesh, axis), mesh))


# ---------------------------------------------------------------------------
# compressed-bytes all-gather
# ---------------------------------------------------------------------------

def _gather_shards(a, mesh: Mesh, axis: str, shard_dim: int):
    """All-gather the stream array ``a``, sharded over ``axis`` on its
    shard dim, into a replicated array with that dim merged into the block
    dim after it: ``(..., S, Bs, ...) -> (..., S*Bs, ...)``.

    The gather concatenates the devices' block ranges itself
    (``all_gather(tiled=True)`` inside ``shard_map``).  Gathering the
    ``(S, Bs, w)`` layout and merging its dims afterwards is the same bytes,
    but on a TPU that uint8 reshape is a relayout that compiles for tens of
    seconds per stream when ``Bs`` is not a multiple of 32 (35 s for one
    stream of StableLM-3B's embedding, ahead-of-time compile for a v5e; the
    tiled gather compiles in 0.5 s)."""
    spec = P(*((None,) * shard_dim + (axis,)))

    def local(x):
        shape = x.shape
        x = x.reshape(shape[:shard_dim]
                      + (shape[shard_dim] * shape[shard_dim + 1],)
                      + shape[shard_dim + 2:])
        return jax.lax.all_gather(x, axis, axis=shard_dim, tiled=True)

    return shard_map(local, mesh=mesh, in_specs=spec, out_specs=P(),
                     check_vma=False)(a)


def stream_nbytes(ct: CompressedTensor) -> int:
    """Device-layout byte total of the stream arrays (>= the exact
    ``nbytes_wire``: the high stream is padded to its static bound)."""
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(ct.streams))


def gather_ct(ct: CompressedTensor, mesh: Mesh, axis: str = MODEL_AXIS,
              codec=None) -> CompressedTensor:
    """The compression-aware all-gather: replicate ``ct``'s stream arrays
    over the mesh ``axis`` so every device holds all shards' fixed-length
    wire payloads, ready for one batched shard-local decode.  ONLY
    compressed bytes move — ``(A-1) x stream_nbytes(ct)`` total interconnect
    traffic for an ``A``-way axis, attributed to the ``d2d_allgather``
    ledger link (never the dense equivalent ``(A-1) x nbytes_raw``).

    The gathered tensor comes back in the one-shard layout (``shards=1``:
    the shard dim merged into the block dim by the gather itself, see
    :func:`_gather_shards`), which decodes bit-identically.

    No-op (and nothing counted) for raw/const/unsharded tensors or when
    ``ct.shards`` doesn't divide the axis.  Works eagerly and inside jit
    (counted at trace time).

    A tensor consumed at several call sites (e.g. a tied embed/head handle)
    is gathered ONCE: the eager gathered result is cached on the source
    tensor, so repeat consumption neither re-transfers nor re-counts.
    Tracer streams are never cached (a trace-local value must not outlive
    its trace); inside jit XLA CSEs duplicate gathers itself.
    """
    A = _axis_count(mesh, axis)
    if ct.mode != "enec" or ct.shards <= 1 or A <= 1 or ct.shards % A:
        return ct
    hit = getattr(ct, "_gather_cache", None)
    if hit is not None and hit[0] is mesh and hit[1] == axis:
        return hit[2]
    n_leaves = len(jax.tree.leaves(ct.streams))
    (codec or current_codec()).count_link(
        "d2d_allgather", stream_nbytes(ct) * (A - 1), ops=n_leaves)
    # the shard dim sits right before the block dim: (..., S, Bs[, w])
    lead = ct.streams.high_len.ndim
    streams = block_codec.BlockStreams(*(
        _gather_shards(a, mesh, axis, lead - 2) for a in ct.streams))
    out = dataclasses.replace(ct, streams=streams, shards=1)
    cached = getattr(ct, "_wire_bytes", None)
    if cached is not None:   # keep the lazily-filled wire-size cache
        out._wire_bytes = cached
    if not any(isinstance(a, jax.core.Tracer)
               for a in jax.tree.leaves(ct.streams)):
        ct._gather_cache = (mesh, axis, out)
    return out


def maybe_gather_ct(ct: CompressedTensor, codec=None) -> CompressedTensor:
    """:func:`gather_ct` under the ambient serving mesh; identity when no
    mesh is installed.  The hook every consumption point calls
    (``StreamedWeight.materialize``, ``FusedWeight.matmul``, the overlap
    prefetch) so single-device behavior is untouched."""
    ctx = serving_mesh()
    if ctx is None or not isinstance(ct, CompressedTensor):
        return ct
    mesh, axis = ctx
    return gather_ct(ct, mesh, axis, codec)


def replicated_local(fn, *args):
    """``fn(*args)`` as per-device code under the ambient serving mesh:
    the inputs are taken replicated (gathered compressed streams, the
    replicated activations) and ``fn`` runs whole on every device.  XLA
    cannot partition a Pallas kernel, so every decode and fused matmul of
    a gathered handle goes through here.  A plain call without a mesh."""
    ctx = serving_mesh()
    if ctx is None:
        return fn(*args)
    return jax.shard_map(fn, mesh=ctx[0], in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


# ---------------------------------------------------------------------------
# shard-local decode (zero interconnect traffic)
# ---------------------------------------------------------------------------

def shard_local_decode(ct: CompressedTensor, mesh: Mesh,
                       axis: str = MODEL_AXIS):
    """Decode a mesh-sharded tensor with each device decoding ONLY its own
    block shard under ``shard_map`` — no stream gather, no dense traffic;
    the dense result comes out sharded over its leading (block) dim.

    Per-block decode is independent (the paper's fixed-length block
    design), so the result is bit-identical to
    ``codec.decompress_array(ct)`` on a single device — asserted per
    format by tests/test_mesh_exec.py.  Per-layer (unstacked) enec tensors
    only; raw/const tensors have nothing to shard-decode.
    """
    if ct.mode != "enec":
        raise ValueError(f"shard_local_decode needs an enec tensor, "
                         f"got mode {ct.mode!r}")
    if ct.shards <= 1:
        raise ValueError("tensor is unsharded — use codec.decompress_array")
    base = 3 if ct.shards > 1 else 2
    if ct.streams.mask.ndim != base:
        raise ValueError("shard_local_decode takes per-layer tensors; "
                         "slice the layer stack first (slice_stacked)")
    A = _axis_count(mesh, axis)
    if A <= 1 or ct.shards % A:
        raise ValueError(
            f"shards={ct.shards} not divisible over mesh axis "
            f"{axis!r} of size {A}")
    fmt, p, block_elems = ct.fmt, ct.params, ct.block_elems
    in_specs = jax.tree.map(
        lambda a: P(axis, *((None,) * (a.ndim - 1))), ct.streams)

    def body(streams):
        # local shapes: (S/A, B/S, ...) — flatten to this device's flat
        # blocks and run the pure reference block decode (jit-compatible;
        # shard_map compiles it once per program)
        flat = block_codec.flatten_blocks(streams)
        return block_codec.decode_blocks(flat, block_elems, fmt, p)

    bits = shard_map(body, mesh=mesh, in_specs=(in_specs,),
                     out_specs=P(axis, None))(ct.streams)
    return block_codec.from_blocks(bits, ct.shape, fmt)
