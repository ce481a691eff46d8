"""Weight-execution handles: HOW a serve-time weight is stored and executed.

The serving stack used to thread a ``decompressor=`` pytree-materialization
hook through the model; this module replaces it with a first-class
abstraction.  Every big weight leaf is assigned one of three execution
modes by the policy layer (``runtime.streaming.assign_weight_modes``):

  dense    :class:`DenseWeight`     raw array resident in HBM
  stream   :class:`StreamedWeight`  ENEC streams in HBM; decompressed to a
                                    dense weight inside the serve step
  fused    :class:`FusedWeight`     ENEC tile streams in HBM; decompressed
                                    INSIDE the matmul kernel's VMEM tiles —
                                    the dense weight never exists in HBM

Handles share one interface: ``matmul(x)`` contracts (M, K) activations
against the (K, N) weight, ``materialize()`` returns the dense weight.
Every mode's ``matmul`` realizes the *same* canonical contraction — the
128x128 tile grid with k-major f32 accumulation of
``kernels.ref.tiled_matmul_ref``, which is the exact schedule the fused
Pallas kernel executes — so serve logits are bit-identical across modes:
the mode changes where weight bytes live and when they decompress, never
the numerics.  On a TPU the dense and streamed modes run that contraction
as the Pallas kernel ``kernels.decompress_matmul.tiled_matmul`` (see
:func:`dense_matmul`).

Handles are registered pytrees whose array fields carry a leading ``(L,)``
layer-stack dim; ``lax.scan`` (or ``tree.map(a[i])`` on the unrolled path)
slices them per layer, then :func:`resolve` materializes storage-only
handles while matmul-capable ones pass through to the layers
(``models.layers.weight_matmul``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.api import MATMUL_TILE, CompressedTensor
from repro.core.codec_api import current_codec


def dense_matmul(x, w):
    """The canonical serve matmul of a materialized (K, N) weight
    (``kernels.ops.tiled_matmul``: the Pallas kernel on a TPU, whose
    arithmetic does not depend on the program around it; the jnp schedule
    elsewhere).  Under a serving mesh it runs replicated on every device,
    like the decode and fused kernels."""
    from repro.kernels import ops  # lazy: keep module import light
    from repro.runtime.collectives import replicated_local
    return replicated_local(ops.tiled_matmul, x, w)


class WeightHandle:
    """Base marker for weight-execution handles.

    Subclasses implement ``matmul(x2d) -> (M, N) f32`` (the canonical tiled
    contraction) and ``materialize() -> (K, N)`` (the dense weight, bit-exact
    for compressed modes — ENEC is lossless).
    """

    def matmul(self, x):
        raise NotImplementedError

    def materialize(self, codec=None):
        raise NotImplementedError


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseWeight(WeightHandle):
    """Raw weight executed through the canonical serve matmul (baseline
    mode, and the fallback when a leaf turns out incompressible)."""
    w: jax.Array  # (..., K, N); leading (L,) when stacked

    def materialize(self, codec=None):
        return self.w

    def matmul(self, x):
        return dense_matmul(x, self.w)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StreamedWeight(WeightHandle):
    """A stacked weight (L, ...) stored as per-layer ENEC streams.

    Compressed in a *moveaxis(tp_axis -> 0)* layout so decompression stays
    shard-local under TP (see runtime/streaming.py).  ``execution`` is the
    resolve-time behaviour: "materialize" leaves (non-matmul consumers like
    MoE experts / SSM params) are decompressed to dense arrays before the
    layer runs; "matmul" leaves pass through to the layers and execute the
    canonical tiled contraction on the just-decompressed weight.

    ``flat`` marks a handle built from a NON-stacked 2-D leaf (embed /
    lm_head-style) stored as an L=1 stack: ``layer_shape`` is the full leaf
    shape, the stream layout keeps the leading (1,) stack dim (so wire
    records and ``stream_stats`` see one invariant layout), and
    materialization squeezes it back out.  Flat handles are never sliced by
    the layer loop.
    """
    ct: CompressedTensor                       # arrays have leading (L,) dim
    tp_axis: int = dataclasses.field(metadata=dict(static=True))
    layer_shape: tuple = dataclasses.field(metadata=dict(static=True))
    dtype_str: str = dataclasses.field(metadata=dict(static=True))
    execution: str = dataclasses.field(default="materialize",
                                       metadata=dict(static=True))
    flat: bool = dataclasses.field(default=False,
                                   metadata=dict(static=True))

    def materialize(self, codec=None):
        # moveaxis'd layout; the ambient codec decodes unless one is passed.
        # Under an ambient serving mesh the stream shards are first gathered
        # as compressed bytes (collectives.maybe_gather_ct), then ONE local
        # decode runs on every device — the interconnect never carries the
        # dense weight.
        from repro.runtime.collectives import maybe_gather_ct, \
            replicated_local
        ct = maybe_gather_ct(self.ct, codec)
        w_perm = replicated_local((codec or current_codec()).decompress_array,
                                  ct)
        w = jnp.moveaxis(w_perm, 0, self.tp_axis)
        return w.astype(jnp.dtype(self.dtype_str))

    def matmul(self, x):
        return dense_matmul(x, self.materialize())


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FusedWeight(WeightHandle):
    """A (L, K, N) matmul weight stored as ENEC *tile* streams and executed
    by the fused decompress+matmul Pallas kernel — the dense weight never
    materializes in HBM.  ``k``/``n`` are the unpadded logical dims (ragged
    edges ride the zero-padded tile layout of ``core.api.matmul_tiles``)."""
    ct: CompressedTensor                       # tile streams, leading (L,)
    k: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    dtype_str: str = dataclasses.field(metadata=dict(static=True))

    def matmul(self, x):
        from repro.kernels import ops  # lazy: keep module import light
        from repro.runtime.collectives import maybe_gather_ct, \
            replicated_local
        return replicated_local(
            lambda x, ct: ops.decompress_matmul(x, ct, self.k, self.n),
            x, maybe_gather_ct(self.ct))

    def materialize(self, codec=None):
        from repro.runtime.collectives import maybe_gather_ct
        w = (codec or current_codec()).untile_matmul_weight(
            maybe_gather_ct(self.ct, codec), self.k, self.n)
        return w.astype(jnp.dtype(self.dtype_str))


def is_handle(x) -> bool:
    return isinstance(x, WeightHandle)


def handle_kind(leaf) -> str:
    """Weight-execution kind of a tree leaf: "dense"/"stream"/"fused" for
    handles, "expert" for an expert-store reference
    (``runtime.experts.ExpertRef``), "raw" for plain arrays — the shared
    vocabulary the restore report and the serve health line use to
    describe a (possibly mixed) degraded tree.  All kinds produce
    bit-identical logits (module docstring; experts: models/moe.py), so a
    mixed kind census is a capacity/latency statement, never a
    correctness one."""
    if isinstance(leaf, DenseWeight):
        return "dense"
    if isinstance(leaf, StreamedWeight):
        return "stream"
    if isinstance(leaf, FusedWeight):
        return "fused"
    if isinstance(leaf, WeightHandle):
        # lazy: experts.py imports this module at load time
        from repro.runtime.experts import ExpertRef
        if isinstance(leaf, ExpertRef):
            return "expert"
    return "raw"


# ---------------------------------------------------------------------------
# checkpoint (de)serialization: spec <-> handle
# ---------------------------------------------------------------------------

def handle_spec(handle: WeightHandle) -> dict:
    """JSON-able static metadata of a compressed handle — everything the
    checkpoint manifest needs to rebuild it around a deserialized stream
    bundle (docs/CHECKPOINT.md)."""
    if isinstance(handle, StreamedWeight):
        spec = {"kind": "stream", "tp_axis": handle.tp_axis,
                "layer_shape": list(handle.layer_shape),
                "dtype": handle.dtype_str, "execution": handle.execution}
        if handle.flat:
            spec["flat"] = True
        return spec
    if isinstance(handle, FusedWeight):
        return {"kind": "fused", "k": handle.k, "n": handle.n,
                "dtype": handle.dtype_str}
    raise TypeError(f"no spec for handle type {type(handle).__name__}")


def handle_from_spec(spec: dict, ct: CompressedTensor) -> WeightHandle:
    """Inverse of :func:`handle_spec`: rebuild the handle around a stream
    bundle deserialized straight from the wire — the dense weight is never
    touched."""
    kind = spec["kind"]
    if kind == "stream":
        return StreamedWeight(ct=ct, tp_axis=int(spec["tp_axis"]),
                              layer_shape=tuple(spec["layer_shape"]),
                              dtype_str=spec["dtype"],
                              execution=spec.get("execution", "materialize"),
                              flat=bool(spec.get("flat", False)))
    if kind == "fused":
        return FusedWeight(ct=ct, k=int(spec["k"]), n=int(spec["n"]),
                           dtype_str=spec["dtype"])
    raise ValueError(f"unknown handle spec kind {kind!r}")


def finish_materialize(handle, w_stacked):
    """Stacked decode result -> the handle's original dense ``(L, ...)``
    leaf (un-permute / un-tile the storage layout)."""
    if isinstance(handle, StreamedWeight):
        w = jnp.moveaxis(w_stacked, 1, 1 + handle.tp_axis)
        if handle.flat:        # L=1 stack of a 2-D leaf: drop the stack dim
            w = w[0]
        return w.astype(jnp.dtype(handle.dtype_str))
    if isinstance(handle, FusedWeight):
        t = MATMUL_TILE
        k, n = handle.k, handle.n
        kp, np_ = -(-k // t) * t, -(-n // t) * t
        tiles = w_stacked.reshape(w_stacked.shape[0], np_ // t, kp // t, t, t)
        w = tiles.transpose(0, 2, 3, 1, 4).reshape(w_stacked.shape[0], kp, np_)
        return w[:, :k, :n].astype(jnp.dtype(handle.dtype_str))
    raise TypeError(f"not a compressed handle: {type(handle).__name__}")


def materialize_full(handle, codec=None):
    """Materialize a STACKED handle to its original dense ``(L, ...)`` leaf
    in one decode dispatch (``materialize()`` operates on per-layer slices;
    this is the whole-stack inverse the checkpoint loader needs to restore a
    training tree from serving-layout records)."""
    if isinstance(handle, DenseWeight):
        return handle.w
    from repro.runtime.collectives import maybe_gather_ct
    codec = codec or current_codec()
    return finish_materialize(
        handle, codec.decompress_stacked(maybe_gather_ct(handle.ct, codec)))


def materialize_full_many(handles, codec=None):
    """:func:`materialize_full` over many handles with O(#decoder buckets)
    decode dispatches — handles sharing a bucket decode in one concatenated
    dispatch via ``Codec.decompress_stacked_many`` (batched checkpoint
    restore, whole-tree materialization)."""
    from repro.runtime.collectives import maybe_gather_ct
    codec = codec or current_codec()
    decs = codec.decompress_stacked_many(
        [None if isinstance(h, DenseWeight)
         else maybe_gather_ct(h.ct, codec) for h in handles])
    return [h.w if isinstance(h, DenseWeight) else finish_materialize(h, d)
            for h, d in zip(handles, decs)]


def resolve(tree, codec=None, *, prefetched=None):
    """Per-layer handle resolution — the serve step's replacement for the
    retired ``decompressor=`` hook.  Storage-only handles (StreamedWeight in
    "materialize" execution) become dense arrays; matmul-capable handles
    pass through for the layers to execute; everything else is untouched.

    Without prefetch, every StreamedWeight decodes serially inside the
    layer it belongs to.  The measured overlap scheduler
    (``runtime.overlap``, benchmarks/bench_overlap.py) instead decodes
    layer l+1 one step ahead and hands the result back here:
    ``prefetched`` maps flatten slots (``tree`` flattened with
    ``is_leaf=is_handle``) to already-decoded dense weights — a
    "materialize" handle at that slot is replaced by the buffer directly,
    a "matmul" handle becomes a :class:`DenseWeight` around it (same
    canonical tiled contraction, so logits are bit-identical either way).
    ``codec`` pins the decoding codec; default is the ambient codec at
    trace time.
    """
    flat, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_handle)
    pre = prefetched or {}
    out = []
    for slot, leaf in enumerate(flat):
        if slot in pre:
            if not isinstance(leaf, StreamedWeight):
                raise TypeError(
                    f"prefetched slot {slot} is not a StreamedWeight: "
                    f"{type(leaf).__name__}")
            w = pre[slot]
            out.append(DenseWeight(w=w) if leaf.execution == "matmul" else w)
        elif isinstance(leaf, StreamedWeight) and leaf.execution != "matmul":
            out.append(leaf.materialize(codec))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)
