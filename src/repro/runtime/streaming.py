"""Weight-execution policy: ENEC-compressed weights resident in HBM,
decompressed either layer-by-layer inside the serve step (paper §VI-C) or
tile-by-tile inside the matmul kernel itself (fused mode, DESIGN.md §8).

This module decides, per parameter leaf, HOW serve-time weights execute —
the handle classes themselves live in ``runtime.weights``:

  raw      small / non-stacked leaves: untouched arrays
  dense    big matmul weights wrapped in DenseWeight (canonical executor,
           raw bytes in HBM) — the baseline the other modes compare against
  stream   StreamedWeight: per-layer ENEC streams, decompressed inside the
           step; ``lax.scan`` slices the streams so XLA's latency-hiding
           scheduler overlaps layer l+1's stream DMA + decode with layer
           l's matmuls (the paper's pipeline one level down the hierarchy)
  fused    FusedWeight: tile-wise ENEC streams consumed by the fused
           decompress+matmul Pallas kernel — the dense weight never exists
           in HBM, so decode-phase effective HBM bandwidth rises by the
           compression ratio

TP locality (stream mode): a weight whose axis ``k`` is model-sharded is
compressed in a *moveaxis(k -> 0)* layout with the block dimension sharded
on "model".  Decompression is then shard-local (blocks stay on their
device), the un-permute is a metadata transpose, and no resharding
collectives appear on the latency path.  Fused tile streams are n-major
block-ordered; they shard whenever the tile-block count divides the
requested shard width (:func:`fused_shards` — a contiguous shard range of
the flat tile axis re-flattens to the exact kernel layout), falling back
to ``shards=1`` per leaf when pad blocks would corrupt the tile order.

Only leaves >= ``min_bytes`` are compressed (norms/biases stay raw —
negligible bytes, and the decode cost would not amortize).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import (MATMUL_TILE, SUPPORTED_FLOAT_DTYPES,
                            CompressedTensor, abstract_compressed,
                            matmul_tiles)
from repro.core.codec_api import current_codec
from repro.core.params import EnecParams
from repro.runtime.overlap import OVERLAP_MODES, \
    overlap_enabled  # noqa: F401  (policy surface re-export)
from repro.runtime.weights import (DenseWeight, FusedWeight,  # noqa: F401
                                   StreamedWeight, WeightHandle, handle_kind,
                                   is_handle, materialize_full_many, resolve)

MIN_STREAM_BYTES = 1 << 20  # 1 MiB
STREAM_SHARDS = 16          # production TP width (divisors also work)

WEIGHT_MODES = ("dense", "stream", "fused")

# Stacked 2-D weights consumed as x @ W by the attention/MLP layers — the
# decode path's dominant weight bytes, and the set the fused kernel (and the
# canonical tiled executor) can take over.  MoE expert stacks / SSM / xLSTM
# params keep the materialize path.
MATMUL_LEAF_NAMES = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"})


def _pstr(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name",
                    getattr(k, "idx", k)))) for k in path)


def stream_eligible(pstr: str, shape, dtype,
                    min_bytes: int = MIN_STREAM_BYTES) -> bool:
    """The ONE streamed-leaf predicate (shared by the concrete policy and
    the abstract dry-run path, which used to carry diverging copies): a
    leaf is compressible iff it is big enough to amortize the in-step
    decode and is either a stacked (L, ...) float stack or a plain 2-D
    float weight (``embed`` / ``lm_head``-style — the biggest single
    tensors in the tree, compressed as L=1 stacks with the same
    never-worse escape)."""
    nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    if nbytes < min_bytes or jnp.dtype(dtype) not in SUPPORTED_FLOAT_DTYPES:
        return False
    stacked = "period" in pstr or "stack" in pstr
    if len(shape) == 2:
        # a 2-D leaf inside the layer stack is an (L, d) stack of vectors
        # (norm gains): the layer loop slices it, so it cannot be an L=1
        # flat stream
        return not stacked
    return stacked and len(shape) >= 3


def _tp_axis_for(path: str, shape) -> int:
    """Which axis is model-sharded at serve time (single source of truth
    for both the concrete and abstract streamed trees; mirror of
    sharding.py's name rules)."""
    name = path.rsplit("/", 1)[-1]
    if name == "embed":
        return 0
    if name in ("wo", "w_down", "out_proj"):
        return len(shape) - 2
    if name in ("e_gate", "e_up", "e_down"):
        return len(shape) - 3
    return len(shape) - 1


def fused_shards(k: int, n: int, shards: int) -> int:
    """TP shard count a fused ``(k, n)`` tile stream can actually use:
    ``shards`` when the n-major flat tile-block count divides it evenly —
    each shard then holds a contiguous range of flat tiles and the kernel's
    ``t = n_tile * k_tiles + k_tile`` order survives the shard split — else
    1, because ``stacked_blocks`` would insert pad blocks that corrupt the
    flat tile order (the PR 2 restriction, now per-leaf instead of
    global)."""
    t = MATMUL_TILE
    blocks = (-(-k // t)) * (-(-n // t))
    return shards if shards > 1 and blocks % shards == 0 else 1


def _is_matmul_pos(pstr: str, ndim: int) -> bool:
    """Is this leaf executed through the handle-aware canonical matmul
    (``models.layers.weight_matmul``)?  Name alone is not enough: xLSTM's
    ``mlstm/wq`` shares the ``wq`` name but is consumed by a plain einsum,
    so only the attention/MLP subtrees qualify — everything else must
    materialize before its layer runs."""
    parts = pstr.split("/")
    return (parts[-1] in MATMUL_LEAF_NAMES and ndim == 3
            and len(parts) >= 2 and parts[-2] in ("attn", "mlp"))


# ---------------------------------------------------------------------------
# the policy: params tree -> handle tree
# ---------------------------------------------------------------------------

def serving_job(pstr: str, leaf, mode: str,
                min_bytes: int = MIN_STREAM_BYTES) -> Optional[dict]:
    """The per-leaf compression plan for a compressing mode ("stream" /
    "fused"): which layout to encode (``arr()`` builds it when its encode
    batch runs) and the handle metadata to attach.  ``None`` means the
    leaf is not eligible and stays raw/dense.

    Shared between :func:`assign_weight_modes` and the checkpoint writer's
    ``serving_layout`` path, so a checkpoint stores byte-for-byte the stream
    bundle the policy would build — that is what lets ``load_for_serving``
    deserialize records straight into handles.
    """
    if not stream_eligible(pstr, leaf.shape, leaf.dtype, min_bytes):
        return None
    if leaf.ndim == 2:
        # plain 2-D leaf (embed / lm_head-style): an L=1 stack in the same
        # moveaxis layout; the flat handle squeezes the stack dim back out
        tp_axis = _tp_axis_for(pstr, leaf.shape)
        return dict(kind="stream", leaf=leaf,
                    arr=lambda: jnp.moveaxis(leaf, tp_axis, 0)[None],
                    tp_axis=tp_axis, layer_shape=leaf.shape,
                    matmul_pos=False, flat=True)
    matmul_pos = _is_matmul_pos(pstr, leaf.ndim)
    if mode == "fused" and matmul_pos:
        return dict(kind="fused", leaf=leaf,
                    arr=lambda: matmul_tiles(leaf),
                    k=leaf.shape[1], n=leaf.shape[2], matmul_pos=True)
    tp_axis = _tp_axis_for(pstr, leaf.shape[1:])
    return dict(kind="stream", leaf=leaf,
                arr=lambda: jnp.moveaxis(leaf, 1 + tp_axis, 1),
                tp_axis=tp_axis, layer_shape=leaf.shape[1:],
                matmul_pos=matmul_pos)


def build_serving_handle(job: dict, ct):
    """Handle (or fallback leaf) from a :func:`serving_job` compression
    result.  ``ct=None`` (const / incompressible) falls back to DenseWeight
    at matmul positions — executor and logits never depend on
    compressibility — or to the raw array elsewhere."""
    leaf = job["leaf"]
    if job["kind"] == "fused":
        # tile accounting runs on the zero-padded layout; re-check the
        # escape against the true (unpadded) raw bytes
        if ct is not None and ct.nbytes_wire() >= leaf.size \
                * leaf.dtype.itemsize:
            ct = None
        return (DenseWeight(w=leaf) if ct is None else
                FusedWeight(ct=ct, k=job["k"], n=job["n"],
                            dtype_str=str(leaf.dtype)))
    if ct is None:  # incompressible / const escape
        return DenseWeight(w=leaf) if job["matmul_pos"] else leaf
    return StreamedWeight(
        ct=ct, tp_axis=job["tp_axis"],
        layer_shape=tuple(job["layer_shape"]),
        dtype_str=str(leaf.dtype),
        execution="matmul" if job["matmul_pos"] else "materialize",
        flat=job.get("flat", False))


def assign_weight_modes(params, *, mode: str = "fused",
                        shared_params: Optional[EnecParams] = None,
                        min_bytes: int = MIN_STREAM_BYTES,
                        shards: int = STREAM_SHARDS,
                        codec=None):
    """Assign every leaf a weight-execution mode from its path, shape,
    bytes, and TP constraints; compress everything in ONE batched pipeline
    pass (``compress_stacked_many`` — O(#buckets) encode dispatches).

    mode="dense":  matmul positions wrapped in DenseWeight (canonical
                   executor), everything else raw.
    mode="stream": every eligible leaf becomes StreamedWeight; matmul
                   positions execute the canonical contraction on the
                   just-decompressed weight, the rest materialize.
    mode="fused":  matmul positions become FusedWeight tile streams,
                   TP-sharded per leaf when the tile-block count allows it
                   (:func:`fused_shards`; leaves whose count doesn't divide
                   ``shards`` encode unsharded); other eligible leaves
                   stream as above.

    The never-worse escape is intact in every mode: a leaf whose streams
    would not beat raw bytes falls back to DenseWeight (matmul positions,
    so the executor — and therefore the logits — stay identical) or to the
    raw array.

    Leaves that are ALREADY handles pass through untouched, so the policy
    can finish a tree that ``CheckpointManager.load_for_serving`` partially
    restored straight from wire records.

    ``codec`` selects the :class:`repro.core.Codec` doing the encoding
    (default: the ambient codec) — two models can be assigned under
    different codecs in one process with independent caches/counters.
    """
    if mode not in WEIGHT_MODES:
        raise ValueError(f"unknown weight mode {mode!r}; "
                         f"expected one of {WEIGHT_MODES}")
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=is_handle)
    out = [None] * len(flat)
    jobs = []   # serving_job dicts + their slots
    for slot, (path, leaf) in enumerate(flat):
        if is_handle(leaf):
            out[slot] = leaf
            continue
        pstr = _pstr(path)
        if mode == "dense":
            eligible = stream_eligible(pstr, leaf.shape, leaf.dtype,
                                       min_bytes)
            out[slot] = (DenseWeight(w=leaf)
                         if eligible and _is_matmul_pos(pstr, leaf.ndim)
                         else leaf)
            continue
        job = serving_job(pstr, leaf, mode, min_bytes)
        if job is None:
            out[slot] = leaf
            continue
        job["slot"] = slot
        jobs.append(job)
    cts = compress_serving_jobs(jobs, shards, codec or current_codec(),
                                shared_params)
    for j, ct in zip(jobs, cts):
        out[j["slot"]] = build_serving_handle(j, ct)
    return jax.tree_util.tree_unflatten(treedef, out)


# raw bytes one batched encode pass may take in: a pass holds its inputs'
# block copies, their concatenation and the streams at once, so batching a
# whole full-width model in one pass needs ~3x its size in HBM
ENCODE_BATCH_BYTES = 1 << 30


def compress_serving_jobs(jobs, shards: int, codec, shared_params=None):
    """Compress :func:`serving_job` plans, one ``Optional[CompressedTensor]``
    per job.  One batched pass (O(#buckets) encode dispatches) per shard
    width — fused leaves whose tile count doesn't divide ``shards`` drop to
    1 — split further only where a pass would exceed
    :data:`ENCODE_BATCH_BYTES` of raw weights.  Each job's encode layout
    is built just before its pass and dropped after it."""
    cts = [None] * len(jobs)
    by_shards: dict = {}
    for idx, j in enumerate(jobs):
        width = (fused_shards(j["k"], j["n"], shards)
                 if j["kind"] == "fused" else shards)
        by_shards.setdefault(width, []).append(idx)
    for width, idxs in sorted(by_shards.items()):
        batches, nbytes = [[]], 0
        for i in idxs:
            size = jobs[i]["leaf"].size * jobs[i]["leaf"].dtype.itemsize
            if batches[-1] and nbytes + size > ENCODE_BATCH_BYTES:
                batches.append([])
                nbytes = 0
            batches[-1].append(i)
            nbytes += size
        for batch in batches:
            group = codec.compress_stacked_many(
                [jobs[i]["arr"]() for i in batch], p=shared_params,
                shards=width)
            for i, ct in zip(batch, group):
                cts[i] = ct
    return cts


# ---------------------------------------------------------------------------
# legacy stream-everything entry points (checkpointing, benches, dry-run)
# ---------------------------------------------------------------------------

def compress_params_for_streaming(params, *,
                                  shared_params: Optional[EnecParams] = None,
                                  min_bytes: int = MIN_STREAM_BYTES,
                                  shards: int = STREAM_SHARDS,
                                  codec=None, plan=None):
    """params tree -> same-structure tree with big stacked leaves replaced
    by materialize-mode StreamedWeight (the §VI-C deployment: every stream
    decompresses to a dense weight inside the step; serve output is
    bit-identical to serving the raw tree).

    Device-resident batched pipeline (docs/PIPELINE.md): every eligible
    ``(L, ...)`` stack is handed to ``Codec.compress_stacked_many``, which
    computes statistics on device (one tiny host transfer for the whole
    tree), runs the histogram search per stack (a layer stack is one
    logical tensor, so every layer shares static codec metadata), and
    encodes each stack in ONE jit dispatch — no per-layer compress loop, no
    full-tensor ``device_get``, no ``jnp.stack`` of stream pytrees.

    ``plan`` accepts the :func:`streaming_encode_plan` built for the SAME
    (params, min_bytes, shards) — planning is not free (stats dispatches +
    host search + block staging), so inspect-then-run callers hand the
    inspected plan back instead of paying for it twice.
    """
    out, treedef, eligible = _stream_jobs(params, min_bytes)
    codec = codec or current_codec()
    if plan is None:
        plan = codec.plan_encode([e[2] for e in eligible], stacked=True,
                                 p=shared_params, shards=shards)
    elif not plan.stacked or plan.n_inputs != len(eligible) \
            or plan.shards != shards:
        raise ValueError(
            f"plan does not match this tree/policy: stacked={plan.stacked} "
            f"n_inputs={plan.n_inputs} (expected {len(eligible)}) "
            f"shards={plan.shards} (expected {shards})")
    cts = codec.execute(plan)
    for (slot, leaf, _, tp_axis, flat2d), ct in zip(eligible, cts):
        if ct is None:
            out[slot] = leaf                            # incompressible/const
            continue
        out[slot] = StreamedWeight(
            ct=ct, tp_axis=tp_axis,
            layer_shape=tuple(leaf.shape if flat2d else leaf.shape[1:]),
            dtype_str=str(leaf.dtype), flat=flat2d)
    return jax.tree_util.tree_unflatten(treedef, out)


def _stream_jobs(params, min_bytes):
    """Shared eligibility walk of :func:`compress_params_for_streaming` and
    :func:`streaming_encode_plan` — the two must see the same leaves."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = [None] * len(flat)
    eligible = []   # (slot, leaf, perm, tp_axis, flat2d)
    for slot, (path, leaf) in enumerate(flat):
        pstr = _pstr(path)
        if not stream_eligible(pstr, leaf.shape, leaf.dtype, min_bytes):
            out[slot] = leaf
            continue
        if leaf.ndim == 2:      # embed/head-style leaf as an L=1 stack
            tp_axis = _tp_axis_for(pstr, leaf.shape)
            perm = jnp.moveaxis(leaf, tp_axis, 0)[None]
            eligible.append((slot, leaf, perm, tp_axis, True))
            continue
        tp_axis = _tp_axis_for(pstr, leaf.shape[1:])
        perm = jnp.moveaxis(leaf, 1 + tp_axis, 1)       # (L, tp_dim, ...)
        eligible.append((slot, leaf, perm, tp_axis, False))
    return out, treedef, eligible


def streaming_encode_plan(params, *,
                          shared_params: Optional[EnecParams] = None,
                          min_bytes: int = MIN_STREAM_BYTES,
                          shards: int = STREAM_SHARDS, codec=None):
    """The :class:`repro.core.EncodePlan` that
    :func:`compress_params_for_streaming` would execute over ``params`` —
    the whole-tree O(#buckets) dispatch guarantee as inspectable data
    (``len(plan.buckets)`` == encode dispatches; benches and CI assert it
    against the measured cache counters instead of trusting folklore)."""
    _, _, eligible = _stream_jobs(params, min_bytes)
    codec = codec or current_codec()
    return codec.plan_encode([e[2] for e in eligible], stacked=True,
                             p=shared_params, shards=shards)


def decompress_sliced(p_sliced):
    """Materialize every storage-only handle in a layer slice (the retired
    ``decompressor`` hook's behaviour — the model now does this itself via
    ``runtime.weights.resolve``; kept for direct/manual use)."""
    return resolve(p_sliced)


def materialize_weight_tree(tree, codec=None):
    """Inverse of :func:`assign_weight_modes` /
    :func:`compress_params_for_streaming`: every handle back to its dense
    ``(L, ...)`` leaf, batched through the decode pipeline so the whole
    tree costs O(#decoder buckets) decode dispatches instead of one per
    leaf (or per layer) — bit-identical to materializing each handle alone
    (ENEC is lossless and the batched decode is dispatch-sharing only).
    """
    flat, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_handle)
    slots = [i for i, leaf in enumerate(flat) if is_handle(leaf)]
    outs = materialize_full_many([flat[i] for i in slots], codec)
    for i, out in zip(slots, outs):
        flat[i] = out
    return jax.tree_util.tree_unflatten(treedef, flat)


def abstract_streamed_params(cfg, p: EnecParams, *,
                             min_bytes: int = MIN_STREAM_BYTES,
                             shards: int = STREAM_SHARDS):
    """ShapeDtypeStruct version of compress_params_for_streaming — lets the
    dry-run lower the streamed serve step without allocating anything.
    Shares :func:`stream_eligible` / :func:`_tp_axis_for` with the concrete
    path so the two cannot drift."""
    from repro.models.registry import abstract_params

    params = abstract_params(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        pstr = _pstr(path)
        if not stream_eligible(pstr, leaf.shape, leaf.dtype, min_bytes):
            out.append(leaf)
            continue
        flat2d = len(leaf.shape) == 2
        layer_shape = leaf.shape if flat2d else leaf.shape[1:]
        tp_axis = _tp_axis_for(pstr, layer_shape)
        n_layers = 1 if flat2d else leaf.shape[0]
        perm_shape = (layer_shape[tp_axis],) + tuple(
            d for i, d in enumerate(layer_shape) if i != tp_axis)
        ct1 = abstract_compressed(perm_shape, leaf.dtype, p, shards=shards)
        streams = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_layers,) + s.shape, s.dtype),
            ct1.streams)
        ct = CompressedTensor(
            streams=streams, raw_bytes=None, fmt_name=ct1.fmt_name,
            params=ct1.params, shape=ct1.shape, dtype_str=ct1.dtype_str,
            block_elems=ct1.block_elems, shards=ct1.shards, mode="enec")
        out.append(StreamedWeight(ct=ct, tp_axis=tp_axis,
                                  layer_shape=tuple(layer_shape),
                                  dtype_str=str(jnp.dtype(leaf.dtype)),
                                  flat=flat2d))
    return jax.tree_util.tree_unflatten(treedef, out)


def mode_mix(tree) -> dict:
    """Handle-kind census of a weight tree (``runtime.weights.handle_kind``
    per leaf).  A clean restore shows one compressed kind plus raw
    smalls; a DEGRADED restore shows up here as a mixed tree — leaves
    adopted from a prior step's different layout, or dense fallbacks for
    quarantined bundles.  Logits are unaffected (every kind executes the
    canonical contraction); the mix is the observable of how far the tree
    is from its requested mode."""
    mix: dict = {}
    for leaf in jax.tree.leaves(tree, is_leaf=is_handle):
        k = handle_kind(leaf)
        mix[k] = mix.get(k, 0) + 1
    return mix


def stream_stats(tree) -> dict:
    """Bytes + handle-count accounting over a weight-execution tree.

    ``overlap_eligible_tensors`` counts the streamed leaves the decode-
    prefetch pipeline (``runtime.overlap``) can schedule ahead of compute;
    ``flat_stream_tensors`` is the subset stored as L=1 stacks of plain 2-D
    leaves (embed / lm_head), which sit outside the layer loop and decode
    once per step rather than once per layer."""
    from repro.runtime.experts import ExpertRef
    total_raw = total_dev = 0
    counts = {"streamed_tensors": 0, "fused_tensors": 0, "dense_handles": 0,
              "flat_stream_tensors": 0, "overlap_eligible_tensors": 0,
              "expert_tensors": 0}
    for leaf in jax.tree.leaves(tree, is_leaf=is_handle):
        if isinstance(leaf, ExpertRef):
            # expert stacks live as compressed records in HOST RAM; the
            # device holds only the tiny (L,) layer-id vector, so their
            # raw bytes count toward hbm_ratio with ~zero device bytes
            counts["expert_tensors"] += 1
            total_raw += leaf.raw_nbytes()
            total_dev += leaf.layer_ids.size * leaf.layer_ids.dtype.itemsize
        elif isinstance(leaf, StreamedWeight):
            counts["streamed_tensors"] += 1
            if leaf.flat:
                counts["flat_stream_tensors"] += 1
            else:
                counts["overlap_eligible_tensors"] += 1
            n_layers = leaf.ct.streams.mask.shape[0]
            per_layer_raw = int(np.prod(leaf.layer_shape)) \
                * jnp.dtype(leaf.dtype_str).itemsize
            total_raw += n_layers * per_layer_raw
            total_dev += leaf.ct.nbytes_device()
        elif isinstance(leaf, FusedWeight):
            counts["fused_tensors"] += 1
            n_layers = leaf.ct.streams.mask.shape[0]
            total_raw += n_layers * leaf.k * leaf.n \
                * jnp.dtype(leaf.dtype_str).itemsize
            total_dev += leaf.ct.nbytes_device()
        elif isinstance(leaf, DenseWeight):
            counts["dense_handles"] += 1
            total_raw += leaf.w.size * leaf.w.dtype.itemsize
            total_dev += leaf.w.size * leaf.w.dtype.itemsize
        elif hasattr(leaf, "size"):
            total_raw += leaf.size * leaf.dtype.itemsize
            total_dev += leaf.size * leaf.dtype.itemsize
    return {**counts, "raw_bytes": total_raw, "device_bytes": total_dev,
            "hbm_ratio": total_raw / max(total_dev, 1)}
