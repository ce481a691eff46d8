"""The v1 public codec API: ``CodecConfig`` + ``Codec`` with an explicit
plan/execute split and NO module-global state.

PRs 1-4 grew ``core/api.py`` into ~20 top-level functions driven by process
globals (two backend selectors, two compile caches, two cache-stat
singletons, a wire transfer counter).  That shape cannot host two models
with different codec settings in one process, and it made the pipeline's
O(#buckets) dispatch guarantees benchmark folklore instead of API
properties.  This module is the replacement:

* :class:`CodecConfig` — the immutable policy knobs: encode/decode backend,
  default ``block_elems``, the block-count bucketing policy, and the
  parameter-search policy (per-tensor histogram search, or fixed
  ``shared_params`` for the paper's transferability mode).
* :class:`Codec` — an instance owning its OWN encoder/decoder compile
  caches, cache-stat counters, and host->device transfer counter.  Two
  codecs with different backends coexist in one process with fully
  independent state.
* **plan/execute split** — :meth:`Codec.plan_encode` /
  :meth:`Codec.plan_decode` return :class:`EncodePlan` / :class:`DecodePlan`
  objects that expose the bucket assignment (one
  ``(backend, fmt, (n, m, L), block_elems, block-count bucket)`` group per
  jit dispatch), the dispatch count, and the predicted wire bytes as
  inspectable data; :meth:`Codec.execute` runs the batched dispatches.
  ``len(plan.buckets)`` IS the number of dispatches the execute performs —
  asserted by tests, relied on by the benchmarks.

The legacy module-level functions in ``core/api.py`` remain as thin
deprecated wrappers over :func:`current_codec` (the ambient codec:
:func:`use_codec` context override, else the process :func:`default_codec`),
so existing trees, wire records, and tests keep working bit-identically.
See docs/API.md for the stability contract and the migration table.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import codec as block_codec
from . import params as params_mod
from . import stats as stats_mod
from .api import (MATMUL_TILE, CompressedTensor, _is_supported_float,
                  _raw_tensor, matmul_tiles, slice_stacked)
from .codec import BlockStreams
from .dtypes import FORMATS, FloatFormat, format_for
from .params import DEFAULT_BLOCK_ELEMS, EnecParams, expected_ratio

BACKENDS = ("reference", "pallas")

# Transfer-ledger links: every byte a codec moves is attributed to exactly
# one link, split compressed-vs-dense (paper thesis: the links should only
# ever carry compressed bytes).
#   h2d            host->device uploads (wire deserialization, raw leaves)
#   d2d_allgather  device<->device stream gathers over a mesh axis
#                  (compressed-bytes all-gather for FSDP-style weights)
#   d2d_psum       device<->device gradient collectives
#                  (optim.grad_compress.compressed_allreduce)
#   disk           checkpoint pack-file record reads
LINKS = ("h2d", "d2d_allgather", "d2d_psum", "disk")

_flatten_streams = block_codec.flatten_blocks


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CodecConfig:
    """Immutable policy for one :class:`Codec` instance.

    encode_backend / decode_backend
        ``"reference"`` (pure-jnp codec, any backend — default) or
        ``"pallas"`` (the TPU kernels; ``interpret=True`` elsewhere).
    block_elems
        Default ENEC block size when a call does not override it
        (paper §VI-D: 16384 == one 128x128 MXU tile).
    bucket_pow2_max / bucket_multiple
        The block-count bucketing policy for the compile caches: counts are
        rounded up to powers of two up to ``bucket_pow2_max``, then to
        multiples of ``bucket_multiple`` — bounding distinct compiles while
        keeping pad waste small.
    shared_params
        ``None`` (default) searches parameters per tensor from its exponent
        histogram; a fixed :class:`EnecParams` selects the paper's
        transferability mode (every tensor encodes under these params,
        widened to its exact exponent range for unconditional losslessness).
    max_cached_programs
        Safety valve on each compile cache (never hit in practice).
    """
    encode_backend: str = "reference"
    decode_backend: str = "reference"
    block_elems: int = DEFAULT_BLOCK_ELEMS
    bucket_pow2_max: int = 64
    bucket_multiple: int = 64
    shared_params: Optional[EnecParams] = None
    max_cached_programs: int = 512

    def __post_init__(self):
        for field in ("encode_backend", "decode_backend"):
            name = getattr(self, field)
            if name not in BACKENDS:
                raise ValueError(f"unknown {field} {name!r}; "
                                 f"expected one of {BACKENDS}")
        if self.block_elems < 1 or self.bucket_pow2_max < 1 \
                or self.bucket_multiple < 1:
            raise ValueError("block_elems / bucket policy must be >= 1")


# ---------------------------------------------------------------------------
# plan objects: the bucket assignment as inspectable data
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EncodeBucket:
    """One encode dispatch: every member tensor shares this compiled
    encoder.  ``key`` is the compile-cache key
    ``(backend, fmt, params-key, block_elems, block-count bucket)``."""
    backend: str
    fmt_name: str
    params_key: tuple        # (n, m, L) on reference; full tuple on pallas
    block_elems: int
    block_bucket: int        # bucketed (padded) block count of the dispatch
    nblocks: int             # true flat blocks across all members
    n_tensors: int           # member stacks encoded by this dispatch
    predicted_wire_bytes: int

    @property
    def key(self) -> tuple:
        return (self.backend, self.fmt_name, self.params_key,
                self.block_elems, self.block_bucket)


@dataclasses.dataclass(frozen=True)
class DecodeBucket:
    """One decode dispatch; mirror of :class:`EncodeBucket`."""
    backend: str
    fmt_name: str
    params_key: tuple
    block_elems: int
    block_bucket: int
    nblocks: int
    n_tensors: int

    @property
    def key(self) -> tuple:
        return (self.backend, self.fmt_name, self.params_key,
                self.block_elems, self.block_bucket)


@dataclasses.dataclass
class EncodePlan:
    """Inspectable encode schedule for one input tree.

    ``len(buckets)`` == the exact number of encode dispatches
    :meth:`Codec.execute` will launch; ``n_fallback`` counts inputs that
    skip the encoder entirely (unsupported dtype, empty, or constant —
    resolved per the calling mode's escape rules at execute time).
    """
    config: CodecConfig
    buckets: Tuple[EncodeBucket, ...]
    n_inputs: int
    n_fallback: int
    stacked: bool
    shards: int
    block_elems: int = DEFAULT_BLOCK_ELEMS
    # -- internal execution state (not part of the stable surface) --------
    _treedef: Any = dataclasses.field(repr=False, default=None)
    _groups: list = dataclasses.field(repr=False, default_factory=list)
    _fallbacks: dict = dataclasses.field(repr=False, default_factory=dict)
    _leaves: list = dataclasses.field(repr=False, default_factory=list)

    @property
    def dispatch_count(self) -> int:
        return len(self.buckets)

    @property
    def predicted_wire_bytes(self) -> int:
        return sum(b.predicted_wire_bytes for b in self.buckets)


@dataclasses.dataclass
class DecodePlan:
    """Inspectable decode schedule; mirror of :class:`EncodePlan`.

    ``n_passthrough`` counts const/raw/non-compressed leaves that restore
    without a decode dispatch.
    """
    config: CodecConfig
    buckets: Tuple[DecodeBucket, ...]
    n_inputs: int
    n_passthrough: int
    # exact=True skips block-count bucket rounding: each dispatch decodes
    # its true block count with zero pad waste (the overlap prefetch path,
    # which decodes the same per-layer leaf set every step, so the compile
    # cache sees one stable exact count instead of unbounded variety)
    exact: bool = False
    _treedef: Any = dataclasses.field(repr=False, default=None)
    _groups: list = dataclasses.field(repr=False, default_factory=list)
    _passthrough: dict = dataclasses.field(repr=False, default_factory=dict)
    _leaves: list = dataclasses.field(repr=False, default_factory=list)

    @property
    def dispatch_count(self) -> int:
        return len(self.buckets)


def _is_ct(x) -> bool:
    return isinstance(x, CompressedTensor)


def _stack_dim(ct: CompressedTensor) -> Optional[int]:
    """Leading layer count of a stacked tensor, or ``None`` for a per-leaf
    tensor (whose metadata already describes the whole array)."""
    base = 3 if ct.shards > 1 else 2
    return ct.streams.mask.shape[0] if ct.streams.mask.ndim == base + 1 \
        else None


def _stacked_from_bits(ct: CompressedTensor, n_layers: int, bits):
    """(L*B, N) decoded bits -> the dense ``(L,) + ct.shape`` stack.

    The bits are first split per layer as ``(L, B, N)`` (major dims only):
    on a TPU the one-step ``(L*B, N) -> (L, B*N)`` reshape of a full-width
    stack compiles to minutes of relayout code, the two eager steps in
    under a second (see ``codec.stacked_blocks``)."""
    per = int(np.prod(ct.shape))
    bits = bits.reshape(n_layers, -1, bits.shape[-1])
    if bits.shape[1] * bits.shape[2] != per:   # block padding to slice away
        bits = bits.reshape(n_layers, -1)[:, :per]
    return bits.view(ct.fmt.float_dtype).reshape(
        (n_layers,) + ct.shape).astype(jnp.dtype(ct.dtype_str))


# ---------------------------------------------------------------------------
# the codec
# ---------------------------------------------------------------------------

class Codec:
    """One ENEC codec instance: config + compile caches + counters.

    All state is instance-scoped — construct one per model/tenant and pass
    it explicitly (``CheckpointManager(codec=...)``,
    ``assign_weight_modes(..., codec=...)``), or install it as the ambient
    codec with :func:`use_codec`.  Every compression entry point either
    takes the plan/execute route (:meth:`plan_encode` -> :meth:`execute`)
    or is a thin convenience over it.
    """

    def __init__(self, config: Optional[CodecConfig] = None, **overrides):
        if config is None:
            config = CodecConfig(**overrides)
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config
        self._encode_cache: dict = {}
        self._decode_cache: dict = {}
        self._encode_stats = {"compiles": 0, "cache_hits": 0,
                              "dispatches": 0, "padded_blocks": 0}
        self._decode_stats = {"compiles": 0, "cache_hits": 0,
                              "dispatches": 0, "padded_blocks": 0}
        self._transfer = {"h2d_bytes": 0, "h2d_arrays": 0}
        self._links = {link: {"compressed_bytes": 0, "dense_bytes": 0,
                              "ops": 0} for link in LINKS}

    def __repr__(self):
        c = self.config
        return (f"Codec(encode={c.encode_backend!r}, "
                f"decode={c.decode_backend!r}, block_elems={c.block_elems})")

    # -- configuration ----------------------------------------------------

    def configure(self, config: CodecConfig) -> "Codec":
        """Swap the config in place, clearing only the compile caches whose
        keys the change invalidates.  Returns ``self``."""
        old = self.config
        if config == old:
            return self
        self.config = config
        if (config.encode_backend, config.bucket_pow2_max,
                config.bucket_multiple) != (old.encode_backend,
                                            old.bucket_pow2_max,
                                            old.bucket_multiple):
            self._encode_cache.clear()
        if (config.decode_backend, config.bucket_pow2_max,
                config.bucket_multiple) != (old.decode_backend,
                                            old.bucket_pow2_max,
                                            old.bucket_multiple):
            self._decode_cache.clear()
        return self

    def set_encode_backend(self, name: str) -> None:
        """Legacy-compat mutator; prefer constructing
        ``Codec(encode_backend=...)``."""
        self.configure(dataclasses.replace(self.config, encode_backend=name))

    def set_decode_backend(self, name: str) -> None:
        """Legacy-compat mutator; prefer constructing
        ``Codec(decode_backend=...)``."""
        self.configure(dataclasses.replace(self.config, decode_backend=name))

    # -- counters ---------------------------------------------------------

    def encode_cache_stats(self) -> dict:
        """Counters for the jit'd-encoder cache: ``compiles`` distinct
        encoder instantiations, ``dispatches`` encode calls,
        ``padded_blocks`` zero blocks added by block-count bucketing."""
        return dict(self._encode_stats,
                    cached_encoders=len(self._encode_cache),
                    backend=self.config.encode_backend)

    def decode_cache_stats(self) -> dict:
        """Mirror of :meth:`encode_cache_stats` for the decoder cache."""
        return dict(self._decode_stats,
                    cached_decoders=len(self._decode_cache),
                    backend=self.config.decode_backend)

    def reset_encode_cache_stats(self, clear_cache: bool = False) -> None:
        for k in self._encode_stats:
            self._encode_stats[k] = 0
        if clear_cache:
            self._encode_cache.clear()

    def reset_decode_cache_stats(self, clear_cache: bool = False) -> None:
        for k in self._decode_stats:
            self._decode_stats[k] = 0
        if clear_cache:
            self._decode_cache.clear()

    def transfer_stats(self) -> dict:
        """Bytes this codec moved, per link (see :data:`LINKS`).

        The flat ``h2d_bytes`` / ``h2d_arrays`` keys are the legacy h2d-only
        view (the compressed-restore acceptance test uses them to prove no
        dense weight ever crossed the host->device link); ``links`` is the
        full per-link ledger with a compressed-vs-dense split per link.
        """
        out = dict(self._transfer)
        out["links"] = self.link_stats()
        return out

    def link_stats(self) -> dict:
        """The per-link transfer ledger alone:
        ``{link: {compressed_bytes, dense_bytes, ops}}``."""
        return {link: dict(v) for link, v in self._links.items()}

    def reset_transfer_stats(self) -> None:
        for k in self._transfer:
            self._transfer[k] = 0
        for entry in self._links.values():
            for k in entry:
                entry[k] = 0

    def count_link(self, link: str, nbytes: int, *, dense: bool = False,
                   ops: int = 1) -> None:
        """Attribute ``nbytes`` moved over ``link`` (one of :data:`LINKS`).
        ``dense=True`` marks payloads that are NOT fixed-length wire
        streams (raw checkpoint leaves, incompressible escapes) — the
        quantity the per-link acceptance gates require to stay zero on the
        collective links."""
        if link not in self._links:
            raise ValueError(f"unknown transfer link {link!r}; "
                             f"expected one of {LINKS}")
        entry = self._links[link]
        entry["dense_bytes" if dense else "compressed_bytes"] += int(nbytes)
        entry["ops"] += int(ops)
        if link == "h2d":
            self._transfer["h2d_bytes"] += int(nbytes)
            self._transfer["h2d_arrays"] += int(ops)

    def count_h2d(self, nbytes: int, arrays: int = 1, *,
                  dense: bool = False) -> None:
        """Record a host->device upload (``core.wire.h2d`` calls this).
        Thin alias for ``count_link("h2d", ...)``."""
        self.count_link("h2d", nbytes, dense=dense, ops=arrays)

    # -- bucketing / compile caches --------------------------------------

    def _block_bucket(self, nblocks: int) -> int:
        """Round the block count up so a 48-layer model hits a handful of
        compiled codecs instead of one per distinct tensor shape: powers of
        two up to ``bucket_pow2_max`` blocks, multiples of
        ``bucket_multiple`` above (pure pow2 would pad up to 2x the work
        for large stacks)."""
        cfg = self.config
        if nblocks <= 1:
            return 1
        if nblocks <= cfg.bucket_pow2_max:
            return 1 << (nblocks - 1).bit_length()
        return -(-nblocks // cfg.bucket_multiple) * cfg.bucket_multiple

    def _encoder_key(self, fmt_name: str, p: EnecParams,
                     block_elems: int) -> tuple:
        """Compile-cache key sans block count.  The reference encoder keeps
        the linear-map parameter ``b`` as a traced per-block operand (it
        never enters a shape), so one compiled program serves every ``b`` —
        the key carries only (n, m, L).  The Pallas kernel bakes the whole
        param tuple in."""
        backend = self.config.encode_backend
        if backend == "pallas":
            return (backend, fmt_name, p.astuple(), block_elems)
        return (backend, fmt_name, (p.n, p.m, p.L), block_elems)

    def _decoder_key(self, fmt_name: str, p: EnecParams,
                     block_elems: int) -> tuple:
        """Decoder mirror of :meth:`_encoder_key`: the reference decoder
        takes the inverse-transform params ``(b, l)`` as traced per-block
        operands, the Pallas kernel bakes the full tuple in."""
        backend = self.config.decode_backend
        if backend == "pallas":
            return (backend, fmt_name, p.astuple() + (p.l,), block_elems)
        return (backend, fmt_name, (p.n, p.m, p.L), block_elems)

    def _encoder_for(self, fmt_name: str, p: EnecParams, block_elems: int,
                     bucket: int):
        key = self._encoder_key(fmt_name, p, block_elems) + (bucket,)
        fn = self._encode_cache.get(key)
        if fn is None:
            if len(self._encode_cache) >= self.config.max_cached_programs:
                self._encode_cache.clear()   # safety valve
            self._encode_stats["compiles"] += 1
            fmt = FORMATS[fmt_name]
            # encode reads (n, m, L) for shapes and b for arithmetic only;
            # normalizing the bookkeeping fields lets params that differ in
            # (l, expected_bits) — and, on the reference backend, b — share
            # one compile
            p_norm = EnecParams(b=p.b, n=p.n, m=p.m, L=p.L, l=0)
            if self.config.encode_backend == "pallas":
                from repro.kernels import ops as kernel_ops  # lazy: cycle
                fn = kernel_ops.pipeline_encoder(fmt, p_norm)
            else:
                fn = jax.jit(functools.partial(block_codec.encode_blocks,
                                               fmt=fmt, p=p_norm))
            self._encode_cache[key] = fn
        else:
            self._encode_stats["cache_hits"] += 1
        return fn

    def _decoder_for(self, fmt_name: str, p: EnecParams, block_elems: int,
                     bucket: int):
        key = self._decoder_key(fmt_name, p, block_elems) + (bucket,)
        fn = self._decode_cache.get(key)
        if fn is None:
            if len(self._decode_cache) >= self.config.max_cached_programs:
                self._decode_cache.clear()   # safety valve
            self._decode_stats["compiles"] += 1
            fmt = FORMATS[fmt_name]
            # decode reads (n, m, L) for shapes; (b, l) enter arithmetic
            # only and the reference backend always overrides them with
            # per-block vectors, so params differing in (b, l,
            # expected_bits) share one compile there
            p_norm = EnecParams(b=p.b, n=p.n, m=p.m, L=p.L, l=p.l)
            if self.config.decode_backend == "pallas":
                from repro.kernels import ops as kernel_ops  # lazy: cycle
                fn = kernel_ops.pipeline_decoder(fmt, p_norm, block_elems)
            else:
                fn = jax.jit(functools.partial(block_codec.decode_blocks,
                                               n_elems=block_elems, fmt=fmt,
                                               p=p_norm))
            self._decode_cache[key] = fn
        else:
            self._decode_stats["cache_hits"] += 1
        return fn

    def _encode_bucketed(self, bits, fmt: FloatFormat, p: EnecParams,
                         block_elems: int, b_vec=None) -> BlockStreams:
        """One encode dispatch for a (B, N) block array, compile-cached on
        the bucketed block count (pad with zero blocks, slice the result).

        ``b_vec`` optionally carries a per-block linear-map parameter so
        blocks from stacks with different searched ``b`` share the dispatch.
        """
        nblocks = bits.shape[0]
        bucket = self._block_bucket(nblocks)
        if self.config.encode_backend != "pallas" and b_vec is None:
            b_vec = jnp.full((nblocks,), p.b, jnp.int32)
        if bucket != nblocks:
            self._encode_stats["padded_blocks"] += bucket - nblocks
            bits = jnp.concatenate(
                [bits,
                 jnp.zeros((bucket - nblocks, bits.shape[1]), bits.dtype)])
            if b_vec is not None:
                b_vec = jnp.concatenate(
                    [b_vec, jnp.full((bucket - nblocks,), p.b, jnp.int32)])
        fn = self._encoder_for(fmt.name, p, block_elems, bucket)
        self._encode_stats["dispatches"] += 1
        streams = fn(bits) if b_vec is None else fn(bits, b_vec=b_vec)
        if bucket != nblocks:
            streams = jax.tree.map(lambda a: a[:nblocks], streams)
        return streams

    def _decode_bucketed(self, streams: BlockStreams, fmt: FloatFormat,
                         p: EnecParams, block_elems: int,
                         b_vec=None, l_vec=None, exact=False):
        """One decode dispatch for flat (B, ...) block streams; mirror of
        :meth:`_encode_bucketed` (per-block ``b_vec`` / ``l_vec`` let
        tensors with different searched ``(b, l)`` share the dispatch).
        ``exact=True`` decodes the true block count without bucket
        rounding (zero pad waste; see :meth:`plan_decode`)."""
        nblocks = streams.mask.shape[0]
        # inside a traced program the enclosing jit compiles once per shape
        # anyway, so padding to a shared bucket would only copy the streams
        exact = exact or isinstance(streams.mask, jax.core.Tracer)
        bucket = nblocks if exact else self._block_bucket(nblocks)
        if self.config.decode_backend != "pallas":
            if b_vec is None:
                b_vec = jnp.full((nblocks,), p.b, jnp.int32)
            if l_vec is None:
                l_vec = jnp.full((nblocks,), p.l, jnp.int32)
        if bucket != nblocks:
            self._decode_stats["padded_blocks"] += bucket - nblocks
            pad = bucket - nblocks
            streams = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]), streams)
            if b_vec is not None:
                b_vec = jnp.concatenate(
                    [b_vec, jnp.full((pad,), p.b, jnp.int32)])
                l_vec = jnp.concatenate(
                    [l_vec, jnp.full((pad,), p.l, jnp.int32)])
        fn = self._decoder_for(fmt.name, p, block_elems, bucket)
        self._decode_stats["dispatches"] += 1
        bits = (fn(streams) if b_vec is None
                else fn(streams, b_vec=b_vec, l_vec=l_vec))
        return bits[:nblocks] if bucket != nblocks else bits

    # -- plan_encode ------------------------------------------------------

    def plan_encode(self, tree, *, stacked: bool = False,
                    p: Optional[EnecParams] = None,
                    block_elems: Optional[int] = None,
                    shards: int = 1) -> EncodePlan:
        """Build the encode schedule for every array leaf of ``tree``.

        ``stacked=False`` (default) compresses each leaf as one tensor
        (:meth:`compress_tree` semantics — escapes produce const/raw
        tensors); ``stacked=True`` treats each leaf as an ``(L, ...)``
        layer stack (:meth:`compress_stacked_many` semantics — escapes
        resolve to ``None``: the stack must stay dense).

        The plan is pure data + staged device blocks: statistics are one
        jit dispatch per leaf with ONE batched host transfer, the host-side
        histogram search runs here, and leaves sharing an encoder bucket
        ``(backend, fmt, params-key, block_elems, block-count bucket)`` are
        assigned to one :class:`EncodeBucket` == one future jit dispatch.
        Nothing is encoded until :meth:`execute`.
        """
        if p is None:
            p = self.config.shared_params
        if block_elems is None:
            block_elems = self.config.block_elems
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        fallbacks: dict = {}    # slot -> ("dense" | "const", host_first)
        prepared = []           # (slot, fmt, bits2d, layer_shape, dtype, dev)
        for slot, x in enumerate(leaves):
            x = jnp.asarray(x)
            leaves[slot] = x
            xs = x if stacked else x[None]
            if xs.ndim < 1 or not _is_supported_float(xs) or xs.size == 0:
                fallbacks[slot] = ("dense", None)
                continue
            fmt = format_for(xs.dtype)
            # the (L, ...) bit view keeps the layer's own dims: staging
            # blocks from it avoids a slow TPU relayout (stacked_blocks)
            bits = xs.view(fmt.uint_dtype)
            prepared.append((slot, fmt, bits, xs.shape[1:], str(xs.dtype),
                             stats_mod.stack_stats_device(bits, fmt)))
        host_stats = stats_mod.fetch_stats([pr[-1] for pr in prepared])

        # host search + block layout, grouped by encoder key
        groups: Dict[tuple, list] = {}
        for (slot, fmt, bits, layer_shape, dtype_str, _), st in zip(
                prepared, host_stats):
            if st.is_const.any():
                # parity with the per-leaf const escape: a constant layer
                # keeps the whole stack dense (stacked) / stores the single
                # value (per-leaf)
                fallbacks[slot] = ("const", st.first)
                continue
            pi = (params_mod.search(st.hist, fmt, block_elems=block_elems)
                  if p is None else p)
            # one widen to the stack's exact bounds: covers transferred
            # params and sampled histograms
            pi = params_mod.widen_for_range(pi, *st.bounds())
            blocks, per_layer_blocks = block_codec.stacked_blocks(
                bits, block_elems, shards,
                pad_value=pi.b << fmt.mant_bits)
            key = self._encoder_key(fmt.name, pi, block_elems)
            groups.setdefault(key, []).append(dict(
                slot=slot, fmt=fmt, p=pi, blocks=blocks,
                n_layers=bits.shape[0], layer_shape=layer_shape,
                dtype_str=dtype_str, per_layer_blocks=per_layer_blocks,
                raw_bytes=bits.size * jnp.dtype(dtype_str).itemsize))

        buckets = []
        for key, members in groups.items():
            nblocks = sum(m["blocks"].shape[0] for m in members)
            predicted = sum(
                int(m["raw_bytes"] / expected_ratio(m["p"], m["fmt"]))
                for m in members)
            buckets.append(EncodeBucket(
                backend=key[0], fmt_name=key[1], params_key=key[2],
                block_elems=key[3], block_bucket=self._block_bucket(nblocks),
                nblocks=nblocks, n_tensors=len(members),
                predicted_wire_bytes=predicted))
        return EncodePlan(
            config=self.config, buckets=tuple(buckets),
            n_inputs=len(leaves), n_fallback=len(fallbacks),
            stacked=stacked, shards=shards, block_elems=block_elems,
            _treedef=treedef, _groups=list(groups.values()),
            _fallbacks=fallbacks, _leaves=leaves)

    # -- plan_decode ------------------------------------------------------

    def plan_decode(self, tree, *, exact: bool = False) -> DecodePlan:
        """Build the decode schedule for every :class:`CompressedTensor` in
        ``tree`` (any pytree; a plain list of tensors — with ``None`` holes
        — works too).  Tensors sharing a decoder bucket are assigned to one
        :class:`DecodeBucket` == one future jit dispatch; const/raw tensors
        and non-compressed leaves restore without any dispatch
        (``n_passthrough``).  ``exact=True`` disables block-count bucket
        rounding — each dispatch decodes its true block count (no pad
        waste), at the cost of one compiled decoder per distinct count;
        use it when the same tensor set decodes repeatedly (the overlap
        scheduler's per-layer prefetch)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_ct)
        passthrough: dict = {}   # slot -> "ct" (const/raw) | "identity"
        groups: Dict[tuple, list] = {}
        for slot, leaf in enumerate(leaves):
            if not _is_ct(leaf):
                passthrough[slot] = "identity"
                continue
            if leaf.mode != "enec":
                passthrough[slot] = "ct"
                continue
            key = self._decoder_key(leaf.fmt_name, leaf.params,
                                    leaf.block_elems)
            groups.setdefault(key, []).append(dict(
                slot=slot, ct=leaf, stack=_stack_dim(leaf),
                flat=_flatten_streams(leaf.streams)))
        buckets = []
        for key, members in groups.items():
            nblocks = sum(m["flat"].mask.shape[0] for m in members)
            buckets.append(DecodeBucket(
                backend=key[0], fmt_name=key[1], params_key=key[2],
                block_elems=key[3],
                block_bucket=nblocks if exact
                else self._block_bucket(nblocks),
                nblocks=nblocks, n_tensors=len(members)))
        return DecodePlan(
            config=self.config, buckets=tuple(buckets),
            n_inputs=len(leaves), n_passthrough=len(passthrough),
            exact=exact, _treedef=treedef, _groups=list(groups.values()),
            _passthrough=passthrough, _leaves=leaves)

    # -- execute ----------------------------------------------------------

    def execute(self, plan):
        """Run a plan's batched dispatches and return the output tree.

        Launches EXACTLY ``len(plan.buckets)`` jit dispatches (one per
        bucket) plus, for encode plans, one batched host transfer for the
        never-worse wire-size escape.  The plan must have been built by a
        codec with this configuration (compile-cache keys depend on it).
        """
        if isinstance(plan, EncodePlan):
            if plan.config != self.config:
                raise ValueError(
                    "plan was built under a different CodecConfig — "
                    "re-plan with this codec before executing")
            return self._execute_encode(plan)
        if isinstance(plan, DecodePlan):
            if plan.config != self.config:
                raise ValueError(
                    "plan was built under a different CodecConfig — "
                    "re-plan with this codec before executing")
            return self._execute_decode(plan)
        raise TypeError(f"not a plan: {type(plan).__name__}")

    def _execute_encode(self, plan: EncodePlan):
        results: List[Optional[CompressedTensor]] = [None] * plan.n_inputs
        shards = plan.shards
        for members in plan._groups:
            if len(members) == 1:
                all_blocks = members[0]["blocks"]
            else:
                all_blocks = jnp.concatenate([m["blocks"] for m in members])
            b_vec = None
            if self.config.encode_backend != "pallas":
                b_vec = jnp.concatenate(
                    [jnp.full((m["blocks"].shape[0],), m["p"].b, jnp.int32)
                     for m in members])
            # block arrays are (B, block_elems), so the group's block size
            # is simply the trailing dim
            streams = self._encode_bucketed(
                all_blocks, members[0]["fmt"], members[0]["p"],
                members[0]["blocks"].shape[1], b_vec=b_vec)
            offset = 0
            for m in members:
                nb = m["blocks"].shape[0]
                s = jax.tree.map(lambda a: a[offset:offset + nb], streams)
                offset += nb
                n_layers, plb = m["n_layers"], m["per_layer_blocks"]
                lead = ((n_layers, shards, plb // shards) if shards > 1
                        else (n_layers, plb))
                s = block_codec.reshape_blocks(s, lead)
                results[m["slot"]] = CompressedTensor(
                    streams=s, raw_bytes=None, fmt_name=m["fmt"].name,
                    params=m["p"], shape=tuple(m["layer_shape"]),
                    dtype_str=m["dtype_str"],
                    block_elems=m["blocks"].shape[1],
                    shards=shards, mode="enec")

        # never-worse escape: ONE batched transfer for every stack's
        # high_len, which also fills the nbytes_wire caches
        pending = [(slot, ct) for slot, ct in enumerate(results)
                   if ct is not None]
        if pending:
            high_lens = jax.device_get(
                [ct.streams.high_len for _, ct in pending])
            for (slot, ct), hl in zip(pending, high_lens):
                n_layers = ct.streams.mask.shape[0]
                wire = ct._set_wire_bytes(hl)
                if wire >= n_layers * ct.nbytes_raw():
                    results[slot] = None

        if not plan.stacked:
            results = self._finish_per_leaf(plan, results)
        return jax.tree_util.tree_unflatten(plan._treedef, results)

    def _finish_per_leaf(self, plan: EncodePlan, results):
        """Per-leaf (compress_tree) semantics: unwrap the L=1 stacks and
        resolve escapes to const/raw tensors instead of ``None``."""
        out = []
        for slot, ct in enumerate(results):
            if ct is not None:
                wire_bytes = ct._wire_bytes        # survives the unstack
                ct = slice_stacked(ct, 0)
                ct._wire_bytes = wire_bytes
                out.append(ct)
                continue
            x = plan._leaves[slot]
            kind, first = plan._fallbacks.get(slot, ("dense", None))
            if kind == "const":
                fmt = format_for(x.dtype)
                out.append(CompressedTensor(
                    streams=None,
                    raw_bytes=jnp.asarray(first[:1]).view(jnp.uint8),
                    fmt_name=fmt.name, params=None, shape=tuple(x.shape),
                    dtype_str=str(x.dtype), block_elems=plan.block_elems,
                    shards=plan.shards, mode="const"))
            else:
                # unsupported dtype / empty / incompressible: raw escape
                out.append(_raw_tensor(x, plan.shards))
        return out

    def _execute_decode(self, plan: DecodePlan):
        results: List[Optional[Any]] = [None] * plan.n_inputs
        # passthrough leaves: identity for non-tensors, direct expansion
        # for const/raw tensors (no dispatch either way)
        for slot, kind in plan._passthrough.items():
            leaf = plan._leaves[slot]
            results[slot] = (self.decompress_array(leaf) if kind == "ct"
                             else leaf)
        for members in plan._groups:
            if len(members) == 1:
                flat = members[0]["flat"]
            else:
                flat = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                                    *[m["flat"] for m in members])
            p0 = members[0]["ct"].params
            b_vec = l_vec = None
            if self.config.decode_backend != "pallas":
                b_vec = jnp.concatenate(
                    [jnp.full((m["flat"].mask.shape[0],), m["ct"].params.b,
                              jnp.int32) for m in members])
                l_vec = jnp.concatenate(
                    [jnp.full((m["flat"].mask.shape[0],), m["ct"].params.l,
                              jnp.int32) for m in members])
            bits = self._decode_bucketed(flat, members[0]["ct"].fmt, p0,
                                         members[0]["ct"].block_elems,
                                         b_vec=b_vec, l_vec=l_vec,
                                         exact=plan.exact)
            offset = 0
            for m in members:
                nb = m["flat"].mask.shape[0]
                bits_m = bits[offset:offset + nb]
                offset += nb
                ct = m["ct"]
                results[m["slot"]] = (
                    block_codec.from_blocks(bits_m, ct.shape, ct.fmt)
                    if m["stack"] is None
                    else _stacked_from_bits(ct, m["stack"], bits_m))
        return jax.tree_util.tree_unflatten(plan._treedef, results)

    # -- single-array convenience (direct ports of the legacy functions) --

    def compress_array(self, x, p: Optional[EnecParams] = None,
                       block_elems: Optional[int] = None,
                       shards: int = 1) -> CompressedTensor:
        """Compress one array. ``p=None`` uses the config's params policy
        (per-tensor histogram search unless ``shared_params`` is set).

        Device-resident: statistics are one jit'd reduction, only the
        histogram crosses to the host, and the full tensor is never
        transferred.
        """
        if p is None:
            p = self.config.shared_params
        if block_elems is None:
            block_elems = self.config.block_elems
        x = jnp.asarray(x)
        if not _is_supported_float(x) or x.size == 0:
            return _raw_tensor(x, shards)
        fmt = format_for(x.dtype)
        flat_bits = jnp.ravel(x).view(fmt.uint_dtype)
        st = stats_mod.stack_stats(flat_bits[None, :], fmt)
        # constant-tensor escape (RZE-style, LC framework §II-C)
        if bool(st.is_const[0]):
            return CompressedTensor(
                streams=None,
                raw_bytes=jnp.asarray(st.first[:1]).view(jnp.uint8),
                fmt_name=fmt.name, params=None, shape=tuple(x.shape),
                dtype_str=str(x.dtype), block_elems=block_elems,
                shards=shards, mode="const")
        if p is None:
            p = params_mod.search(st.hist, fmt, block_elems=block_elems)
        # widen to the EXACT exponent bounds: a no-op for freshly searched
        # params on an exact histogram, the lossless escape for transferred
        # params, and the correctness guarantee for sampled histograms
        p = params_mod.widen_for_range(p, *st.bounds())
        bits, _ = block_codec.bits_to_blocks(flat_bits, block_elems, shards,
                                             pad_value=p.b << fmt.mant_bits)
        streams = self._encode_bucketed(bits, fmt, p, block_elems)
        if shards > 1:
            streams = block_codec.reshape_blocks(
                streams, (shards, streams.mask.shape[0] // shards))
        ct = CompressedTensor(
            streams=streams, raw_bytes=None, fmt_name=fmt.name, params=p,
            shape=tuple(x.shape), dtype_str=str(x.dtype),
            block_elems=block_elems, shards=shards, mode="enec")
        if ct.nbytes_wire() >= ct.nbytes_raw():
            return _raw_tensor(x, shards)  # incompressible: raw escape
        return ct

    def decompress_array(self, ct: CompressedTensor):
        """Exact inverse of :meth:`compress_array` (jit-compatible).

        Rides the bucketed, compile-cached decoder, so even per-leaf calls
        share compiled decode programs across tensors; use
        :meth:`decompress_stacked_many` / :meth:`plan_decode` to share the
        *dispatch* too.
        """
        dtype = jnp.dtype(ct.dtype_str)
        if ct.mode == "const":
            value = ct.raw_bytes.view(dtype)[0]
            return jnp.broadcast_to(value, ct.shape)
        if ct.mode == "raw":
            return ct.raw_bytes.view(dtype).reshape(ct.shape)
        bits = self._decode_bucketed(_flatten_streams(ct.streams), ct.fmt,
                                     ct.params, ct.block_elems)
        return block_codec.from_blocks(bits, ct.shape, ct.fmt)

    # -- stacked (layer-stack) API ---------------------------------------

    def compress_stacked_many(self, stacks: Sequence[Any],
                              p: Optional[EnecParams] = None,
                              block_elems: Optional[int] = None,
                              shards: int = 1
                              ) -> List[Optional[CompressedTensor]]:
        """Compress many ``(L, ...)`` layer stacks with O(#buckets)
        dispatches: :meth:`plan_encode` + :meth:`execute`.  Returns one
        entry per stack — a stacked :class:`CompressedTensor`, or ``None``
        when the stack must stay dense (unsupported dtype, a constant
        layer, or incompressible data)."""
        plan = self.plan_encode(list(stacks), stacked=True, p=p,
                                block_elems=block_elems, shards=shards)
        return self.execute(plan)

    def compress_stacked(self, x, p: Optional[EnecParams] = None,
                         block_elems: Optional[int] = None,
                         shards: int = 1) -> Optional[CompressedTensor]:
        """Compress one ``(L, ...)`` layer stack in a single encode
        dispatch; ``None`` when the stack must stay dense."""
        return self.compress_stacked_many([x], p, block_elems, shards)[0]

    def decompress_stacked(self, ct: CompressedTensor):
        """Inverse of :meth:`compress_stacked`: one dispatch -> (L, ...)."""
        n_layers = ct.streams.mask.shape[0]
        bits = self._decode_bucketed(_flatten_streams(ct.streams), ct.fmt,
                                     ct.params, ct.block_elems)
        return _stacked_from_bits(ct, n_layers, bits)

    def decompress_stacked_many(self, cts: Sequence[Optional[CompressedTensor]],
                                *, exact: bool = False
                                ) -> List[Optional[Any]]:
        """Decompress many tensors with O(#buckets) decode dispatches:
        :meth:`plan_decode` + :meth:`execute`.  Accepts any mix of per-leaf
        and stacked tensors plus ``const`` / ``raw`` / ``None`` entries;
        outputs are bit-identical to the per-leaf path (``exact`` only
        drops the pad blocks a bucketed dispatch would decode and slice
        away — see :meth:`plan_decode`)."""
        plan = self.plan_decode(list(cts), exact=exact)
        return self.execute(plan)

    # -- pytree API -------------------------------------------------------

    def compress_tree(self, tree, shared_params: Optional[EnecParams] = None,
                      block_elems: Optional[int] = None, shards: int = 1):
        """Compress every leaf with O(#buckets) encode dispatches; float
        leaves get per-tensor searched params (or ``shared_params`` /
        the config's params policy)."""
        plan = self.plan_encode(tree, stacked=False, p=shared_params,
                                block_elems=block_elems, shards=shards)
        return self.execute(plan)

    def decompress_tree(self, ctree):
        """Inverse of :meth:`compress_tree` with O(#buckets) dispatches."""
        return self.execute(self.plan_decode(ctree))

    # -- tile-wise compression for the fused decompress+matmul kernel -----

    def tile_weights_for_fusion_many(self, ws: Sequence[Any],
                                     p: Optional[EnecParams] = None,
                                     shards: int = 1
                                     ) -> List[Optional[CompressedTensor]]:
        """Compress many (L, K, N) / (K, N) matmul weights tile-wise for
        the fused kernel, riding :meth:`compress_stacked_many`: per-stack
        searched params, one encode dispatch per bucket, never-worse
        escape intact (``None`` entries must stay dense).

        ``shards > 1`` splits each layer's tile-block axis into contiguous
        TP shard ranges; the flat (n-major) tile order is preserved, so the
        fused kernel consumes the re-flattened streams unchanged.  Every
        weight's tile-block count must divide by ``shards`` (no pad blocks
        are allowed inside a fused stream — see
        :func:`repro.runtime.streaming.fused_shards`)."""
        tiles = [matmul_tiles(w) for w in ws]
        if shards > 1:
            for w, t in zip(ws, tiles):
                blocks = t.shape[-1] // DEFAULT_BLOCK_ELEMS
                if blocks % shards:
                    raise ValueError(
                        f"fused tile stream of {tuple(jnp.shape(w))} has "
                        f"{blocks} tile blocks — not divisible into "
                        f"{shards} shards (pad blocks would corrupt the "
                        f"kernel's flat tile order)")
        return self.compress_stacked_many(
            tiles, p=p, block_elems=DEFAULT_BLOCK_ELEMS, shards=shards)

    def tile_weights_for_fusion(self, w, p: Optional[EnecParams] = None
                                ) -> CompressedTensor:
        """Compress one weight tile-wise for the fused kernel; raises on
        the incompressible escape (callers that need the fallback use
        :meth:`tile_weights_for_fusion_many`)."""
        squeeze = jnp.asarray(w).ndim == 2
        ct = self.tile_weights_for_fusion_many([w], p)[0]
        if ct is None:
            raise ValueError(
                "weight is incompressible or constant — serve dense")
        if squeeze:
            ct = dataclasses.replace(
                ct, streams=jax.tree.map(lambda a: a[0], ct.streams))
        return ct

    def untile_matmul_weight(self, ct: CompressedTensor, k: int, n: int):
        """Inverse of :func:`core.api.matmul_tiles` for ONE layer slice of
        a tile-wise tensor: decompress, un-permute, strip the padding."""
        t = MATMUL_TILE
        kp, np_ = -(-k // t) * t, -(-n // t) * t
        flat = self.decompress_array(ct)
        tiles = flat.reshape(np_ // t, kp // t, t, t)
        return tiles.transpose(1, 2, 0, 3).reshape(kp, np_)[:k, :n]


# ---------------------------------------------------------------------------
# the ambient codec: process default + context override
# ---------------------------------------------------------------------------

_default_lock = threading.Lock()
_default: Optional[Codec] = None
_ambient: contextvars.ContextVar = contextvars.ContextVar(
    "repro_enec_codec", default=None)


def default_codec() -> Codec:
    """The lazily-created process-default :class:`Codec` — the instance the
    legacy ``core.api`` wrappers operate on."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Codec()
    return _default


def set_default_codec(codec: Codec) -> Codec:
    """Replace the process-default codec; returns the previous one (which
    may be freshly created if none existed yet)."""
    global _default
    prev = default_codec()
    _default = codec
    return prev


def current_codec() -> Codec:
    """The ambient codec: the innermost :func:`use_codec` context if one is
    active, else :func:`default_codec`."""
    return _ambient.get() or default_codec()


@contextlib.contextmanager
def use_codec(codec: Codec):
    """Context manager installing ``codec`` as the ambient codec — every
    legacy wrapper and codec-default consumer inside the block uses it."""
    token = _ambient.set(codec)
    try:
        yield codec
    finally:
        _ambient.reset(token)
