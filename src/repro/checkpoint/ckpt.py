"""ENEC-compressed, fault-tolerant checkpointing (enec-v2 container).

Layout (one directory per step):
    <root>/step_000001230/
        manifest.json          tree structure + per-record (pack, offset,
                               length) index, shapes, dtypes, ENEC stats
        pack-00000.bin ...     per-shard pack files: concatenated framed
                               wire records (length + CRC32 per record)
    <root>/LATEST              atomic pointer file (rename-committed)

Properties needed at 1000+ nodes:
  * atomicity — write to ``.tmp-`` dir, fsync every pack AND the manifest
    AND the directory entries, rename, fsync the parent; LATEST updated
    last; a crash mid-save never corrupts the previous checkpoint and never
    commits a step whose manifest is missing or truncated;
  * async     — saves run on a background thread over host copies, training
    continues; a failed async save re-raises from ``wait()`` and from the
    next ``save()`` instead of vanishing in a daemon thread;
  * parallel  — records are serialized by a thread pool (``writers``) and
    streamed round-robin to the per-shard pack files (peak host memory
    never holds the whole checkpoint);
  * verified  — every record is framed (explicit length + CRC32), so
    ``load()`` rejects truncated or bit-flipped records with a clear error
    instead of silently misdecoding;
  * partial   — records are indexed by name, so serving restores ONLY the
    weight records (optimizer state is never read, let alone inflated);
  * elastic   — load() reshards to ANY mesh via device_put with the target
    NamedShardings (topology can shrink/grow between runs);
  * ~1.35x fewer bytes to the storage system via ENEC (per-tensor searched
    params; raw escape keeps incompressible leaves at 1.0x, never worse);
  * keep-last-k retention + stale-tmp-dir GC (crashed saves leak nothing).

``serving_layout="stream"|"fused"`` additionally stores every
policy-eligible weight in its exact *serving* stream layout (the same
bundles ``runtime.streaming.assign_weight_modes`` would build), which is
what lets :meth:`CheckpointManager.load_for_serving` deserialize records
straight into ``StreamedWeight`` / ``FusedWeight`` handles — compressed
bytes flow disk -> HBM and the dense tensor never exists on the host.
``load()`` still restores the bit-exact dense training tree from the same
records (docs/CHECKPOINT.md).

Reliability (docs/RELIABILITY.md): every pack/manifest read and pack write
funnels through the manager's :class:`RetryPolicy` (transient I/O errors
are retried with backoff+jitter) and the fault-injection hooks of
``runtime/faults.py``.  Under ``policy="degraded"``, a record that still
fails — corrupt frame, exhausted retries, decode failure — is quarantined
on a :class:`RestoreReport` and restored per record from the newest
earlier step with an intact copy, while the surviving records keep the
batched O(#buckets) decode path.  ``policy="strict"`` (the default)
preserves the historical abort-on-first-error contract.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import wire as enec_wire
from repro.core.api import SUPPORTED_FLOAT_DTYPES, slice_stacked
from repro.core.codec_api import Codec, current_codec
from repro.runtime import experts as rt_experts
from repro.runtime import faults as rt_faults
from repro.runtime import streaming as rt_streaming
from repro.runtime.retry import RetryPolicy
from repro.runtime.weights import (DenseWeight, finish_materialize,
                                   handle_from_spec, handle_spec, is_handle)

_ENEC_DTYPES = SUPPORTED_FLOAT_DTYPES

MANIFEST_FORMAT = "enec-v2"

# tree roots that hold optimizer state under the {"params", "opt"} saving
# convention: their leaves mirror the weight paths (so the serving-layout
# eligibility heuristic would match them) but can never be served — they
# stay plain records instead of paying the tile/moveaxis re-layout
_NON_SERVING_ROOTS = frozenset({"opt", "opt_state", "optimizer"})


class CheckpointError(RuntimeError):
    """A checkpoint could not be saved or restored."""


RESTORE_POLICIES = ("strict", "degraded")


@dataclasses.dataclass
class _ExpertPart:
    """One per-expert record queued for the batched decode of a training
    ``load()``: its manifest handle spec (parent / layer / expert grid
    coordinates) plus the device-resident compressed tensor.  The decode
    pass reassembles every part of a parent into the dense ``(L, E, ...)``
    stack — still O(#buckets) dispatches, since all parts of a leaf share
    one searched param set and therefore one decoder bucket."""
    spec: dict
    ct: object


@dataclasses.dataclass
class QuarantinedRecord:
    """One record a restore could not use: its coordinates (name, pack,
    byte offset, length), why it was rejected, and — once the per-record
    fallback succeeds — where the replacement bytes came from."""
    name: str
    pack: str
    offset: int
    length: int
    cause: str
    fallback: str = ""

    def describe(self) -> str:
        line = (f"{self.name} [{self.pack} @ {self.offset}, "
                f"{self.length}B]: {self.cause}")
        if self.fallback:
            line += f" -> {self.fallback}"
        return line


@dataclasses.dataclass
class RestoreReport:
    """What a restore survived (docs/RELIABILITY.md): the quarantined
    records with cause and fallback, plus the manager's retry-policy
    counters — surfaced next to the codec cache stats so reliability is
    observable, not folklore.  Every ``load``/``load_for_serving`` stashes
    its report on ``CheckpointManager.last_restore_report``; an empty
    quarantine list means the restore was clean."""
    step: int
    policy: str
    quarantined: list = dataclasses.field(default_factory=list)
    retry: dict = dataclasses.field(default_factory=dict)

    @property
    def degraded(self) -> bool:
        return bool(self.quarantined)

    def summary(self) -> str:
        head = (f"RestoreReport(step={self.step}, policy={self.policy}, "
                f"quarantined={len(self.quarantined)}, retry={self.retry})")
        return "\n".join([head] + ["  " + q.describe()
                                   for q in self.quarantined])


def _tree_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=is_handle)
    names = ["/".join(str(getattr(k, "key", getattr(k, "name",
             getattr(k, "idx", k)))) for k in path) for path, _ in flat]
    return names, [l for _, l in flat], treedef


def _fsync_path(path) -> None:
    """fsync a file or directory by path (directories need it too: the
    rename-commit is only durable once the parent's entries are)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _leaf_nbytes(shape, dtype_str: str) -> int:
    return int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dtype_str).itemsize


@dataclasses.dataclass
class CheckpointManager:
    root: Path
    keep_last: int = 3
    compress: bool = True
    writers: int = 4                       # pack shards == writer threads
    serving_layout: Optional[str] = None   # None | "stream" | "fused"
    serving_min_bytes: int = rt_streaming.MIN_STREAM_BYTES
    serving_shards: int = 1
    # serving_layout extension: save (L, E, ...) MoE expert stacks as
    # PER-EXPERT wire records so load_for_serving can restore them into an
    # ExpertStore without inflating cold experts (docs/MOE.md).  Opt-in:
    # trees that never install an expert store keep monolithic records.
    expert_records: bool = False
    codec: Optional[Codec] = None          # default: ambient codec at init
    retry: Optional[RetryPolicy] = None    # default: RetryPolicy()
    _thread: Optional[threading.Thread] = None
    _exc: Optional[BaseException] = None

    def __post_init__(self):
        self.root = Path(self.root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.last_decode_plan = None   # DecodePlan of the latest load
        self.last_restore_report = None   # RestoreReport of the latest load
        self.last_expert_store = None  # ExpertStore of the latest serving load
        if self.retry is None:
            # one policy per manager: its attempt counters aggregate every
            # pack/manifest read and pack write this manager performs
            self.retry = RetryPolicy()
        if self.codec is None:
            # captured once — every save/load of this manager encodes and
            # decodes through ONE codec instance (caches, counters)
            self.codec = current_codec()
        if self.serving_layout is not None and \
                self.serving_layout not in ("stream", "fused"):
            raise ValueError(
                f"serving_layout must be None, 'stream' or 'fused', "
                f"got {self.serving_layout!r}")

    # -- save ------------------------------------------------------------

    def save(self, step: int, tree, *, blocking: bool = False) -> None:
        self.wait()    # also re-raises a previous async failure
        names, leaves, _ = _tree_paths(tree)
        # compression runs device-resident BEFORE any host transfer: only
        # compressed streams (and the raw non-float leaves) ever cross to the
        # host, and repeated (shape, dtype) float leaves share one stacked
        # encode dispatch (docs/PIPELINE.md)
        payload, dense_specs = self._prepare(names, leaves)
        if blocking:
            self._save_host(step, names, payload, dense_specs)
            return
        self._thread = threading.Thread(
            target=self._save_guarded, args=(step, names, payload,
                                             dense_specs),
            daemon=True)
        self._thread.start()

    def _save_guarded(self, step, names, payload, dense_specs):
        try:
            self._save_host(step, names, payload, dense_specs)
        except BaseException as e:  # noqa: BLE001 — surfaced via wait()
            self._exc = e

    def wait(self):
        """Join the in-flight async save.  If it failed, re-raise its
        exception here (and therefore also from the next ``save()``, which
        waits first) — an async save error must never report success."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise CheckpointError(
                f"async checkpoint save failed: {exc}") from exc

    def _prepare(self, names, leaves):
        """Per-leaf record plan:
             ("np",  host_array)            raw host bytes (non-float)
             ("ct",  CompressedTensor)      plain enec/raw/const record
             ("hct", ct, spec, raw_bytes)   stacked serving-layout record
             ("xct", meta, records)         per-expert record group (MoE)
        """
        payload: list = [None] * len(leaves)
        float_slots, other_slots, serve_jobs = [], [], []
        dense_specs: dict = {}   # slot -> handle spec for fallback leaves
        for i, (name, leaf) in enumerate(zip(names, leaves)):
            if is_handle(leaf):
                if isinstance(leaf, rt_experts.ExpertRef):
                    # the store already holds the exact per-expert wire
                    # bodies — re-emit them verbatim, no re-encode
                    payload[i] = ("xct", leaf.store.meta(leaf.name),
                                  leaf.store.records_for(leaf.name))
                    continue
                if isinstance(leaf, DenseWeight):
                    leaf = leaf.w       # stored dense; re-wrapped on restore
                    dense_specs[i] = {"kind": "dense"}
                    leaves[i] = leaf
                else:
                    spec = handle_spec(leaf)
                    raw = _leaf_nbytes(
                        (leaf.ct.streams.mask.shape[0],)
                        + tuple(spec.get("layer_shape")
                                or (spec["k"], spec["n"])), spec["dtype"])
                    payload[i] = ("hct", leaf.ct, spec, raw)
                    continue
            dt = getattr(leaf, "dtype", None)   # dtype check without a copy
            if not (self.compress and dt is not None
                    and jnp.dtype(dt) in _ENEC_DTYPES):
                other_slots.append(i)
                continue
            if self.serving_layout is not None and i not in dense_specs \
                    and name.split("/", 1)[0] not in _NON_SERVING_ROOTS:
                if (self.expert_records
                        and rt_experts.is_expert_leaf(name, leaf)
                        and _leaf_nbytes(leaf.shape, str(jnp.dtype(
                            leaf.dtype))) >= self.serving_min_bytes):
                    enc = rt_experts.encode_expert_leaf(
                        name, jnp.asarray(leaf), self.codec)
                    if enc is not None:
                        meta, records = enc
                        payload[i] = ("xct", meta, records)
                        continue
                    # escape (const / incompressible): monolithic record
                # (slots unwrapped from a DenseWeight stay dense records —
                # the policy that built the tree already decided against
                # compressing them)
                job = rt_streaming.serving_job(name, jnp.asarray(leaf),
                                               self.serving_layout,
                                               self.serving_min_bytes)
                if job is not None:
                    job["slot"] = i
                    serve_jobs.append(job)
                    continue
            float_slots.append(i)
        if other_slots:   # one batched transfer for all uncompressed leaves
            hosts = jax.device_get([leaves[i] for i in other_slots])
            for i, h in zip(other_slots, hosts):
                payload[i] = ("np", np.asarray(h))

        # serving-layout leaves: compress the exact stream bundles the
        # weight-execution policy would build (shared serving_job /
        # build_serving_handle code path), so load_for_serving can
        # deserialize them straight into handles
        if serve_jobs:
            # the policy's own batching and per-job shard widths
            cts = rt_streaming.compress_serving_jobs(
                serve_jobs, self.serving_shards, self.codec)
            for job, ct in zip(serve_jobs, cts):
                i = job["slot"]
                handle = rt_streaming.build_serving_handle(job, ct)
                if is_handle(handle) and not isinstance(handle, DenseWeight):
                    spec = handle_spec(handle)
                    payload[i] = ("hct", handle.ct, spec,
                                  job["leaf"].size * job["leaf"].dtype.itemsize)
                else:
                    # const / incompressible escape: plain dense record,
                    # re-wrapped as DenseWeight by the restore policy
                    if job["matmul_pos"]:
                        dense_specs[i] = {"kind": "dense"}
                    float_slots.append(i)

        # every remaining float leaf rides the batched pipeline as its own
        # L=1 stack: per-leaf searched params (ratio parity with the seed —
        # unrelated same-shape tensors like weights vs Adam moments must NOT
        # share params), no jnp.stack duplicate on device, while statistics,
        # the never-worse wire check, and encode dispatches all stay batched
        # — leaves whose (n, m, L) coincide share one concatenated dispatch
        # via the encoder's dynamic-b bucketing.
        float_slots.sort()
        cts = self.codec.compress_stacked_many(
            [jnp.asarray(leaves[i])[None] for i in float_slots])
        for i, ct in zip(float_slots, cts):
            if ct is None:
                # const / incompressible / empty: per-leaf escape path.
                payload[i] = ("ct",
                              self.codec.compress_array(
                                  jnp.asarray(leaves[i])))
            else:
                payload[i] = ("ct", slice_stacked(ct, 0))
        return payload, dense_specs

    # -- record building / pack writing ----------------------------------

    def _build_record(self, index, name, item, dense_specs):
        """List of (manifest entry sans pack/offset, framed blob, raw
        bytes) — one element for ordinary leaves, one PER EXPERT for an
        ``xct`` record group."""
        tag = item[0]
        if tag == "xct":
            _, meta, records = item
            eshape = [int(s) for s in meta["expert_shape"]]
            per_raw = _leaf_nbytes(eshape, meta["dtype"])
            out = []
            for l, j, body in records:
                entry = {"name": f"{name}::x{l:04d}.{j:04d}",
                         "index": index, "shape": eshape,
                         "dtype": meta["dtype"], "mode": "enec",
                         "handle": {"kind": "expert", "parent": name,
                                    "layer": int(l), "expert": int(j),
                                    "n_layers": int(meta["n_layers"]),
                                    "n_experts": int(meta["n_experts"]),
                                    "expert_shape": eshape,
                                    "dtype": meta["dtype"]},
                         "bytes": len(body)}
                out.append((entry, enec_wire.frame(body), per_raw))
            return out
        if tag == "np":
            leaf = item[1]
            entry = {"name": name, "index": index, "shape": list(leaf.shape),
                     "dtype": str(leaf.dtype), "mode": "npraw"}
            blob = b"RAW0" + leaf.tobytes()
            raw = leaf.nbytes
        elif tag == "ct":
            ct = item[1]
            entry = {"name": name, "index": index, "shape": list(ct.shape),
                     "dtype": ct.dtype_str, "mode": ct.mode}
            if ct.params is not None:
                entry["params"] = list(ct.params.astuple())
            blob = enec_wire.to_wire(ct)   # moves compressed bytes only
            raw = ct.nbytes_raw()
        else:   # "hct": stacked serving-layout record
            _, ct, spec, raw = item
            entry = {"name": name, "index": index,
                     "shape": list(ct.shape), "dtype": ct.dtype_str,
                     "mode": ct.mode, "handle": spec,
                     "stack": int(ct.streams.mask.shape[0]),
                     "params": list(ct.params.astuple())}
            blob = enec_wire.to_wire(ct, stacked=True)
        spec = dense_specs.get(index)
        if spec is not None and "handle" not in entry:
            entry["handle"] = spec
        entry["bytes"] = len(blob)
        return [(entry, enec_wire.frame(blob), raw)]

    def _save_host(self, step: int, names, payload, dense_specs) -> None:
        t0 = time.time()
        final = self.root / f"step_{step:012d}"
        tmp = self.root / f".tmp-step_{step:012d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        n_packs = max(1, min(self.writers, len(payload) or 1))
        manifest = {"format": MANIFEST_FORMAT, "step": step,
                    "packs": [f"pack-{i:05d}.bin" for i in range(n_packs)],
                    "leaves": []}
        if self.serving_layout is not None:
            manifest["serving_layout"] = {
                "mode": self.serving_layout,
                "min_bytes": self.serving_min_bytes,
                # requested width; fused records narrow per record via
                # rt_streaming.fused_shards (each record's ct stores its
                # actual shard count)
                "shards": self.serving_shards}
        raw_total = comp_total = 0
        offsets = [0] * n_packs
        # records are serialized by the thread pool and STREAMED round-robin
        # to the pack shards; submission is bounded (a sliding window of
        # in-flight builds), so peak host memory holds a few frames — never
        # the whole checkpoint — even when the filesystem writes slowly
        files = [open(tmp / name, "wb") for name in manifest["packs"]]
        workers = max(self.writers, 1)
        pending: deque = deque()

        def drain_one():
            nonlocal raw_total, comp_total
            i, fut = pending.popleft()
            pack = i % n_packs
            for entry, framed, raw in fut.result():
                entry["pack"] = pack
                entry["offset"] = offsets[pack]
                entry["length"] = len(framed)

                def write_framed(f=files[pack], pos=offsets[pack],
                                 fr=framed, name=manifest["packs"][pack]):
                    # seek back to the record's committed offset on every
                    # attempt, so a retried write after a partial one lays
                    # the frame down exactly once
                    rt_faults.check_write(name)
                    f.seek(pos)
                    f.write(fr)

                self.retry.call(write_framed,
                                describe=manifest["packs"][pack])
                offsets[pack] += len(framed)
                raw_total += raw
                comp_total += entry["bytes"]
                manifest["leaves"].append(entry)

        try:
            with ThreadPoolExecutor(max_workers=workers) as ex:
                for i, (n, it) in enumerate(zip(names, payload)):
                    pending.append((i, ex.submit(
                        self._build_record, i, n, it, dense_specs)))
                    if len(pending) >= 2 * workers:
                        drain_one()
                while pending:
                    drain_one()
            for f in files:
                f.flush()
                os.fsync(f.fileno())
        finally:
            for f in files:
                f.close()

        manifest["raw_bytes"] = raw_total
        manifest["compressed_bytes"] = comp_total
        manifest["ratio"] = raw_total / max(comp_total, 1)
        manifest["save_s"] = round(time.time() - t0, 3)
        # fsync the manifest AND the tmp directory entries BEFORE the
        # rename: otherwise a crash can commit a step directory whose
        # manifest is missing or truncated
        with open(tmp / "manifest.json", "w") as f:
            f.write(json.dumps(manifest, indent=1))
            f.flush()
            os.fsync(f.fileno())
        _fsync_path(tmp)
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)                       # atomic commit
        _fsync_path(self.root)                  # …made durable
        latest_tmp = self.root / ".LATEST.tmp"
        with open(latest_tmp, "w") as f:
            f.write(final.name)
            f.flush()
            os.fsync(f.fileno())
        latest_tmp.rename(self.root / "LATEST")
        _fsync_path(self.root)
        self._gc()

    def _gc(self):
        # never GC by name alone: a step whose manifest does not parse
        # might hold the only intact copy of a record a degraded restore
        # still needs — retention counts and deletes only steps it can
        # actually read
        steps = sorted(p for p in self.root.glob("step_*") if p.is_dir())
        intact = [p for p in steps if self._try_manifest(p) is not None]
        for old in intact[: max(0, len(intact) - self.keep_last)]:
            shutil.rmtree(old, ignore_errors=True)
        # stale tmp dirs from crashed saves would otherwise leak forever
        # (our own tmp has already been renamed away by the time GC runs)
        for stale in self.root.glob(".tmp-step_*"):
            shutil.rmtree(stale, ignore_errors=True)

    # -- load ------------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        """Step named by the LATEST pointer, or None when the pointer is
        missing, unreadable, or garbage (default-step resolution then
        falls back to the newest step directory with an intact manifest
        instead of dying on the pointer file)."""
        ptr = self.root / "LATEST"
        try:
            text = ptr.read_text()
        except OSError:
            return None
        try:
            return int(text.strip().split("_")[-1])
        except ValueError:
            return None

    def manifest(self, step: Optional[int] = None) -> dict:
        """The manifest of ``step`` (default: latest) without reading any
        record bytes — launchers use it to sniff the name prefix and the
        stored serving layout."""
        return self._step_dir(step)[1]

    def _try_manifest(self, cdir) -> Optional[dict]:
        """Parse a step dir's manifest, or None if it is missing, corrupt,
        or unreadable (reads go through the retry policy, so a transient
        error does not misclassify an intact step)."""
        path = cdir / "manifest.json"
        try:
            raw = self.retry.call(lambda: rt_faults.read_file(path),
                                  describe=str(path))
            return json.loads(raw.decode())
        except (OSError, ValueError):
            # ValueError covers JSONDecodeError and UnicodeDecodeError
            return None

    def _step_candidates(self) -> list:
        """Step numbers to try for ``step=None``: LATEST's target first,
        then every committed step directory, newest first."""
        out = []
        s = self.latest_step()
        if s is not None:
            out.append(s)
        for p in sorted(self.root.glob("step_*"), reverse=True):
            if not p.is_dir():
                continue
            try:
                c = int(p.name.split("_")[-1])
            except ValueError:
                continue
            if c not in out:
                out.append(c)
        return out

    def _step_dir(self, step: Optional[int]) -> tuple:
        """Resolve ``(cdir, manifest)``.  An EXPLICIT step must be intact
        (a corrupt manifest raises).  ``step=None`` resolves LATEST and —
        when the pointer dangles or its manifest is corrupt — falls back
        to the newest earlier step whose manifest parses, so one damaged
        file never makes the whole root unrestorable."""
        if step is not None:
            cdir = self.root / f"step_{step:012d}"
            manifest_path = cdir / "manifest.json"
            if not manifest_path.exists():
                raise CheckpointError(f"{cdir} has no manifest.json")
            try:
                return cdir, json.loads(manifest_path.read_text())
            except (json.JSONDecodeError, UnicodeDecodeError) as e:
                raise CheckpointError(
                    f"{manifest_path} is corrupt: {e}") from e
        candidates = self._step_candidates()
        if not candidates:
            raise FileNotFoundError(f"no checkpoint under {self.root}")
        causes = []
        for c in candidates:
            try:
                return self._step_dir(c)
            except CheckpointError as e:
                causes.append(str(e))
        raise CheckpointError(
            "no step with an intact manifest under "
            f"{self.root}: " + "; ".join(causes))

    @staticmethod
    def _require_records(names, by_name, cdir, what="records", groups=None):
        missing = [n for n in names
                   if n not in by_name and not (groups and n in groups)]
        if missing:
            raise CheckpointError(
                f"checkpoint {cdir.name} lacks {what} for {missing[:5]}"
                + ("…" if len(missing) > 5 else ""))

    @staticmethod
    def _expert_groups(manifest) -> dict:
        """parent leaf name -> its per-expert record entries (the
        ``expert_records`` save layout splits one ``(L, E, ...)`` leaf
        into ``L*E`` sub-records named ``{parent}::x{l:04d}.{j:04d}``)."""
        groups: dict = {}
        for e in manifest["leaves"]:
            spec = e.get("handle")
            if spec is not None and spec.get("kind") == "expert":
                groups.setdefault(spec["parent"], []).append(e)
        return groups

    @staticmethod
    def _expand_entries(names, by_name, groups):
        """Record entries to read for ``names``, expert groups inlined."""
        out = []
        for n in names:
            if n in by_name:
                out.append(by_name[n])
            else:
                out.extend(groups[n])
        return out

    @staticmethod
    def _check_leaf(name, shape, like, dtype=None):
        if tuple(shape) != tuple(like.shape):
            raise CheckpointError(f"{name}: ckpt {tuple(shape)} vs model "
                                  f"{tuple(like.shape)}")
        if dtype is not None and dtype != str(jnp.dtype(like.dtype)):
            raise CheckpointError(f"{name}: ckpt dtype {dtype} vs model "
                                  f"{jnp.dtype(like.dtype)}")

    def _quarantine(self, report, e, manifest, cause) -> QuarantinedRecord:
        """Record one failed record on ``report`` with its coordinates."""
        packs = manifest.get("packs")
        pack = (packs[e["pack"]] if packs is not None and "pack" in e
                else f"t_{e.get('index', 0):05d}.enec")
        q = QuarantinedRecord(
            name=e["name"], pack=pack, offset=int(e.get("offset", 0)),
            length=int(e.get("length", e.get("bytes", -1))), cause=cause)
        report.quarantined.append(q)
        return q

    def _iter_records(self, cdir, manifest, entries, report=None):
        """Yield ``(entry, payload_bytes)`` for ``entries``, validated
        (frame length + CRC for v2 packs; declared blob size for v1
        per-leaf files), one record at a time in pack/offset order — the
        caller stages each record to device as it goes, so peak host
        memory holds one record's compressed bytes, never the whole
        checkpoint (decoding is deferred into one batched pass).  Only the
        requested records are read (partial load never touches the rest of
        the pack).

        Every read funnels through the manager's retry policy and the
        fault-injection hooks (runtime/faults.py) — transient I/O errors
        are absorbed here.  With ``report=None`` (strict) the first record
        that still fails raises; with a :class:`RestoreReport` the record
        is quarantined and skipped, and the caller arranges a per-record
        fallback afterwards."""
        fmt = manifest.get("format", "enec-v1")
        if fmt == "enec-v1":
            for e in entries:
                path = cdir / f"t_{e['index']:05d}.enec"
                try:
                    blob = self.retry.call(
                        lambda p=path: rt_faults.read_file(p),
                        describe=path.name)
                    if "bytes" in e and len(blob) != e["bytes"]:
                        raise CheckpointError(
                            f"{path.name}: {len(blob)} bytes on disk, "
                            f"manifest declares {e['bytes']} — truncated "
                            f"or corrupt")
                except OSError as err:
                    if report is None:
                        raise CheckpointError(
                            f"{path.name} ({e['name']}): {err}") from err
                    self._quarantine(report, e, manifest, str(err))
                    continue
                except CheckpointError as err:
                    if report is None:
                        raise
                    self._quarantine(report, e, manifest, str(err))
                    continue
                self.codec.count_link("disk", len(blob),
                                      dense=e.get("mode") == "npraw")
                yield e, blob
            return
        if fmt != MANIFEST_FORMAT:
            raise CheckpointError(f"unknown checkpoint format {fmt!r}")
        by_pack: dict = {}
        for e in entries:
            by_pack.setdefault(e["pack"], []).append(e)
        for pack, es in sorted(by_pack.items()):
            path = cdir / manifest["packs"][pack]
            for e in sorted(es, key=lambda e: e["offset"]):
                try:
                    buf = self.retry.call(
                        lambda e=e: rt_faults.read_range(
                            path, e["offset"], e["length"]),
                        describe=f"{path.name}@{e['offset']}")
                    payload, end = enec_wire.read_frame(
                        buf, record=e["name"], pack=path.name,
                        base_offset=e["offset"])
                    if end != len(buf):
                        raise enec_wire.WireError(
                            f"frame length {end} != indexed {len(buf)}",
                            record=e["name"], pack=path.name,
                            offset=e["offset"])
                except (OSError, enec_wire.WireError) as err:
                    if isinstance(err, enec_wire.WireError):
                        # satellite: both except sites attach (leaf name,
                        # pack file, byte offset) to the WireError
                        err.with_context(record=e["name"], pack=path.name,
                                         offset=e["offset"])
                    if report is None:
                        raise CheckpointError(
                            f"{path.name} @ {e['offset']} ({e['name']}): "
                            f"{err}") from err
                    self._quarantine(report, e, manifest, str(err))
                    continue
                # the disk link of the per-link ledger: compressed record
                # payloads vs raw (npraw) bytes actually read off storage
                self.codec.count_link("disk", len(payload),
                                      dense=e.get("mode") == "npraw")
                yield e, payload

    def _decode_npraw(self, e, blob):
        blob = bytes(blob)
        if blob[:4] != b"RAW0":
            raise CheckpointError(f"corrupt raw blob for {e['name']}")
        arr = np.frombuffer(blob[4:], dtype=np.dtype(e["dtype"]))
        if arr.size != int(np.prod(e["shape"], dtype=np.int64)):
            raise CheckpointError(
                f"{e['name']}: raw payload holds {arr.size} elements, "
                f"manifest declares shape {e['shape']}")
        # counted on this manager's codec like every other record upload —
        # these are DENSE bytes on the h2d link (the raw escape)
        return enec_wire.h2d(arr.reshape(e["shape"]), self.codec,
                             dense=True)

    def _record_ct(self, e, blob, packs=None, stream_place=None):
        """Deserialize one compressed record's payload — the compressed
        streams move to device here (counted on this manager's codec);
        nothing is decoded yet.  ``stream_place`` (a
        ``collectives.stream_placer`` hook) uploads each stream leaf with
        its TP-shard dim on the target mesh axis, so a shard's pack bytes
        reach the owning devices only.  Any :class:`WireError` leaves with
        the record's (leaf name, pack file, byte offset) attached."""
        pack = packs[e["pack"]] if packs is not None and "pack" in e \
            else None
        try:
            return enec_wire.from_wire(blob, codec=self.codec,
                                       record=e["name"], pack=pack,
                                       offset=e.get("offset"),
                                       stream_place=stream_place)
        except enec_wire.WireError as err:
            err.with_context(record=e["name"], pack=pack,
                             offset=e.get("offset"))
            raise CheckpointError(f"{e['name']}: {err}") from err

    def _queue_record(self, e, blob, pending, vals, like, packs=None):
        """One record -> either an eagerly decoded host value (``npraw``)
        or a device-resident compressed object queued on ``pending`` for
        the batched decode pass (serving-layout records become handles;
        plain enec/raw/const records stay CompressedTensors)."""
        name = e["name"]
        if e["mode"] == "npraw":
            val = self._decode_npraw(e, blob)
            self._check_leaf(name, val.shape, like)
            vals[name] = val.astype(like.dtype)
            return
        ct = self._record_ct(e, blob, packs=packs)
        spec = e.get("handle")
        if spec is not None and spec.get("kind") == "expert":
            # one slice of a per-expert record group: queued as a part,
            # reassembled into the dense parent stack after the batched
            # decode (_decode_pending)
            pending.append((name, like, _ExpertPart(spec, ct)))
            return
        obj = (handle_from_spec(e["handle"], ct)
               if "handle" in e and e.get("stack") else ct)
        pending.append((name, like, obj))

    def _decode_pending(self, pending, vals):
        """Decode every queued compressed record in ONE batched pipeline
        pass: records sharing a decoder bucket — serving-layout handle
        records and plain enec records alike — share a concatenated decode
        dispatch (``core.api.decompress_stacked_many``), so restoring a
        model costs O(#buckets) decode dispatches instead of one per
        record.  The decode runs where the streams live (device); outputs
        are bit-identical to the retired per-record path.  The executed
        :class:`repro.core.DecodePlan` is kept on ``last_decode_plan`` so
        callers (benches, CI) can assert the restore cost
        ``len(plan.buckets)`` dispatches."""
        plan = self.codec.plan_decode(
            [obj.ct if (is_handle(obj) or isinstance(obj, _ExpertPart))
             else obj for _, _, obj in pending])
        decs = self.codec.execute(plan)
        # keep only the inspectable summary: the execution-state fields
        # hold the full compressed streams on device and would pin them
        # until the next load
        self.last_decode_plan = dataclasses.replace(
            plan, _treedef=None, _groups=[], _passthrough={}, _leaves=[])
        parents: dict = {}
        for (name, like, obj), dec in zip(pending, decs):
            if isinstance(obj, _ExpertPart):
                g = parents.setdefault(
                    obj.spec["parent"],
                    {"like": like, "spec": obj.spec, "decs": {}})
                g["decs"][(int(obj.spec["layer"]),
                           int(obj.spec["expert"]))] = dec
                continue
            val = finish_materialize(obj, dec) if is_handle(obj) else dec
            self._check_leaf(name, val.shape, like)
            vals[name] = val.astype(like.dtype)
        for parent, g in parents.items():
            sp = g["spec"]
            nl, ne = int(sp["n_layers"]), int(sp["n_experts"])
            eshape = tuple(int(s) for s in sp["expert_shape"])
            self._check_leaf(parent, (nl, ne) + eshape, g["like"])
            buf = np.empty((nl, ne) + eshape, jnp.dtype(sp["dtype"]))
            for l in range(nl):
                for j in range(ne):
                    dec = g["decs"].get((l, j))
                    if dec is None:
                        raise CheckpointError(
                            f"{parent}: expert record grid incomplete — "
                            f"missing layer {l} expert {j}")
                    buf[l, j] = np.asarray(dec)
            vals[parent] = jnp.asarray(buf).astype(g["like"].dtype)

    def _apply_decode_faults(self, pending, manifest, by_name, report):
        """Fault-injection hook for the decode dispatch: records matched
        by an active "decode" fault are dropped from the batched plan
        BEFORE it is built — quarantined (degraded) or fatal (strict) —
        so the surviving records still decode in one replanned batched
        pass.  No-op without an active injector."""
        if rt_faults.active() is None:
            return pending
        out = []
        for item in pending:
            name = item[0]
            try:
                rt_faults.check_decode(name)
            except rt_faults.InjectedFault as err:
                if report is None:
                    raise CheckpointError(
                        f"decode failed for {name}: {err}") from err
                self._quarantine(report, by_name.get(name, {"name": name}),
                                 manifest, f"decode failed: {err}")
                continue
            out.append(item)
        return out

    def _intact_steps(self, before: Optional[int] = None) -> list:
        """``(step, cdir, manifest)`` for every committed step whose
        manifest parses, newest first; ``before`` excludes that step and
        anything newer (fallback never reads forward in time)."""
        out = []
        for p in sorted(self.root.glob("step_*"), reverse=True):
            if not p.is_dir():
                continue
            try:
                s = int(p.name.split("_")[-1])
            except ValueError:
                continue
            if before is not None and s >= before:
                continue
            man = self._try_manifest(p)
            if man is not None:
                out.append((s, p, man))
        return out

    def _fallback_restore(self, report, manifest, like_by_name, vals,
                          pending, process=None):
        """Per-record fallback for quarantined records: walk earlier steps
        (newest first, intact manifests only) and restore the first intact
        copy of each record — read, validated, shape/dtype-checked, and
        decode-fault-checked exactly like a first-class record, so an
        injected decode failure cannot sneak back in through the fallback.
        ``process`` overrides how a recovered record is staged (the
        serving restore passes its adopt-or-queue closure).  A record with
        no intact source anywhere raises: a degraded restore never
        fabricates weights."""
        steps = self._intact_steps(before=manifest.get("step"))
        for q in report.quarantined:
            if q.fallback or q.name not in like_by_name:
                continue
            like = like_by_name[q.name]
            for s, fcdir, fman in steps:
                fe = next((e for e in fman["leaves"]
                           if e["name"] == q.name), None)
                if fe is None:
                    continue
                n_pend = len(pending)
                try:
                    got = False
                    for e2, payload in self._iter_records(fcdir, fman,
                                                          [fe]):
                        if process is not None:
                            process(e2, payload, like, fman, pending, vals)
                        else:
                            self._queue_record(e2, payload, pending, vals,
                                               like,
                                               packs=fman.get("packs"))
                        got = True
                    if not got:
                        raise CheckpointError(
                            f"{q.name}: record unreadable at step {s}")
                    new = pending[n_pend:]
                    if new:
                        pending[n_pend:] = self._apply_decode_faults(
                            new, fman, {q.name: fe}, None)
                except (OSError, CheckpointError,
                        enec_wire.WireError):
                    # this step can't supply the record — roll back any
                    # partial staging and walk further back in time
                    del pending[n_pend:]
                    vals.pop(q.name, None)
                    continue
                kind = ((fe.get("handle") or {}).get("kind")
                        or fe.get("mode", "?"))
                q.fallback = f"step {s} ({kind} record)"
                break
            if not q.fallback:
                raise CheckpointError(
                    "restore failed — no intact source for quarantined "
                    "record(s):\n" + report.summary())

    def _begin_report(self, policy, manifest) -> RestoreReport:
        if policy not in RESTORE_POLICIES:
            raise ValueError(f"unknown restore policy {policy!r}; "
                             f"expected one of {RESTORE_POLICIES}")
        return RestoreReport(step=int(manifest.get("step", -1)),
                             policy=policy)

    def _finish_report(self, report) -> None:
        report.retry = self.retry.stats()
        self.last_restore_report = report

    def load(self, like_tree, step: Optional[int] = None,
             shardings=None, *, policy: str = "strict"):
        """Restore into the structure of ``like_tree``; reshard to
        ``shardings`` (elastic: any mesh) or keep host arrays.

        ``policy="strict"`` (default) aborts on the first bad record —
        bit-exactness or nothing, the right contract for training resume.
        ``policy="degraded"`` quarantines records that fail I/O,
        validation, or decode and falls back per record to the newest
        earlier step holding an intact copy; the :class:`RestoreReport`
        (returned on ``last_restore_report``) enumerates every quarantined
        record with cause and fallback.  A record with no intact source
        anywhere still raises — degraded mode trades freshness, never
        correctness."""
        cdir, manifest = self._step_dir(step)
        report = self._begin_report(policy, manifest)
        rep = report if policy == "degraded" else None
        names, leaves, treedef = _tree_paths(like_tree)
        by_name = {e["name"]: e for e in manifest["leaves"]}
        groups = self._expert_groups(manifest)
        self._require_records(names, by_name, cdir, groups=groups)
        like_by_name = dict(zip(names, leaves))
        for parent in groups:
            if parent in like_by_name:
                # sub-records validate (and fall back) against the parent
                for e in groups[parent]:
                    like_by_name[e["name"]] = like_by_name[parent]
        packs = manifest.get("packs")
        vals = {}
        pending: list = []
        for e, payload in self._iter_records(
                cdir, manifest, self._expand_entries(names, by_name, groups),
                report=rep):
            try:
                self._queue_record(e, payload, pending, vals,
                                   like_by_name[e["name"]], packs=packs)
            except CheckpointError as err:
                if rep is None:
                    raise
                self._quarantine(rep, e, manifest, str(err))
        pending = self._apply_decode_faults(pending, manifest, by_name, rep)
        if rep is not None and rep.quarantined:
            self._fallback_restore(rep, manifest, like_by_name, vals,
                                   pending)
        self._decode_pending(pending, vals)
        self._finish_report(report)
        tree = jax.tree_util.tree_unflatten(treedef,
                                            [vals.pop(n) for n in names])
        if shardings is not None:
            tree = jax.device_put(tree, shardings)
        return tree, manifest

    # -- restore straight into serving handles ----------------------------

    @staticmethod
    def _spec_serves_mode(spec: dict, mode: str,
                          degraded: bool = False) -> bool:
        """Can a stored serving-layout record be adopted as-is under the
        requested weight-execution mode?

        ``degraded`` relaxes the answer for a quarantined record's
        fallback copy: any compressed handle kind executes the canonical
        contraction bit-identically, so a damaged fused bundle may adopt a
        prior step's stream record (and vice versa) — a capacity/latency
        downgrade, never a numeric one.  The main pass keeps the strict
        answer: asking for fused on an undamaged stream-layout checkpoint
        should re-layout for fused speed, not silently keep stream
        execution."""
        kind = spec.get("kind")
        if mode == "fused":
            return kind == "fused" or (
                kind == "stream"
                and (degraded
                     or spec.get("execution", "materialize")
                     == "materialize"))
        if mode == "stream":
            return kind == "stream" or (degraded and kind == "fused")
        return False

    def load_for_serving(self, like_params, *, mode: str = "fused",
                         step: Optional[int] = None, prefix: str = "",
                         min_bytes: int = rt_streaming.MIN_STREAM_BYTES,
                         shards: int = rt_streaming.STREAM_SHARDS,
                         policy: str = "strict", mesh=None,
                         expert_store=None):
        """Restore ONLY the weight records into a serving handle tree.

        ``like_params`` is the (dense) params structure — ShapeDtypeStructs
        are fine, nothing is allocated from it.  ``prefix`` namespaces the
        record names ("params" when the checkpoint was saved as
        ``{"params": ..., "opt": ...}``); optimizer records are never read.

        Records stored in a matching serving layout deserialize DIRECTLY
        into ``StreamedWeight`` / ``FusedWeight`` handles — disk -> HBM with
        no dense tensor on the host (``wire.transfer_stats()`` proves it).
        Everything else (plain v1/v2 records, or a layout mismatch) is
        decompressed on device and handed to ``assign_weight_modes``, which
        passes existing handles through untouched.

        ``policy="degraded"`` keeps serving through damage: a record that
        fails I/O, validation, or decode is quarantined and restored from
        the newest earlier step holding an intact copy — adopted as a
        handle when its layout serves ``mode`` (a damaged fused bundle
        degrades to the prior step's stream or dense record), decoded and
        re-assigned by the policy otherwise.  The rest of the tree restores
        batched exactly as before (the DecodePlan replans only the
        surviving buckets); logits stay bit-identical because every handle
        mode executes the same canonical contraction.  The
        :class:`RestoreReport` on ``last_restore_report`` enumerates each
        quarantined record's cause and fallback.

        ``mesh`` restores straight onto a serving mesh: adopted records'
        stream shards upload to their OWNING devices only (the per-shard
        pack bytes never fan out over h2d — ``collectives.stream_placer``),
        and the finished tree is placed per ``collectives.serving_pspecs``
        (stream shards on the "model" axis, dense math replicated).

        Checkpoints saved with ``expert_records=True`` hold MoE expert
        stacks as per-expert wire records: those restore straight into an
        :class:`~repro.runtime.experts.ExpertStore` (``expert_store``, or
        a fresh unbounded one — also stashed on ``last_expert_store``)
        WITHOUT inflating or uploading a single cold expert; the tree gets
        an ``ExpertRef`` handle per stack and routed experts decode
        on demand through the store's LRU cache (docs/MOE.md)."""
        if mode not in rt_streaming.WEIGHT_MODES:
            raise ValueError(f"unknown weight mode {mode!r}")
        from repro.runtime import collectives as rt_collectives
        stream_place = (None if mesh is None
                        else rt_collectives.stream_placer(mesh))
        cdir, manifest = self._step_dir(step)
        report = self._begin_report(policy, manifest)
        rep = report if policy == "degraded" else None
        names, leaves, treedef = _tree_paths(like_params)
        full = [f"{prefix}/{n}" if prefix else n for n in names]
        by_name = {e["name"]: e for e in manifest["leaves"]}
        groups = {p: es for p, es in self._expert_groups(manifest).items()
                  if p in set(full)}
        if groups and mesh is not None:
            raise CheckpointError(
                "expert-record checkpoints cannot restore onto a serving "
                "mesh yet: the expert store decodes host-side per step "
                "(see docs/MOE.md) — load with mesh=None")
        est = expert_store
        if est is None and groups:
            est = rt_experts.ExpertStore(codec=self.codec)
        self.last_expert_store = est if groups else None
        self._require_records(full, by_name, cdir, what="weight records",
                              groups=groups)
        like_by_name = dict(zip(full, leaves))
        for parent, es in groups.items():
            for e in es:
                like_by_name[e["name"]] = like_by_name[parent]
        vals = {}
        pending: list = []

        def serve_record(e, payload, like, man, pending, vals):
            """Adopt a matching serving-layout record as a handle, else
            queue it for the batched decode — shared by the main pass and
            the per-record step fallback so a recovered record takes
            exactly the path it would have taken undamaged."""
            name = e["name"]
            spec = e.get("handle")
            if spec is not None and spec.get("kind") == "expert":
                # per-expert record: the compressed bytes go STRAIGHT into
                # the expert store — no upload, no decode; cold experts
                # stay wire records until routing asks for them
                est.add_meta(spec["parent"],
                             n_layers=spec["n_layers"],
                             n_experts=spec["n_experts"],
                             expert_shape=spec["expert_shape"],
                             dtype=spec["dtype"])
                est.add_record(spec["parent"], spec["layer"],
                               spec["expert"], bytes(payload))
                return
            # a record arriving here while already quarantined is the
            # FALLBACK copy from an earlier step — adoption relaxes to any
            # bit-identical handle kind (see _spec_serves_mode)
            is_fallback = rep is not None and any(
                q.name == name for q in rep.quarantined)
            if spec and spec["kind"] != "dense" and e.get("stack") \
                    and mode != "dense" \
                    and self._spec_serves_mode(spec, mode,
                                               degraded=is_fallback):
                if spec["kind"] == "stream":
                    # flat records are L=1 stacks of plain 2-D leaves:
                    # layer_shape IS the leaf shape (no stack prefix)
                    leaf_shape = tuple(spec["layer_shape"]) \
                        if spec.get("flat") \
                        else (int(e["stack"]),) + tuple(spec["layer_shape"])
                else:
                    leaf_shape = (int(e["stack"]),
                                  int(spec["k"]), int(spec["n"]))
                self._check_leaf(name, leaf_shape, like,
                                 dtype=spec["dtype"])
                ct = self._record_ct(e, payload, packs=man.get("packs"),
                                     stream_place=stream_place)
                # adopt only when the stored stream layout matches the
                # width assign_weight_modes would pick for this record —
                # fused tile streams narrow per record when the tile-block
                # count doesn't divide; a mismatch joins the batched
                # decode + device re-layout below instead of silently
                # keeping the ckpt's sharding
                req_shards = (rt_streaming.fused_shards(
                    int(spec["k"]), int(spec["n"]), shards)
                    if spec["kind"] == "fused" else shards)
                # a fallback copy adopts at whatever width the older step
                # stored — any width executes bit-identically, and the
                # damaged record must not lose its handle to a re-layout
                if ct.shards == req_shards or is_fallback:
                    vals[name] = handle_from_spec(spec, ct)
                    return
                pending.append((name, like, handle_from_spec(spec, ct)))
                return
            self._queue_record(e, payload, pending, vals, like,
                               packs=man.get("packs"))

        for e, payload in self._iter_records(
                cdir, manifest, self._expand_entries(full, by_name, groups),
                report=rep):
            try:
                serve_record(e, payload, like_by_name[e["name"]], manifest,
                             pending, vals)
            except CheckpointError as err:
                if rep is None:
                    raise
                self._quarantine(rep, e, manifest, str(err))
        pending = self._apply_decode_faults(pending, manifest, by_name, rep)
        if rep is not None and rep.quarantined:
            self._fallback_restore(rep, manifest, like_by_name, vals,
                                   pending, process=serve_record)
        self._decode_pending(pending, vals)
        for parent in groups:
            m = est.meta(parent)
            self._check_leaf(
                parent,
                (m["n_layers"], m["n_experts"]) + tuple(m["expert_shape"]),
                like_by_name[parent], dtype=m["dtype"])
            miss = est.missing(parent)
            if miss:
                raise CheckpointError(
                    f"{parent}: expert record grid incomplete — missing "
                    f"{miss[:5]}" + ("…" if len(miss) > 5 else ""))
            vals[parent] = est.ref(parent)
        self._finish_report(report)
        tree = jax.tree_util.tree_unflatten(treedef,
                                            [vals.pop(n) for n in full])
        tree = rt_streaming.assign_weight_modes(
            tree, mode=mode, min_bytes=min_bytes, shards=shards,
            codec=self.codec)
        if mesh is not None:
            # records re-laid-out by the policy (and every replicated
            # upload) land on their final serving placement: stream shards
            # on the "model" axis, everything else replicated
            tree = rt_collectives.place_serving_tree(tree, mesh)
        return tree, manifest
