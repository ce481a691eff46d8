"""Production serving launcher: continuous-batching generation behind the
weight-execution policy (paper §VI-C + the fused decode path of DESIGN.md
§8), driven by the resilient engine of ``runtime/engine.py``.

Modes (runtime/streaming.py, docs/SERVING.md):
  dense   raw weights, canonical tiled matmul executor (baseline)
  stream  ENEC streams decompressed layer-by-layer inside the step
  fused   ENEC tile streams decompressed inside the matmul kernel itself
          (default — the high-throughput decode route)

All three produce bit-identical logits; they differ only in where weight
bytes live and when they decompress.

Serving (docs/TRAFFIC.md): every run goes through the continuous-batching
engine — ``--batch N`` submits N requests into a bounded admission queue
(``--queue-depth``), they join a ``--concurrency``-slot KV ring at token
granularity, and ``--deadline-ms`` attaches a total per-request deadline
(expired work is shed before prefill or evicted at step granularity).
The one-shot path of earlier PRs is just an engine run whose requests all
arrive at t=0; logits are bit-identical to the old loop.

Checkpoints (docs/CHECKPOINT.md): ``--ckpt DIR`` restores weights through
``CheckpointManager.load_for_serving`` — compressed records flow disk->HBM
and deserialize straight into weight handles; the dense model never exists
on the host.  ``--save-ckpt DIR`` writes an enec-v2 checkpoint (in the
serving layout of the active mode) and continues serving, so a smoke cycle
can produce and consume its own checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --batch 4 --tokens 8 --mode fused --save-ckpt /tmp/ck
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --batch 4 --tokens 8 --mode fused --ckpt /tmp/ck

Programmatic use: ``main(argv)`` takes the CLI's arguments and returns a
:class:`ServeResult` (the requests with their states, tokens and, with
``--collect-logits``, logits), so callers such as ``chip_smoke.py`` check
what was served through this exact code path.  ``--warmup`` serves the
batch once untimed first; ``--verify-codec`` decodes every compressed leaf
and compares it to the initialised weights.  The codec runs the Pallas
kernels on a TPU and the jnp reference elsewhere unless
``--codec-backend`` says otherwise.

Reliability (docs/RELIABILITY.md): restores run with record quarantine and
per-record fallback.  ``--degraded`` (default) serves with the fallback
handles and prints the RestoreReport; ``--strict`` exits nonzero with the
full quarantine list.  :data:`HEALTH` exposes the readiness state
(initializing/restoring/ready/degraded/draining/stopped/failed) for
probes; it is an engine-owned, thread-safe
:class:`repro.runtime.engine.ServerHealth` and is reset at every
``main()`` entry so embedded back-to-back runs never inherit a stale
state from an earlier exception.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.codec_api import Codec
from repro.models import build_model
from repro.runtime.engine import PHASES, Engine, EngineConfig, ServerHealth
from repro.runtime.streaming import assign_weight_modes, mode_mix, \
    stream_stats

# module-level so smoke tests and embedding code can probe the last run's
# health without threading it through main().  The class lives in
# runtime/engine.py now (engine-owned, thread-safe transitions); this
# instance is the launcher's alias — main() resets it at entry and hands
# it to the Engine, which owns every later transition.
HEALTH = ServerHealth()


def _link_line(tag, codec):
    """One-line per-link transfer ledger (docs/DISTRIBUTED.md): which links
    moved bytes and whether any of them carried DENSE weights (a sharded
    serve should show dense traffic on no link but the npraw h2d escape)."""
    live = {k: v for k, v in codec.link_stats().items() if v["ops"]}
    if not live:
        return f"[launch.serve] {tag} links: none"
    parts = []
    for k, v in live.items():
        s = f"{k}:{v['compressed_bytes'] / 1e6:.1f}MB"
        if v["dense_bytes"]:
            s += f"+{v['dense_bytes'] / 1e6:.1f}MB-dense"
        parts.append(s)
    return f"[launch.serve] {tag} links: " + " ".join(parts)


def _restore_params(args, model, mode, codec, policy, mesh=None,
                    expert_store=None):
    """--ckpt: weights come from the checkpoint, never from init.  The
    launcher's explicit codec owns the restore: its transfer counter and
    decoder cache stats are what gets reported.

    The restore always runs under ``policy="degraded"`` so the FULL
    quarantine list is collected in one pass; main() then decides between
    serving degraded and exiting nonzero (--strict).  Returns
    ``(params, RestoreReport)``."""
    from repro.checkpoint.ckpt import CheckpointManager

    mgr = CheckpointManager(args.ckpt, codec=codec)
    manifest = mgr.manifest()
    names = {e["name"] for e in manifest["leaves"]}
    # train-loop checkpoints are saved as {"params": ..., "opt": ...};
    # serving checkpoints hold the params tree at the root
    prefix = "params" if any(n.startswith("params/") for n in names) else ""
    like = jax.eval_shape(model.init, jax.random.key(0))
    codec.reset_transfer_stats()
    codec.reset_decode_cache_stats()
    t0 = time.perf_counter()
    params, _ = mgr.load_for_serving(like, mode=mode, prefix=prefix,
                                     min_bytes=args.min_bytes,
                                     shards=args.shards, policy="degraded",
                                     mesh=mesh, expert_store=expert_store)
    jax.block_until_ready(jax.tree.leaves(params))
    dt = time.perf_counter() - t0
    ts = codec.transfer_stats()
    dst = codec.decode_cache_stats()
    report = mgr.last_restore_report
    rs = report.retry if report is not None else {}
    print(f"[launch.serve] restored step {manifest['step']} from "
          f"{args.ckpt} in {dt:.2f}s "
          f"(h2d {ts['h2d_bytes'] / 1e6:.1f} MB compressed, "
          f"ratio {manifest.get('ratio', 0):.3f}x, "
          f"{dst['dispatches']} decode dispatches, "
          f"io retries {rs.get('retries', 0)}/"
          f"{rs.get('attempts', 0)} attempts)")
    print(_link_line("restore", codec))
    return params, report


@dataclasses.dataclass
class ServeResult:
    """What one :func:`main` run served: the requests (state, tokens and,
    with ``--collect-logits``, per-token logits), the engine that served
    them (kept so a caller can inspect its compiled step), and the
    launcher's measurements."""
    requests: list
    engine: Engine
    stats: dict               # engine.stats()["engine"]
    wall_s: float             # the served batch, warm-up excluded
    tok_s: float
    warmup_s: Optional[float]  # --warmup: the untimed, compiling pass
    weight_stats: dict        # stream_stats(params)
    verify: Optional[dict]    # --verify-codec: leaves checked / mismatched

    def all_done(self) -> bool:
        return all(r.state == "done" for r in self.requests)


def _verify_codec(dense, tree, codec) -> dict:
    """Decode every compressed leaf of ``tree`` with ``codec`` and compare
    its bits to the leaf of ``dense`` it was built from.  One leaf at a
    time, so the check holds at most one extra dense leaf on the device."""
    from repro.runtime.weights import (FusedWeight, StreamedWeight,
                                       is_handle, materialize_full)

    def bits(a):
        return jax.lax.bitcast_convert_type(
            a, {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize])

    # one fused comparison: no bit-view copies of two full leaves
    same_bits = jax.jit(lambda a, b: jnp.array_equal(bits(a), bits(b)))
    checked = mismatched = 0
    for orig, leaf in zip(jax.tree.leaves(dense),
                          jax.tree.leaves(tree, is_leaf=is_handle)):
        if not isinstance(leaf, (StreamedWeight, FusedWeight)):
            continue
        got = materialize_full(leaf, codec)
        same = got.shape == orig.shape and bool(same_bits(got, orig))
        checked += 1
        mismatched += not same
        del got
    return {"leaves": checked, "mismatched": mismatched}


def main(argv=None) -> ServeResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve only the first N layers of --arch, at its "
                         "full width (a model cut to fit a time or memory "
                         "budget)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--mode", default=None,
                    choices=("dense", "stream", "fused"),
                    help="weight-execution mode (docs/SERVING.md); "
                         "default fused")
    ap.add_argument("--dense", action="store_true",
                    help="deprecated alias for --mode dense")
    ap.add_argument("--overlap", default="auto",
                    choices=("off", "on", "auto"),
                    help="decode-prefetch pipeline for streamed weights "
                         "(docs/SERVING.md): decode layer l+1 while layer l "
                         "computes; auto enables it whenever streamed "
                         "leaves are present; logits are bit-identical "
                         "either way")
    ap.add_argument("--min-bytes", type=int, default=4096,
                    help="smallest leaf worth compressing")
    ap.add_argument("--expert-cache-mb", type=float, default=None,
                    metavar="MB",
                    help="MoE expert streaming (docs/MOE.md): keep expert "
                         "stacks as per-expert compressed records and "
                         "decode routed experts through a byte-budgeted "
                         "LRU cache of this many MB (0 caches nothing; "
                         "only MoE arches have eligible leaves)")
    ap.add_argument("--shards", type=int, default=None,
                    help="stream-mode TP shard count for the block dim "
                         "(default: the serving mesh's model-axis width "
                         "under --tp/--mesh, else 1)")
    ap.add_argument("--tp", type=int, default=1,
                    help="model-axis width of the serving mesh "
                         "(docs/DISTRIBUTED.md): stream shards live "
                         "distributed over this axis and are gathered as "
                         "compressed bytes at consumption; must divide "
                         "the device count; 1 = single-device layout")
    ap.add_argument("--mesh", action="store_true",
                    help="build a (data, model) serving mesh with the "
                         "largest model axis the local device count "
                         "divides by (shorthand for --tp <max divisor>)")
    ap.add_argument("--codec-backend", default=None,
                    choices=("reference", "pallas"),
                    help="encode/decode backend of the launcher's Codec "
                         "instance (docs/API.md); default pallas on a TPU, "
                         "reference elsewhere")
    ap.add_argument("--warmup", action="store_true",
                    help="serve the same batch once untimed first, so "
                         "every prefill/decode shape is compiled before "
                         "the timed run")
    ap.add_argument("--collect-logits", action="store_true",
                    help="keep every request's per-token logits on the "
                         "returned requests")
    ap.add_argument("--verify-codec", action="store_true",
                    help="decode every compressed leaf and check it is "
                         "bit-identical to the weights it was built from")
    ap.add_argument("--ckpt", default=None, metavar="DIR",
                    help="restore weights from an ENEC checkpoint via "
                         "load_for_serving (docs/CHECKPOINT.md)")
    ap.add_argument("--save-ckpt", default=None, metavar="DIR",
                    help="write an enec-v2 serving-layout checkpoint of "
                         "the initialized weights, then serve")
    ap.add_argument("--concurrency", type=int, default=None,
                    help="KV slot-ring size of the serving engine "
                         "(docs/TRAFFIC.md): how many requests decode "
                         "together; default = --batch")
    ap.add_argument("--queue-depth", type=int, default=16,
                    help="bounded admission queue depth; offers beyond it "
                         "are rejected with queue_full (docs/TRAFFIC.md)")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="total per-request deadline in ms (0 = none): "
                         "expired queued work is shed before prefill, "
                         "in-flight work past it is evicted at step "
                         "granularity (docs/TRAFFIC.md)")
    pol = ap.add_mutually_exclusive_group()
    pol.add_argument("--strict", action="store_true",
                     help="refuse a damaged restore: exit nonzero with the "
                          "full quarantine list instead of serving "
                          "fallback handles (docs/RELIABILITY.md)")
    pol.add_argument("--degraded", action="store_true",
                     help="serve through damage with per-record fallbacks "
                          "and print the RestoreReport (default)")
    args = ap.parse_args(argv)
    if args.dense and args.mode not in (None, "dense"):
        ap.error("--dense conflicts with --mode " + args.mode)
    if args.ckpt and args.save_ckpt:
        ap.error("--ckpt and --save-ckpt are mutually exclusive "
                 "(restored weights are already checkpointed)")
    if args.verify_codec and args.ckpt:
        ap.error("--verify-codec checks freshly initialised weights; a "
                 "restore is verified by its record CRCs")
    mode = "dense" if args.dense else (args.mode or "fused")
    policy = "strict" if args.strict else "degraded"
    if args.expert_cache_mb is not None and (args.mesh or args.tp > 1):
        ap.error("--expert-cache-mb does not compose with --mesh/--tp yet: "
                 "the expert store decodes host-side per step (docs/MOE.md)")
    HEALTH.reset()   # embedded back-to-back runs never inherit stale state
    verify = None

    mesh = None
    if args.mesh or args.tp > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model="max" if args.mesh and args.tp <= 1
                              else args.tp)
        print(f"[launch.serve] serving mesh axes {dict(mesh.shape)}")
    if args.shards is None:
        # shard width follows the mesh so the stream shards actually land
        # one-per-device (an explicit --shards may still over/under-shard);
        # one device keeps one shard, so a layer's streams need no
        # relayout before they decode
        args.shards = mesh.shape["model"] if mesh is not None else 1

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = dataclasses.replace(cfg, scan_layers=True, overlap=args.overlap,
                              n_layers=args.layers or cfg.n_layers)
    model = build_model(cfg)
    # one explicit Codec instance owns this server's compression state —
    # caches, cache stats, and the h2d transfer counter are all scoped to
    # it, so a second model in the same process cannot perturb them
    backend = args.codec_backend or (
        "pallas" if jax.default_backend() == "tpu" else "reference")
    codec = Codec(encode_backend=backend, decode_backend=backend)
    expert_store = None
    if args.expert_cache_mb is not None:
        # 0 MB is a legal budget: every routed expert is a miss and is
        # dropped right after the step (the worst-case decode cost probe)
        from repro.runtime.experts import ExpertStore
        expert_store = ExpertStore(
            budget_bytes=int(args.expert_cache_mb * 2**20), codec=codec)
    if args.ckpt:
        from repro.checkpoint.ckpt import CheckpointError
        HEALTH.transition("restoring")
        try:
            params, report = _restore_params(args, model, mode, codec,
                                             policy, mesh=mesh,
                                             expert_store=expert_store)
        except (CheckpointError, FileNotFoundError) as e:
            HEALTH.transition("failed", str(e))
            print(f"[launch.serve] restore FAILED: {e}")
            raise SystemExit(1)
        if report is not None and report.degraded:
            print("[launch.serve]", report.summary())
            if policy == "strict":
                HEALTH.transition(
                    "failed", f"{len(report.quarantined)} quarantined "
                              f"record(s) under --strict")
                print(f"[launch.serve] --strict: refusing to serve with "
                      f"{len(report.quarantined)} quarantined record(s); "
                      f"exiting nonzero")
                raise SystemExit(1)
            HEALTH.transition(
                "degraded",
                f"{len(report.quarantined)} record(s) on fallback")
        else:
            HEALTH.transition("ready")
    else:
        t_setup = time.perf_counter()
        params = model.init(jax.random.key(0))
        dense = params if args.verify_codec else None
        if expert_store is not None:
            # BEFORE assign_weight_modes: expert stacks become ExpertRef
            # handles and the mode assignment passes them through
            from repro.runtime.experts import install_expert_store
            params, _ = install_expert_store(params, store=expert_store,
                                             min_bytes=args.min_bytes)
        params = assign_weight_modes(params, mode=mode,
                                     min_bytes=args.min_bytes,
                                     shards=args.shards, codec=codec)
        jax.block_until_ready(jax.tree.leaves(params))
        print(f"[launch.serve] weights initialised and assigned ({mode}) "
              f"in {time.perf_counter() - t_setup:.2f}s")
        if dense is not None:
            t0 = time.perf_counter()
            verify = _verify_codec(dense, params, codec)
            verify["seconds"] = time.perf_counter() - t0
            del dense
            print(f"[launch.serve] codec round trip ({backend}): "
                  f"{verify['leaves']} compressed leaves, "
                  f"{verify['mismatched']} mismatched")
        if mesh is not None:
            from repro.runtime.collectives import place_serving_tree
            params = place_serving_tree(params, mesh)
        if args.save_ckpt:
            # the handle tree is saved directly (its stream bundles become
            # the records), so the weights are compressed exactly once
            from repro.checkpoint.ckpt import CheckpointManager
            mgr = CheckpointManager(
                args.save_ckpt,
                serving_layout=None if mode == "dense" else mode,
                serving_min_bytes=args.min_bytes,
                serving_shards=args.shards,
                expert_records=expert_store is not None,
                codec=codec)
            t0 = time.perf_counter()
            mgr.save(0, {"params": params}, blocking=True)
            print(f"[launch.serve] saved serving checkpoint to "
                  f"{args.save_ckpt} in {time.perf_counter() - t0:.2f}s")
        HEALTH.transition("ready")
    print(f"[launch.serve] health={HEALTH.state} ready={HEALTH.ready()} "
          f"policy={policy} mode_mix={mode_mix(params)}")
    weight_stats = stream_stats(params)
    print(f"[launch.serve] mode={mode} overlap={args.overlap} "
          f"codec={backend}:", weight_stats)

    # ---- engine-driven serving (docs/TRAFFIC.md) -------------------------
    # The Engine traces every jit dispatch under the launcher's codec (its
    # compile caches, not the process default's) and, under a serving
    # mesh, gathers compressed shards at each handle consumption point.
    extra_ctx = None
    if mesh is not None:
        from repro.runtime.collectives import use_serving_mesh
        extra_ctx = lambda: use_serving_mesh(mesh)   # noqa: E731
    slots = args.concurrency if args.concurrency else args.batch
    ecfg = EngineConfig(
        max_slots=max(1, slots),
        queue_depth=max(args.queue_depth, args.batch),
        max_prompt_len=args.prompt_len,
        max_new_tokens=args.tokens,
        default_deadline_s=args.deadline_ms / 1e3 if args.deadline_ms
        else None,
        collect_logits=args.collect_logits)
    engine = Engine(model, params, ecfg, codec=codec, health=HEALTH,
                    extra_context=extra_ctx, expert_store=expert_store)
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size), np.int32)

    def serve_batch(tag):
        reqs = [engine.submit(prompts[i], args.tokens, name=f"{tag}{i}")
                for i in range(args.batch)]
        engine.run_until_idle()
        return reqs

    warmup_s = None
    if args.warmup:
        t0 = time.perf_counter()
        serve_batch("warmup")
        warmup_s = time.perf_counter() - t0
        engine.reset_governor()
        print(f"[launch.serve] warm-up batch (compiles included) "
              f"{warmup_s:.2f}s")
    t0 = time.perf_counter()
    reqs = serve_batch("seq")
    wall = time.perf_counter() - t0

    finished = [r for r in reqs if r.state in ("done", "timed_out")]
    n_tok = sum(len(r.tokens) for r in finished)
    ttfts = [r.ttft_s() for r in finished if r.ttft_s() is not None]
    ttft = sum(ttfts) / len(ttfts) if ttfts else 0.0
    if args.tokens > 1:
        tpots = [r.tpot_s() for r in finished if r.tpot_s() is not None]
        tpot = sum(tpots) / len(tpots) if tpots else 0.0
        print(f"[launch.serve] batch={args.batch} TTFT={ttft*1e3:.1f}ms "
              f"TPOT={tpot*1e3:.1f}ms tok/s={n_tok / wall:.1f} mode={mode}")
    else:
        # a single token never enters the decode loop — timing it would
        # divide by ~0 and print inf/garbage tok/s, so report TTFT only
        print(f"[launch.serve] batch={args.batch} TTFT={ttft*1e3:.1f}ms "
              f"(prefill only; --tokens 1 has no decode steps) "
              f"mode={mode}")
    st = engine.stats()["engine"]
    evicted = (st["evicted_deadline"] + st["evicted_fault"]
               + st["evicted_abort"])
    print(f"[launch.serve] engine: slots={ecfg.max_slots} "
          f"steps={st['steps']} prefills={st['prefills']} "
          f"buckets={st['compiled_buckets']} done={st['done']} "
          f"timed_out={st['timed_out']} shed={st['shed']} "
          f"evicted={evicted} rejected={st['rejected']} "
          f"governor={engine.governor.state}")
    waited = 1e3 * st["queue_wait_s"] / max(1, st["admitted"])
    phases = {p: 1e3 * sum(getattr(r, p) for r in engine.step_phases)
              / max(1, len(engine.step_phases)) for p in PHASES}
    print(f"[launch.serve] scheduler: queue_wait={waited:.2f}ms/request "
          f"steps_with_prefill={st['steps_with_prefill']}/{st['steps']} "
          "step phases (mean ms): "
          + " ".join(f"{p}={v:.2f}" for p, v in phases.items()))
    if expert_store is not None:
        es = expert_store.stats()
        dec_ms = (1e3 * sum(engine.step_decode_s)
                  / max(1, len(engine.step_decode_s)))
        budget = ("inf" if es["budget_bytes"] is None
                  else f"{es['budget_bytes'] / 1e6:.2f}MB")
        print(f"[launch.serve] experts: hits={es['hits']} "
              f"misses={es['misses']} evictions={es['evictions']} "
              f"fetches={es['fetches']} buckets={es['fetch_buckets']} "
              f"resident={es['resident_bytes'] / 1e6:.2f}MB/{budget} "
              f"miss-decode={dec_ms:.2f}ms/step")
    print(_link_line("serve", codec))
    if reqs and reqs[0].tokens:
        print("[launch.serve] seq0:", list(reqs[0].tokens))
    engine.shutdown(deadline_s=30.0)
    print(f"[launch.serve] health={HEALTH.state} ({HEALTH.detail})")
    return ServeResult(requests=reqs, engine=engine, stats=st,
                       wall_s=wall, tok_s=n_tok / wall, warmup_s=warmup_s,
                       weight_stats=weight_stats,
                       verify=verify)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    res = main()
    # exit nonzero when the engine had to evict work it had admitted (a
    # step fault or an abort), or the codec round trip found a mismatch
    bad = (res.stats["evicted_fault"] + res.stats["evicted_abort"]
           + (res.verify or {}).get("mismatched", 0))
    raise SystemExit(1 if bad else 0)
