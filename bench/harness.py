"""Serve one cell: build the program's serving stack from its public
pieces, warm every shape the traffic uses, drive the closed loop, and
account the measured window.

The stack is built in the order ``launch/serve.py`` uses: ``build_model``,
``model.init`` (one jitted call from the seed, weights made on the
device), ``assign_weight_modes`` with the configuration's weight format,
then ``Engine``.  The harness then drives ``Engine.submit`` and
``Engine.step`` itself.  Set-up ends once as many requests have been
completed as there are clients (the ring has turned over once on
average), so the start-up burst of prefills is not measured; the window
then runs for the requested seconds.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench.loadgen import ClosedLoop
from bench.window import RequestLog, WindowSummary, summarize

# a ramp that has not completed a request per client by then never will
RAMP_LIMIT_S = 240.0
# a window decode step over this many times the window's median step is
# listed in a result as a stall (the host or the runtime stood still)
STALL_FACTOR = 4.0
STALLS_SHOWN = 20


def arch_config(config: dict):
    """The program's ``ArchConfig`` from a configuration file's published
    keys and its ``serving`` block."""
    from repro.configs.base import ArchConfig

    serving = config["serving"]
    d, heads = config["hidden_size"], config["num_attention_heads"]
    eps = config.get("rms_norm_eps", config.get("layer_norm_eps"))
    return ArchConfig(
        name=config["name"], family=serving["family"],
        n_layers=config["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=config.get("num_key_value_heads", heads),
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        head_dim=config.get("head_dim") or d // heads,
        qk_norm=bool(serving.get("qk_norm", False)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        rope_theta=float(config["rope_theta"]), norm_eps=float(eps),
        scan_layers=True, overlap=serving.get("overlap", "auto"))


@dataclasses.dataclass
class Leaf:
    """One weight leaf as the serving tree holds it (for the work
    functions of ``bench/metrics/work.py``)."""
    path: str
    role: str            # "layer" | "embed" | "head"
    kind: str            # "dense" | "stream" | "fused" | "raw"
    layers: int          # stacked layers (1 for embed/head)
    k: int               # contraction dim (rows of embed)
    n: int               # output dim (width of embed)
    raw_bytes: int       # all layers, as served dense
    device_bytes: int    # all layers, as held on the device


def weight_leaves(tree) -> list:
    import jax

    from repro.runtime.weights import (DenseWeight, FusedWeight,
                                       StreamedWeight, is_handle)
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_handle)
    for path, leaf in flat:
        p = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
        name = p.rsplit("/", 1)[-1]
        role = name if name in ("embed", "head") else "layer"
        if isinstance(leaf, FusedWeight):
            layers = leaf.ct.streams.mask.shape[0]
            item = np.dtype(leaf.dtype_str).itemsize
            out.append(Leaf(p, role, "fused", layers, leaf.k, leaf.n,
                            layers * leaf.k * leaf.n * item,
                            leaf.ct.nbytes_device()))
        elif isinstance(leaf, StreamedWeight):
            layers = leaf.ct.streams.mask.shape[0]
            k, n = leaf.layer_shape[-2], leaf.layer_shape[-1]
            item = np.dtype(leaf.dtype_str).itemsize
            out.append(Leaf(p, role, "stream", layers, k, n,
                            layers * k * n * item, leaf.ct.nbytes_device()))
        elif isinstance(leaf, DenseWeight):
            layers, k, n = leaf.w.shape
            out.append(Leaf(p, role, "dense", layers, k, n,
                            leaf.w.nbytes, leaf.w.nbytes))
        elif role in ("embed", "head") and getattr(leaf, "ndim", 0) == 2:
            out.append(Leaf(p, role, "raw", 1, leaf.shape[0],
                            leaf.shape[1], leaf.nbytes, leaf.nbytes))
    return out


@dataclasses.dataclass
class Run:
    """Everything one run measured; per-layer metrics read it."""
    cell: object
    seed: int
    arch: object
    mode: str
    peaks: dict
    leaves: list
    weight_stats: dict
    compress_s: float
    setup_s: float
    window: WindowSummary
    logs: list
    step_s: list          # engine.step_times_s of the window's decode steps
    decode_rows: list     # per window decode step: requests it advanced
    decode_ctx: list      # per window decode step: summed context lengths
    prefill_lens: list    # prompt lengths prefilled in the window
    prefill_s: list       # first_token_s - admit_s of those prefills
    compiles_in_window: int
    stalls_ms: list       # window decode steps over STALL_FACTOR x median
    hbm_in_use_bytes: int
    memory_peak_bytes: int
    engine_stats: dict    # Engine.stats() at the window's close
    trace: Optional[object] = None   # xplane.TraceSummary of a traced run


class _CompileCounter:
    """Counts programs lowered while ``on`` (a lowering in the measured
    window is a compile or a cache load there)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, _secs, **_kw):
        if self.on and event == self.EVENT:
            self.count += 1


class _Client:
    def __init__(self, idx: int):
        self.idx, self.k = idx, 0
        self.req = None
        self.log: Optional[RequestLog] = None
        self.completed = 0


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def serve_cell(cell, seed: int, seconds: float, *, peaks: dict,
               t_process: float, trace_dir: Optional[Path] = None,
               engine_hook=None) -> tuple:
    """Build, warm, ramp and measure one cell.  Returns ``(run, served)``
    where ``served`` lists ``(prompt, tokens)`` of the requests completed
    in the window, and the program's serving state is already freed.

    ``engine_hook(engine)``, if given, is called on the built engine
    before warm-up (tests plant faults there)."""
    import jax

    from repro.core.codec_api import Codec
    from repro.models import build_model
    from repro.runtime.engine import Engine, EngineConfig
    from repro.runtime.streaming import assign_weight_modes, stream_stats

    counter = _CompileCounter()
    config, traffic = cell.config, cell.traffic
    serving = config["serving"]
    cfg = arch_config(config)
    loop = ClosedLoop(traffic, cfg.vocab_size, seed)
    model = build_model(cfg)
    backend = serving.get("codec", "pallas")
    codec = Codec(encode_backend=backend, decode_backend=backend)

    with _span("bench.init"):
        params = jax.jit(model.init)(jax.random.key(loop.seed))
        jax.block_until_ready(params)
    t0 = time.perf_counter()
    with _span("bench.assign_weight_modes"):
        params = assign_weight_modes(params, mode=serving["weights"],
                                     min_bytes=serving.get("min_bytes",
                                                           4096),
                                     shards=1, codec=codec)
        jax.block_until_ready(jax.tree.leaves(params))
    compress_s = time.perf_counter() - t0
    leaves = weight_leaves(params)
    weight_stats = stream_stats(params)

    slots = int(traffic["slots"])
    # A closed loop of no more clients than slots cannot overload the
    # engine, so its overload governor is held off: a slow or stuck step
    # (a host that stands still) would only make it reject the clients'
    # next requests.  The stall still counts in every metric's time.
    engine = Engine(model, params, EngineConfig(
        max_slots=slots, queue_depth=max(slots, loop.clients),
        max_prompt_len=loop.max_prompt_len,
        max_new_tokens=loop.max_new_tokens,
        watchdog_s=float("inf"), overload_factor=float("inf")),
        codec=codec, clock=time.perf_counter)
    del params
    if engine_hook is not None:
        engine_hook(engine)

    with _span("bench.warmup"):
        for spec in loop.warmup(slots):
            engine.submit(spec.prompt, spec.max_new_tokens)
        engine.run_until_idle()

    clients = [_Client(i) for i in range(loop.clients)]
    logs: list = []

    def submit(c: _Client):
        spec = loop.for_client(c.idx, c.k)
        c.k += 1
        with _span("bench.submit"):
            c.req = engine.submit(spec.prompt, spec.max_new_tokens,
                                  name=f"c{c.idx}.{spec.index}")
        c.log = RequestLog(index=spec.index, prompt_len=spec.prompt.size,
                           max_new_tokens=spec.max_new_tokens,
                           submit_s=c.req.submit_s)
        logs.append(c.log)

    served: list = []
    # per decode step: (requests advanced, summed context) — filled by
    # observe() from the tokens each request gained
    step_rows: list = []

    def observe(now: float, record: Optional[list]):
        rows = ctx = 0
        for c in clients:
            r, log = c.req, c.log
            have = len(log.token_s)
            new = len(r.tokens) - have
            if new > 0:
                if have == 0:
                    log.admit_s = r.admit_s
                    log.token_s.append(r.first_token_s)
                    have, new = 1, new - 1
                for i in range(have, have + new):
                    log.token_s.append(now)
                    rows += 1
                    ctx += log.prompt_len + i
            if r.finished:
                log.state = r.state
                log.end_s = now
                c.completed += r.state == "done"
                if record is not None and r.state == "done":
                    served.append((np.asarray(r.prompt), list(r.tokens)))
                submit(c)
        if record is not None:
            record.append((rows, ctx))

    def drive(until, record=None):
        while True:
            with _span("bench.step"):
                engine.step()
            now = time.perf_counter()
            with _span("bench.clients"):
                observe(now, record)
            if until(now):
                return now

    def ramped(now):
        done = sum(c.completed for c in clients)
        if now - t_ramp > RAMP_LIMIT_S:
            raise RuntimeError(
                f"ramp: after {RAMP_LIMIT_S}s only {done} of "
                f"{len(clients)} requests completed "
                f"(engine {engine.stats()['engine']})")
        return done >= len(clients)

    with _span("bench.ramp"):
        t_ramp = time.perf_counter()
        for c in clients:
            submit(c)
        drive(ramped)

    steps_before = len(engine.step_times_s)
    tracing = trace_dir is not None
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t_open = time.perf_counter()
    setup_s = t_open - t_process
    counter.on = True
    with _span("bench.window"):
        t_close = drive(lambda now: now - t_open >= seconds, step_rows)
    counter.on = False
    dev = jax.devices()[0]
    hbm_in_use = int((dev.memory_stats() or {}).get("bytes_in_use", 0))
    engine_stats = engine.stats()
    if tracing:
        jax.profiler.stop_trace()
    mem_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    window = summarize(logs, t_open, t_close)
    step_s = engine.step_times_s[steps_before:]
    median_s = sorted(step_s)[len(step_s) // 2] if step_s else 0.0
    stalls_ms = [1e3 * t for t in step_s if t > STALL_FACTOR * median_s]
    prefills = [log for log in logs
                if log.admit_s is not None and t_open < log.admit_s <= t_close]
    run = Run(
        cell=cell, seed=seed, arch=cfg, mode=serving["weights"],
        peaks=peaks, leaves=leaves, weight_stats=weight_stats,
        compress_s=compress_s, setup_s=setup_s, window=window, logs=logs,
        step_s=list(step_s),
        decode_rows=[r for r, _ in step_rows if r],
        decode_ctx=[x for r, x in step_rows if r],
        prefill_lens=[log.prompt_len for log in prefills],
        prefill_s=[log.first_token_s - log.admit_s for log in prefills],
        compiles_in_window=counter.count,
        stalls_ms=stalls_ms[:STALLS_SHOWN],
        hbm_in_use_bytes=hbm_in_use,
        memory_peak_bytes=mem_peak, engine_stats=engine_stats)
    # free the program's serving state before the reference runs
    for c in clients:
        c.req = None
    del engine, clients, codec
    gc.collect()
    return run, served

