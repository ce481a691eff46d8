"""Resilient continuous-batching serving engine (docs/TRAFFIC.md).

The scheduler that turns the fast codec into a serving system: requests
enter a bounded :class:`~repro.runtime.admission.AdmissionQueue`, join a
fixed ring of KV *slots* at token granularity, decode together as one
batched step, and leave individually — completion, deadline eviction, and
fault eviction all happen per request while the rest of the batch keeps
going.

Design points (each is load-bearing for a robustness claim):

* **Slot ring, not per-request caches.**  One KV/state cache of
  ``max_slots`` slots is allocated once (``model.init_cache``); a request
  joins by prefilling alone (batch=1) and scattering its cache into its
  slot, and leaves by having the slot marked free — no reallocation, no
  recompile.  Every model op is row-independent, so slot occupancy cannot
  perturb other rows: engine logits are bit-identical to the one-shot
  path (asserted in tests/test_engine.py across dense/stream/fused).

* **Batch-size buckets bound recompiles.**  The decode step runs on the
  slot prefix ``[0, bucket)`` where ``bucket`` is the smallest power of
  two covering the highest occupied slot (capped at ``max_slots``), so at
  most ``log2(max_slots)+1`` step variants ever compile.

* **Deadlines are enforced at every stage.**  Expired-in-queue requests
  are shed before consuming a prefill; in-flight requests past their
  total deadline are evicted at step granularity with their slot
  reclaimed; a request that completes past its deadline is accounted
  ``timed_out``, never ``done``.

* **Step watchdog + overload governor.**  Step wall times feed the
  :class:`~repro.runtime.admission.OverloadGovernor`; a stuck or slow
  step sheds the lowest-priority queued work immediately, and sustained
  overload degrades *admission* (reject at the door) rather than the
  latency of admitted requests.

* **Serving-time fault tolerance.**  Before each prefill/decode step the
  engine probes ``runtime.faults.check_step(request.key)`` per active
  request under its :class:`~repro.runtime.retry.RetryPolicy` (with the
  request's remaining deadline as the retry budget): transient faults are
  absorbed, permanent ones evict ONLY the poisoned request, survivors
  continue bit-identically, and health transitions to ``degraded`` — not
  ``failed``.

* **Graceful drain.**  ``shutdown(deadline_s)`` refuses new work, sheds
  the queue, finishes in-flight requests until the deadline, and evicts
  stragglers as ``aborted``.

* **Observable on the profiler's clock.**  The jitted programs are named
  ``engine_prefill``, ``engine_install`` and ``engine_decode_step`` (the
  device shows them as ``jit_engine_*`` modules), and the host work is
  wrapped in ``engine.*`` spans (``jax.profiler.TraceAnnotation``; with no
  profiler session each is one cheap no-op), with a ``python.gc`` span
  around every garbage collection (docs/SERVING.md, "Observability").
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.runtime import faults as rt_faults
from repro.runtime.admission import (AdmissionQueue, OverloadGovernor,
                                     Request)
from repro.runtime.retry import RetryPolicy


class EngineError(RuntimeError):
    """Unrecoverable engine failure (invalid request, failed state)."""


class ServerHealth:
    """Readiness/health state of a serving process — the answer to a load
    balancer's probe (docs/RELIABILITY.md, docs/TRAFFIC.md).

    States: ``initializing`` -> ``restoring`` -> ``ready`` | ``degraded``
    (serving with fallback handles or after a fault eviction) |
    ``draining`` (shutdown in progress: in-flight finishes, new work
    refused) -> ``stopped`` | ``failed``.

    Engine-owned and thread-safe: all mutation goes through
    :meth:`transition` under a lock (probes may read from other threads),
    and :meth:`reset` returns a long-lived module-level instance (e.g.
    ``launch.serve.HEALTH``) to a clean slate between embedded runs — the
    old module-global was mutated in place and never reset on exceptions.
    """

    STATES = ("initializing", "restoring", "ready", "degraded", "draining",
              "stopped", "failed")

    def __init__(self, state: str = "initializing", detail: str = ""):
        self._lock = threading.Lock()
        self.state = state
        self.detail = detail

    def transition(self, state: str, detail: str = "") -> None:
        if state not in self.STATES:
            raise ValueError(f"unknown health state {state!r}; "
                             f"expected one of {self.STATES}")
        with self._lock:
            self.state, self.detail = state, detail

    def reset(self) -> None:
        self.transition("initializing", "")

    def ready(self) -> bool:
        """Should a load balancer route traffic here?  Degraded serving is
        still correct serving (logits are bit-identical across handle
        modes) — it answers yes.  Draining/stopped/failed answer no."""
        return self.state in ("ready", "degraded")


@dataclasses.dataclass
class EngineConfig:
    """Static policy of one :class:`Engine` (docs/TRAFFIC.md)."""
    max_slots: int = 4            # concurrency: size of the KV slot ring
    queue_depth: int = 16         # bounded admission queue depth
    max_prompt_len: int = 32
    max_new_tokens: int = 8       # per-request cap (requests may ask less)
    default_ttft_deadline_s: Optional[float] = None
    default_deadline_s: Optional[float] = None
    watchdog_s: float = 5.0       # absolute stuck-step threshold
    overload_factor: float = 4.0  # slow-step threshold (x baseline)
    warmup_steps: int = 3
    recovery_steps: int = 8
    shed_per_trip: int = 1        # queued requests shed per governor trip
    collect_logits: bool = False  # keep per-token logits on each request

    @property
    def max_len(self) -> int:
        return self.max_prompt_len + self.max_new_tokens


PHASES = ("admit", "dispatch", "wait", "emit", "gc")


@dataclasses.dataclass
class StepPhases:
    """Seconds of one decode step's :meth:`Engine.step` by phase, on the
    engine's clock: ``admit`` (deadline sweep, admission and its
    prefills, fault probes), ``dispatch`` (the step call), ``wait`` (the
    token fetch), ``emit`` (the bookkeeping after it).  Garbage
    collection pauses are taken out of the phase they fell in and counted
    under ``gc`` (timed on ``time.perf_counter``)."""
    admit: float = 0.0
    dispatch: float = 0.0
    wait: float = 0.0
    emit: float = 0.0
    gc: float = 0.0

    def largest(self) -> str:
        """The phase that took most of the step (the cause of a stall)."""
        return max(PHASES, key=lambda p: getattr(self, p))


class _GcTimer:
    """Seconds the process has spent in the garbage collector, and a
    ``python.gc`` span (with the collection's ``generation``) around each
    collection.  ``gc.callbacks`` is process-wide and a collection never
    runs inside another, so one instance serves every engine."""

    def __init__(self):
        self.seconds = 0.0
        self._t0 = 0.0
        self._span = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._span = TraceAnnotation("python.gc",
                                         generation=info["generation"])
            self._span.__enter__()
            self._t0 = time.perf_counter()
        elif self._span is not None:
            self.seconds += time.perf_counter() - self._t0
            self._span.__exit__(None, None, None)
            self._span = None


_GC_TIMER: Optional[_GcTimer] = None


def _gc_timer() -> _GcTimer:
    """The process's :class:`_GcTimer`, installed on first use."""
    global _GC_TIMER
    if _GC_TIMER is None:
        _GC_TIMER = _GcTimer()
        gc.callbacks.append(_GC_TIMER)
    return _GC_TIMER


class _PhaseClock:
    """Splits one step into :class:`StepPhases`: each :meth:`close` gives
    the time since the previous boundary to a phase, less the garbage
    collection inside it, which goes to ``gc``."""

    def __init__(self, clock: Callable[[], float], gc_timer: _GcTimer):
        self.clock, self.gc_timer = clock, gc_timer
        self.phases = StepPhases()
        self._t, self._gc = clock(), gc_timer.seconds

    def close(self, phase: str) -> None:
        t, g = self.clock(), self.gc_timer.seconds
        paused = g - self._gc
        self.phases.gc += paused
        setattr(self.phases, phase,
                getattr(self.phases, phase) + (t - self._t) - paused)
        self._t, self._gc = t, g


def _next_bucket(n: int, cap: int) -> int:
    """Smallest power of two >= n, capped at ``cap`` (the final bucket)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class Engine:
    """Continuous-batching request scheduler over the weight-handle
    executor (PR 2) and the Codec API (PR 5).

    The engine is single-driver: one thread calls :meth:`step` /
    :meth:`run_until_idle` / :meth:`shutdown`; :meth:`submit` is
    thread-safe and may be called from anywhere.  All JAX dispatches trace
    under the engine's codec (``use_codec``) plus any extra ambient
    context supplied by the launcher (e.g. a serving mesh).
    """

    def __init__(self, model, params, config: EngineConfig, *,
                 codec=None, retry: Optional[RetryPolicy] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 health: Optional[ServerHealth] = None,
                 extra_context: Optional[Callable] = None,
                 expert_store=None):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.config = config
        self.codec = codec
        # the MoE expert-streaming store behind any ExpertRef handles in
        # ``params`` (runtime/experts.py): observed for cache stats and
        # per-step cache-miss decode cost; the fetches themselves happen
        # inside the model's moe_block via io_callback
        self.expert_store = expert_store
        self.clock = clock
        self.sleep = sleep
        self.retry = retry if retry is not None \
            else RetryPolicy(sleep=sleep, clock=clock)
        self.health = health if health is not None else ServerHealth()
        self._extra_context = extra_context

        self.queue = AdmissionQueue(config.queue_depth)
        self.reset_governor()

        s = config.max_slots
        if s < 1:
            raise ValueError(f"max_slots must be >= 1, got {s}")
        self._slots: List[Optional[Request]] = [None] * s
        self._lengths = np.zeros((s,), np.int32)   # host-authoritative
        self._tokens = np.zeros((s,), np.int32)
        self._entries = None                       # device cache (lazy)
        self._prefill_fns: Dict[int, Callable] = {}
        self._step_fns: Dict[int, Callable] = {}
        self._install_fn = None

        self.results: Dict[int, Request] = {}
        # queue_wait_s: summed admit_s - submit_s of admitted requests;
        # steps_with_prefill: decode steps whose step() ran a prefill
        self.counters = {"submitted": 0, "admitted": 0, "done": 0,
                         "timed_out": 0, "rejected": 0, "shed": 0,
                         "evicted_deadline": 0, "evicted_fault": 0,
                         "evicted_abort": 0, "steps": 0, "prefills": 0,
                         "fault_retries": 0, "queue_wait_s": 0.0,
                         "steps_with_prefill": 0}
        self.step_times_s: List[float] = []
        # per decode step, beside step_times_s: where its step() went
        self.step_phases: List[StepPhases] = []
        self._gc_timer = _gc_timer()
        # per decode step: expert-cache MISS decode seconds (0.0 on a
        # fully-resident step) — step_times_s[i] - step_decode_s[i] is the
        # compute-only cost, making the cache-budget latency knob visible
        self.step_decode_s: List[float] = []
        self._draining = False
        # a launcher may hand in a health object already in "degraded"
        # (quarantined restore) — that outranks a plain "ready"
        if not self.health.ready():
            self.health.transition("ready")

    # -- ambient contexts ---------------------------------------------------

    def _trace_ctx(self):
        stack = contextlib.ExitStack()
        if self.codec is not None:
            from repro.core.codec_api import use_codec
            stack.enter_context(use_codec(self.codec))
        if self._extra_context is not None:
            stack.enter_context(self._extra_context())
        return stack

    # -- jit pieces (compiled lazily, bounded variants) ---------------------

    def _ensure_cache(self):
        if self._entries is None:
            cache = self.model.init_cache(self.config.max_slots,
                                          self.config.max_len)
            self._entries = cache["entries"]

    def _prefill_fn(self, plen: int):
        if plen not in self._prefill_fns:
            import jax
            import jax.numpy as jnp
            model, max_len = self.model, self.config.max_len

            def engine_prefill(params, tokens):
                logits, cache = model.prefill_fn(params, {"tokens": tokens},
                                                 max_len)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                return tok, logits, cache["entries"]

            self._prefill_fns[plen] = jax.jit(engine_prefill)
        return self._prefill_fns[plen]

    def _install(self, req_entries, slot: int):
        """Scatter a prefilled batch=1 cache into slot ``slot`` of the
        ring (one compile total: the slot index is a traced scalar)."""
        import jax

        if self._install_fn is None:
            def engine_install(entries, req_entries, slot):
                return jax.tree.map(
                    lambda full, part: jax.lax.dynamic_update_slice_in_dim(
                        full, part, slot, axis=1),
                    entries, req_entries)

            # donated off the CPU, as the step is: the slot is written in place
            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._install_fn = jax.jit(engine_install, donate_argnums=donate)
        self._entries = self._install_fn(self._entries, req_entries,
                                         np.int32(slot))

    def _step_fn(self, bucket: int):
        """One fused decode step over slots ``[0, bucket)``: decode and
        argmax.  The model gets the whole ring and a batch of ``bucket``
        rows; it reads those rows and writes each attention layer's new
        token into them in place, so the donated ring is never sliced or
        copied."""
        if bucket not in self._step_fns:
            import jax
            import jax.numpy as jnp
            model = self.model

            def engine_decode_step(params, entries, tokens, lengths):
                cache = {"entries": entries, "lengths": lengths[:bucket]}
                logits, new_cache = model.decode_fn(params, cache,
                                                    tokens[:bucket])
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                return tok, logits, new_cache["entries"]

            donate = (1,) if jax.default_backend() != "cpu" else ()
            self._step_fns[bucket] = jax.jit(engine_decode_step,
                                             donate_argnums=donate)
        return self._step_fns[bucket]

    def reset_governor(self) -> None:
        """Start the overload governor afresh — after a warm-up pass, whose
        compiling steps say nothing about load."""
        c = self.config
        self.governor = OverloadGovernor(
            watchdog_s=c.watchdog_s, overload_factor=c.overload_factor,
            warmup_steps=c.warmup_steps, recovery_steps=c.recovery_steps)

    def step_hlo(self, bucket: Optional[int] = None) -> str:
        """The decode step over ``bucket`` slots (default: the whole ring)
        as lowered for the compiler — shows which kernels a step runs (a
        native Pallas kernel is a ``tpu_custom_call``) without compiling."""
        bucket = bucket or self.config.max_slots
        self._ensure_cache()
        with self._trace_ctx():
            return self._step_fn(bucket).lower(
                self.params, self._entries, self._tokens,
                self._lengths).as_text()

    # -- submission ---------------------------------------------------------

    def submit(self, prompt, max_new_tokens: Optional[int] = None, *,
               priority: int = 0, ttft_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None,
               name: str = "") -> Request:
        """Offer one request.  Deadlines are RELATIVE seconds from now
        (None falls back to the config defaults).  Returns the Request —
        inspect ``.state``: "queued" on admission, "rejected" with
        ``.detail`` naming the reason on backpressure.  Invalid shapes
        (prompt too long for the ring) raise :class:`EngineError`: that is
        a caller bug, not load."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n_new = self.config.max_new_tokens if max_new_tokens is None \
            else max_new_tokens
        if not 1 <= n_new <= self.config.max_new_tokens:
            raise EngineError(f"max_new_tokens {n_new} outside [1, "
                              f"{self.config.max_new_tokens}]")
        if not 1 <= prompt.size <= self.config.max_prompt_len:
            raise EngineError(f"prompt length {prompt.size} outside [1, "
                              f"{self.config.max_prompt_len}]")
        now = self.clock()
        if ttft_deadline_s is None:
            ttft_deadline_s = self.config.default_ttft_deadline_s
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        req = Request(
            prompt=prompt, max_new_tokens=n_new, priority=priority,
            ttft_deadline_s=None if ttft_deadline_s is None
            else now + ttft_deadline_s,
            deadline_s=None if deadline_s is None else now + deadline_s,
            name=name)
        req.submit_s = now
        with TraceAnnotation("engine.submit", rid=req.rid):
            self.counters["submitted"] += 1
            self.results[req.rid] = req
            ok, _ = self.queue.offer(req,
                                     overloaded=self.governor.overloaded)
            if not ok:
                self.counters["rejected"] += 1
        return req

    # -- lifecycle helpers --------------------------------------------------

    def _active(self) -> List[Request]:
        return [r for r in self._slots if r is not None]

    def _free_slot(self, req: Request) -> None:
        slot = req.slot
        if slot is not None and self._slots[slot] is req:
            self._slots[slot] = None
            self._lengths[slot] = 0   # KV slot reclaimed for reuse
        req.slot = None

    def _finish(self, req: Request, state: str, detail: str = "") -> None:
        req.state, req.detail = state, detail
        req.finish_s = self.clock()
        self._free_slot(req)
        if state == "evicted":
            self.counters[f"evicted_{detail}"] += 1
        elif state in self.counters:
            self.counters[state] += 1

    def _complete(self, req: Request) -> None:
        """All tokens emitted: honest accounting against the deadline —
        a finish past the total deadline is ``timed_out``, not ``done``."""
        now = self.clock()
        late = req.deadline_s is not None and now > req.deadline_s
        self._finish(req, "timed_out" if late else "done")

    def _probe_step_faults(self, now: float) -> None:
        """Per active request: absorb transient step faults through the
        retry policy (budgeted by the request's remaining deadline); a
        permanent fault evicts ONLY the poisoned request and degrades
        health — survivors keep decoding."""
        if rt_faults.active() is None:
            return
        for req in self._active():
            budget = None
            if req.deadline_s is not None:
                budget = max(0.0, req.deadline_s - now)
            before = self.retry.stats()["retries"]
            try:
                self.retry.call(lambda r=req: rt_faults.check_step(r.key),
                                describe=f"step:{req.key}",
                                max_elapsed_s=budget)
            except rt_faults.InjectedFault as e:
                self._finish(req, "evicted", "fault")
                req.detail = "fault"
                self.health.transition(
                    "degraded", f"step fault evicted {req.name}: {e}")
            absorbed = self.retry.stats()["retries"] - before
            req.retries += absorbed
            self.counters["fault_retries"] += absorbed

    def _shed_and_evict(self, now: float) -> None:
        for req in self.queue.shed_expired(now):
            self.counters["shed"] += 1
            req.finish_s = now
        for req in self._active():
            if req.deadline_s is not None and now > req.deadline_s:
                self._finish(req, "evicted", "deadline")

    def _admit(self) -> int:
        """Fill free slots from the queue (lowest slot first, FIFO order);
        each admission = one batch=1 prefill scattered into the ring.  The
        ``engine.admit`` span opens only when a slot is free and a viable
        request waits, so its count is the steps that admitted."""
        if (self._draining or None not in self._slots
                or not self.queue.peek_viable()):
            return 0
        with TraceAnnotation("engine.admit"):
            admitted = 0
            while not self._draining and self.queue.peek_viable():
                try:
                    slot = self._slots.index(None)
                except ValueError:
                    break
                req = self.queue.take()
                if req is None:
                    break
                now = self.clock()
                if req.deadline_s is not None and now > req.deadline_s:
                    req.state, req.detail = "shed", "deadline"
                    req.finish_s = now
                    self.queue.counters["shed_deadline"] += 1
                    self.counters["shed"] += 1
                    continue
                # step-fault probe BEFORE the prefill consumes compute
                if rt_faults.active() is not None:
                    budget = None if req.deadline_s is None \
                        else max(0.0, req.deadline_s - now)
                    before = self.retry.stats()["retries"]
                    try:
                        self.retry.call(
                            lambda r=req: rt_faults.check_step(r.key),
                            describe=f"step:{req.key}", max_elapsed_s=budget)
                    except rt_faults.InjectedFault as e:
                        req.finish_s = self.clock()
                        req.state, req.detail = "evicted", "fault"
                        self.counters["evicted_fault"] += 1
                        self.health.transition(
                            "degraded",
                            f"step fault evicted {req.name} at admission: {e}")
                        continue
                    finally:
                        absorbed = self.retry.stats()["retries"] - before
                        req.retries += absorbed
                        self.counters["fault_retries"] += absorbed
                req.admit_s = self.clock()
                self.counters["queue_wait_s"] += req.admit_s - req.submit_s
                req.slot = slot
                self._slots[slot] = req
                req.state = "running"
                self.counters["admitted"] += 1
                self._run_prefill(req, slot)
                admitted += 1
                if req.finished:
                    continue
                if len(req.tokens) >= req.max_new_tokens:
                    self._complete(req)
            return admitted

    def _run_prefill(self, req: Request, slot: int) -> None:
        import jax

        with TraceAnnotation("engine.prefill", rid=req.rid,
                             plen=int(req.prompt.size), slot=slot):
            self._ensure_cache()
            self.counters["prefills"] += 1
            fn = self._prefill_fn(req.prompt.size)
            with self._trace_ctx():
                tok, logits, req_entries = fn(self.params,
                                              req.prompt[None, :])
                with TraceAnnotation("engine.prefill.wait"):
                    jax.block_until_ready(tok)
                self._install(req_entries, slot)
            req.first_token_s = self.clock()
            with TraceAnnotation("engine.prefill.wait"):
                t = int(np.asarray(tok)[0])
            req.tokens.append(t)
            self._tokens[slot] = t
            self._lengths[slot] = req.prompt.size
            if self.config.collect_logits:
                req.logits.append(np.asarray(logits)[0])

    def _decode_step(self, prefills: int, phase: _PhaseClock) -> None:
        active = self._active()
        bucket = _next_bucket(max(r.slot for r in active) + 1,
                              self.config.max_slots)
        with TraceAnnotation("engine.decode", bucket=bucket,
                             rows=len(active)):
            fn = self._step_fn(bucket)
            dec0 = (self.expert_store.decode_seconds()
                    if self.expert_store is not None else 0.0)
            t0 = self.clock()
            with self._trace_ctx():
                # real (non-injected) transient runtime errors ride the same
                # retry policy as checkpoint I/O; a persistent failure poisons
                # the whole batch — evict it and degrade rather than die
                try:
                    with TraceAnnotation("engine.decode.dispatch"):
                        tok, logits, new_entries = self.retry.call(
                            lambda: fn(self.params, self._entries,
                                       self._tokens, self._lengths),
                            describe=f"decode_step:b{bucket}")
                    phase.close("dispatch")
                    with TraceAnnotation("engine.decode.wait"):
                        np_tok = np.asarray(tok)
                    phase.close("wait")
                except OSError as e:
                    for req in active:
                        self._finish(req, "evicted", "fault")
                    self.health.transition(
                        "degraded", f"decode step failed, batch evicted: {e}")
                    return
            with TraceAnnotation("engine.decode.emit"):
                self._entries = new_entries
                dt = self.clock() - t0
                self.counters["steps"] += 1
                self.counters["steps_with_prefill"] += prefills > 0
                self.step_times_s.append(dt)
                self.step_decode_s.append(
                    (self.expert_store.decode_seconds() - dec0)
                    if self.expert_store is not None else 0.0)
                if self.governor.observe_step(dt):
                    for req in self.queue.shed_lowest_priority(
                            self.config.shed_per_trip, reason="overload"):
                        self.counters["shed"] += 1
                        req.finish_s = self.clock()
                np_logits = np.asarray(logits) if self.config.collect_logits \
                    else None
                for req in active:
                    slot = req.slot
                    t = int(np_tok[slot])
                    req.tokens.append(t)
                    self._tokens[slot] = t
                    self._lengths[slot] += 1
                    if np_logits is not None:
                        req.logits.append(np_logits[slot])
                    if len(req.tokens) >= req.max_new_tokens:
                        self._complete(req)
            phase.close("emit")
            self.step_phases.append(phase.phases)

    # -- driver -------------------------------------------------------------

    def step(self) -> bool:
        """One scheduler iteration: shed/evict by deadline, probe step
        faults, admit from the queue, run one batched decode step.
        Returns True if any work happened (admission or decode)."""
        if self.health.state == "failed":
            raise EngineError(f"engine failed: {self.health.detail}")
        with TraceAnnotation("engine.step") as span:
            phase = _PhaseClock(self.clock, self._gc_timer)
            self._shed_and_evict(self.clock())
            admitted = self._admit()
            self._probe_step_faults(self.clock())
            rows = len(self._active())
            span.set_metadata(prefills=admitted, rows=rows)
            if not rows:
                return admitted > 0
            phase.close("admit")
            self._decode_step(admitted, phase)
            return True

    def has_work(self) -> bool:
        return bool(self._active()) or self.queue.peek_viable()

    def run_until_idle(self, max_steps: Optional[int] = None) -> None:
        """Drive steps until queue and slots are empty (bench/launcher
        loop; submissions may keep arriving from other threads)."""
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                return

    def shutdown(self, deadline_s: Optional[float] = None) -> None:
        """Graceful drain: refuse new work, shed the queue, finish
        in-flight requests; past ``deadline_s`` (relative seconds) the
        stragglers are evicted as ``abort``.  Health: ``draining`` ->
        ``stopped``."""
        self._draining = True
        self.queue.close()
        self.health.transition("draining",
                               f"{len(self._active())} in flight")
        for req in self.queue.drain_all("drain"):
            self.counters["shed"] += 1
            req.finish_s = self.clock()
        abs_deadline = None if deadline_s is None \
            else self.clock() + deadline_s
        while self._active():
            if abs_deadline is not None and self.clock() > abs_deadline:
                for req in self._active():
                    self._finish(req, "evicted", "abort")
                break
            self.step()
        self.health.transition("stopped", "drained")

    # -- observability ------------------------------------------------------

    def stats(self) -> dict:
        """One dict with every counter a probe, bench, or test needs.
        ``experts`` (present when an expert store is installed) carries
        the LRU hit/miss/eviction/resident-bytes counters next to the
        engine counters."""
        out = {
            "engine": dict(self.counters,
                           compiled_buckets=sorted(self._step_fns),
                           active=len(self._active()),
                           queued=len(self.queue)),
            "queue": dict(self.queue.counters,
                          depth=len(self.queue),
                          max_depth_seen=self.queue.max_depth_seen,
                          cap=self.queue.depth),
            "governor": self.governor.stats(),
            "retry": self.retry.stats(),
            "health": {"state": self.health.state,
                       "detail": self.health.detail},
        }
        if self.expert_store is not None:
            out["experts"] = self.expert_store.stats()
        return out
