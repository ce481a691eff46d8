"""ISSUE 9: the resilient continuous-batching serving engine.

The load-bearing claims of docs/TRAFFIC.md, each asserted here:
engine logits are BIT-IDENTICAL to the one-shot serve path in every
weight-execution mode (row-independence of the model ops makes slot
occupancy invisible); admission is bounded with deterministic
reject-with-reason; deadlines shed queued work before any prefill and
evict in-flight work at step granularity with the KV slot reclaimed; a
poisoned request is evicted alone (survivors bit-identical, health
``degraded`` not ``failed``); drain finishes in-flight work and refuses
new; the overload governor sheds queued low-priority work and degrades
admission, never admitted-request latency.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model, lm
from repro.runtime import faults as rt_faults
from repro.runtime.admission import (AdmissionQueue, OverloadGovernor,
                                     Request)
from repro.runtime.engine import (Engine, EngineConfig, EngineError,
                                  ServerHealth)
from repro.runtime.faults import FaultSpec
from repro.runtime.retry import RetryPolicy
from repro.runtime.streaming import assign_weight_modes

PROMPT_LEN = 6
N_NEW = 4


class FakeClock:
    """Deterministic time source for deadline tests (no real sleeping)."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (4, PROMPT_LEN), 0, cfg.vocab_size), np.int32)
    return cfg, model, params, prompts


def _one_shot(model, params, prompt, n_new, max_len):
    """The pre-engine serve loop: batch=1 prefill + argmax decode."""
    logits, cache = model.prefill_fn(params, {"tokens": prompt[None, :]},
                                     max_len)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    toks, outs = [int(np.asarray(tok)[0])], [np.asarray(logits)[0]]
    for _ in range(n_new - 1):
        logits, cache = model.decode_fn(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(int(np.asarray(tok)[0]))
        outs.append(np.asarray(logits)[0])
    return toks, outs


def _ecfg(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_prompt_len", PROMPT_LEN)
    kw.setdefault("max_new_tokens", N_NEW)
    kw.setdefault("collect_logits", True)
    return EngineConfig(**kw)


def _assert_bit_identical(got_logits, ref_logits, msg=""):
    assert len(got_logits) == len(ref_logits), msg
    for i, (g, r) in enumerate(zip(got_logits, ref_logits)):
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint32), np.asarray(r).view(np.uint32),
            err_msg=f"{msg} token {i}")


# ---------------------------------------------------------------------------
# bit-parity with the one-shot path (the tentpole acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["dense", "stream", "fused"])
def test_engine_logits_bit_identical_to_one_shot(setup, mode):
    cfg, model, params, prompts = setup
    tree = assign_weight_modes(params, mode=mode, min_bytes=1024, shards=2)
    engine = Engine(model, tree, _ecfg())
    reqs = [engine.submit(prompts[i], N_NEW, name=f"r{i}") for i in range(2)]
    engine.run_until_idle()
    for i, req in enumerate(reqs):
        assert req.state == "done", (req.state, req.detail)
        ref_toks, ref_logits = _one_shot(model, tree, prompts[i], N_NEW,
                                         engine.config.max_len)
        assert req.tokens == ref_toks, mode
        _assert_bit_identical(req.logits, ref_logits, f"{mode} req{i}")


def test_staggered_join_keeps_bit_parity(setup):
    """Continuous batching: a request that joins mid-flight (while another
    is already decoding) still produces exactly the one-shot logits, and
    so does the request it joined."""
    cfg, model, params, prompts = setup
    engine = Engine(model, params, _ecfg(max_slots=4))
    first = engine.submit(prompts[0], N_NEW, name="first")
    engine.step()            # first is admitted and emits token 1
    engine.step()            # first decodes alone
    late = engine.submit(prompts[1], N_NEW, name="late")
    engine.run_until_idle()
    for req, prompt in ((first, prompts[0]), (late, prompts[1])):
        assert req.state == "done"
        ref_toks, ref_logits = _one_shot(model, params, prompt, N_NEW,
                                         engine.config.max_len)
        assert req.tokens == ref_toks
        _assert_bit_identical(req.logits, ref_logits, req.name)
    # both requests shared the ring: slots differ, logits don't
    st = engine.stats()["engine"]
    assert st["prefills"] == 2 and st["done"] == 2


def test_bucket_compiles_are_bounded(setup):
    """4 concurrent requests over a 4-slot ring compile at most
    log2(4)+1 = 3 step variants, and only the ones actually occupied."""
    cfg, model, params, prompts = setup
    engine = Engine(model, params, _ecfg(max_slots=4, queue_depth=8))
    for i in range(4):
        engine.submit(prompts[i], N_NEW, name=f"b{i}")
    engine.run_until_idle()
    buckets = engine.stats()["engine"]["compiled_buckets"]
    assert set(buckets) <= {1, 2, 4} and len(buckets) <= 3


# ---------------------------------------------------------------------------
# the KV ring is written in place (tests/test_engine_drivers.py: every layer
# driver serves the tokens of a full-sequence forward)
# ---------------------------------------------------------------------------

_TENSOR = re.compile(r"tensor<((?:\d+x)+)[a-z]\w*>")
_WRITE = re.compile(r"(%[\w#]+) = (stablehlo\.dynamic_update_slice|"
                    r"\"stablehlo\.scatter\")[(\s]+(%[\w#]+)")


def _shape(tensor_type: str) -> tuple:
    return tuple(int(d) for d in tensor_type.rstrip("x").split("x"))


def _cache_writes(hlo: str, cache_tail: tuple) -> list:
    """Every op of the lowered step that writes a tensor (the keyed ops:
    ``stablehlo.dynamic_update_slice`` and ``stablehlo.scatter``) and
    yields one shaped like a K/V cache (ending in max_len, KV, hd), as
    ``(name, op, target, operand shapes, result shape)``."""
    lines = hlo.splitlines()
    out = []
    for i, line in enumerate(lines):
        m = _WRITE.search(line)
        if m is None:
            continue
        sig = line
        while "->" not in sig:          # a scatter's types close its region
            i += 1
            sig = lines[i]
        operands, result = sig.rsplit("->", 1)
        types = operands.split(" : ")[-1]
        shapes = [_shape(t) for t in _TENSOR.findall(types)]
        res = _shape(_TENSOR.findall(result)[0])
        if res[-3:] == cache_tail:
            out.append((m.group(1), m.group(2).strip('"'), m.group(3),
                        shapes, res))
    return out


@pytest.mark.parametrize("bucket", [4, 2])
def test_decode_step_writes_one_position_per_row_in_place(setup, bucket):
    """The lowered decode step writes the ring only by
    ``stablehlo.dynamic_update_slice``s of one position per row, chained
    from the ring argument to the returned ring: no layer computes, and no
    layer loop stacks, a whole layer's K/V cache (a per-layer
    ``stablehlo.scatter`` of the new token, or a whole-layer or whole-ring
    ``dynamic_update_slice``, would), and the step never slices the ring
    and writes it back.  Donated on the chip, the returned ring is the
    input's buffer."""
    cfg, model, params, _ = setup
    engine = Engine(model, params, _ecfg(max_slots=4))
    s = lm.attn_shape(cfg)
    max_len = engine.config.max_len
    ring = (cfg.n_layers, 4, max_len, s.n_kv_heads, s.head_dim)
    hlo = engine.step_hlo(bucket)
    writes = _cache_writes(hlo, ring[-3:])
    assert writes
    for name, op, _, shapes, res in writes:
        assert op == "stablehlo.dynamic_update_slice", (name, op, res)
        assert res == ring, (name, res)
        assert shapes[1] == (cfg.n_layers, 1, 1) + ring[-2:], (name, shapes)
    # the rows written are the bucket's, each once per K and V
    assert len(writes) == 2 * bucket
    main = hlo[hlo.index("@main("):]
    main = main[:main.index("\n  }")]
    ret = main[main.rindex("return "):].split(":")[0].split()[1:]
    source = {name: target for name, _, target, _, _ in writes}
    ring_args = re.findall(r"(%arg\d+): tensor<" + "x".join(map(str, ring)),
                           main)
    returned = [v.rstrip(",") for v in ret if v.rstrip(",") in source]
    assert len(returned) == len(ring_args) == 2
    for value in returned:
        while value in source:
            value = source[value]
        assert value in ring_args


# ---------------------------------------------------------------------------
# admission: bounded queue, deterministic reject-with-reason
# ---------------------------------------------------------------------------

def test_queue_full_rejection_is_deterministic(setup):
    cfg, model, params, prompts = setup
    for _ in range(2):   # identical outcome on repeat runs
        engine = Engine(model, params, _ecfg(max_slots=1, queue_depth=2))
        reqs = [engine.submit(prompts[i % 4], 1, name=f"q{i}")
                for i in range(4)]
        assert [r.state for r in reqs] == ["queued", "queued",
                                           "rejected", "rejected"]
        assert [r.detail for r in reqs[2:]] == ["queue_full", "queue_full"]
        st = engine.stats()["queue"]
        assert st["rejected_queue_full"] == 2
        assert st["max_depth_seen"] == 2 <= engine.queue.depth
        engine.run_until_idle()
        assert [r.state for r in reqs[:2]] == ["done", "done"]


def test_invalid_request_raises_not_rejects(setup):
    cfg, model, params, prompts = setup
    engine = Engine(model, params, _ecfg())
    with pytest.raises(EngineError, match="prompt length"):
        engine.submit(np.zeros((PROMPT_LEN + 5,), np.int32))
    with pytest.raises(EngineError, match="max_new_tokens"):
        engine.submit(prompts[0], N_NEW + 1)


# ---------------------------------------------------------------------------
# deadlines: shed before prefill, evict at step granularity, honest bookkeeping
# ---------------------------------------------------------------------------

def test_expired_queued_request_shed_before_prefill(setup):
    cfg, model, params, prompts = setup
    clock = FakeClock()
    engine = Engine(model, params, _ecfg(), clock=clock, sleep=lambda s: None)
    req = engine.submit(prompts[0], N_NEW, ttft_deadline_s=1.0, name="late")
    clock.advance(2.0)       # TTFT deadline passes while queued
    engine.step()
    assert req.state == "shed" and req.detail == "deadline"
    st = engine.stats()["engine"]
    assert st["prefills"] == 0 and st["shed"] == 1


def test_in_flight_deadline_evicts_and_reclaims_slot(setup):
    cfg, model, params, prompts = setup
    clock = FakeClock()
    engine = Engine(model, params, _ecfg(max_slots=2, queue_depth=8),
                    clock=clock, sleep=lambda s: None)
    keeper = engine.submit(prompts[0], N_NEW, deadline_s=1000.0,
                           name="keeper")
    victim = engine.submit(prompts[1], N_NEW, deadline_s=5.0, name="victim")
    engine.step()            # both admitted, first decode
    victim_slot = victim.slot
    assert victim_slot is not None
    clock.advance(10.0)      # victim's total deadline passes mid-flight
    engine.step()
    assert victim.state == "evicted" and victim.detail == "deadline"
    assert victim.slot is None
    assert keeper.state in ("running", "done")
    # the reclaimed slot is reused by the next admission
    succ = engine.submit(prompts[2], N_NEW, deadline_s=1000.0, name="succ")
    engine.step()
    assert succ.slot == victim_slot
    engine.run_until_idle()
    assert keeper.state == "done" and succ.state == "done"
    assert engine.stats()["engine"]["evicted_deadline"] == 1
    # the keeper was never perturbed by the eviction
    ref_toks, ref_logits = _one_shot(model, params, prompts[0], N_NEW,
                                     engine.config.max_len)
    assert keeper.tokens == ref_toks
    _assert_bit_identical(keeper.logits, ref_logits, "keeper")


def test_late_completion_is_timed_out_not_done(setup):
    """A request that finishes past its total deadline must be accounted
    timed_out: the CI deadline gate (admitted-and-done => within deadline)
    holds by construction."""
    cfg, model, params, prompts = setup
    clock = FakeClock()
    engine = Engine(model, params, _ecfg(), clock=clock,
                    sleep=lambda s: None)
    req = engine.submit(prompts[0], 1, deadline_s=5.0, name="tardy")
    # the deadline passes between admission and completion: advance the
    # clock from inside the prefill dispatch
    orig = engine._run_prefill

    def slow_prefill(r, slot):
        clock.advance(10.0)
        orig(r, slot)

    engine._run_prefill = slow_prefill
    engine.run_until_idle()
    assert req.state == "timed_out"
    st = engine.stats()["engine"]
    assert st["timed_out"] == 1 and st["done"] == 0


# ---------------------------------------------------------------------------
# serving-time faults: transient absorbed, permanent evicts only the poisoned
# ---------------------------------------------------------------------------

def _fault_retry():
    return RetryPolicy(base_delay_s=0.0001, max_delay_s=0.001,
                       sleep=lambda s: None)


def test_transient_step_fault_absorbed_by_retry(setup):
    cfg, model, params, prompts = setup
    engine = Engine(model, params, _ecfg(), retry=_fault_retry())
    with rt_faults.inject(FaultSpec(kind="step", match="flaky", times=2)):
        req = engine.submit(prompts[0], N_NEW, name="flaky")
        engine.run_until_idle()
    assert req.state == "done"
    assert req.retries == 2
    assert engine.stats()["engine"]["fault_retries"] == 2
    assert engine.health.state == "ready"       # absorbed, not degraded
    ref_toks, _ = _one_shot(model, params, prompts[0], N_NEW,
                            engine.config.max_len)
    assert req.tokens == ref_toks


def test_permanent_step_fault_evicts_only_poisoned(setup):
    """The fault-isolation acceptance: a permanent step fault on one
    request evicts exactly it; the survivors' tokens AND logits are
    bit-identical to a fault-free run; health degrades, never fails."""
    cfg, model, params, prompts = setup
    # reference: fault-free run with the same three requests
    ref_engine = Engine(model, params, _ecfg(max_slots=4, queue_depth=8))
    ref = [ref_engine.submit(prompts[i], N_NEW, name=f"p{i}")
           for i in range(3)]
    ref_engine.run_until_idle()
    assert all(r.state == "done" for r in ref)

    engine = Engine(model, params, _ecfg(max_slots=4, queue_depth=8),
                    retry=_fault_retry())
    with rt_faults.inject(FaultSpec(kind="step", match="p1", times=-1)):
        reqs = [engine.submit(prompts[i], N_NEW, name=f"p{i}")
                for i in range(3)]
        engine.run_until_idle()
    assert reqs[1].state == "evicted" and reqs[1].detail == "fault"
    for i in (0, 2):
        assert reqs[i].state == "done", (i, reqs[i].state, reqs[i].detail)
        assert reqs[i].tokens == ref[i].tokens
        _assert_bit_identical(reqs[i].logits, ref[i].logits, f"survivor {i}")
    assert engine.health.state == "degraded"
    assert "p1" in engine.health.detail
    assert engine.stats()["engine"]["evicted_fault"] == 1


def test_mid_flight_step_fault_evicts_after_admission(setup):
    """A fault that starts firing after the request is already decoding
    evicts it mid-flight (some tokens emitted) while the rest of the
    batch finishes untouched."""
    cfg, model, params, prompts = setup
    engine = Engine(model, params, _ecfg(max_slots=4, queue_depth=8),
                    retry=_fault_retry())
    survivor = engine.submit(prompts[0], N_NEW, name="ok")
    victim = engine.submit(prompts[1], N_NEW, name="victim")
    engine.step()            # both admitted cleanly, first tokens out
    assert victim.tokens, "victim should have emitted before the fault"
    with rt_faults.inject(FaultSpec(kind="step", match="victim", times=-1)):
        engine.run_until_idle()
    assert victim.state == "evicted" and victim.detail == "fault"
    assert 1 <= len(victim.tokens) < N_NEW
    assert survivor.state == "done"
    ref_toks, ref_logits = _one_shot(model, params, prompts[0], N_NEW,
                                     engine.config.max_len)
    assert survivor.tokens == ref_toks
    _assert_bit_identical(survivor.logits, ref_logits, "survivor")
    assert engine.health.state == "degraded"


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

def test_shutdown_drains_in_flight_and_refuses_new(setup):
    cfg, model, params, prompts = setup
    engine = Engine(model, params, _ecfg(max_slots=1, queue_depth=8))
    running = engine.submit(prompts[0], N_NEW, name="running")
    queued = engine.submit(prompts[1], N_NEW, name="queued")
    engine.step()            # running admitted; queued waits (1 slot)
    assert running.state == "running" and queued.state == "queued"
    engine.shutdown()
    assert running.state == "done"                 # in-flight finished
    assert len(running.tokens) == N_NEW
    assert queued.state == "shed" and queued.detail == "drain"
    late = engine.submit(prompts[2], N_NEW, name="too-late")
    assert late.state == "rejected" and late.detail == "draining"
    assert engine.health.state == "stopped"
    assert not engine.health.ready()


def test_shutdown_deadline_aborts_stragglers(setup):
    cfg, model, params, prompts = setup
    clock = FakeClock()
    engine = Engine(model, params, _ecfg(max_slots=1), clock=clock,
                    sleep=lambda s: None)
    req = engine.submit(prompts[0], N_NEW, name="straggler")
    engine.step()
    assert req.state == "running"
    clock.advance(0.0)
    # the drain budget expires immediately: every engine.step() inside
    # shutdown() is preceded by the deadline check
    orig_step = engine.step

    def step_advancing():
        clock.advance(100.0)
        return orig_step()

    engine.step = step_advancing
    engine.shutdown(deadline_s=50.0)
    assert req.state == "evicted" and req.detail == "abort"
    assert engine.health.state == "stopped"


# ---------------------------------------------------------------------------
# overload governor: watchdog trips shed queued work, admission degrades
# ---------------------------------------------------------------------------

def test_governor_learns_baseline_and_trips_on_slow():
    gov = OverloadGovernor(watchdog_s=5.0, overload_factor=4.0,
                           warmup_steps=3, recovery_steps=2)
    for _ in range(3):
        assert not gov.observe_step(0.1)
    assert gov.state == "nominal" and abs(gov.baseline_s - 0.1) < 1e-9
    assert gov.observe_step(1.0)            # 1.0 > 4 x 0.1: slow
    assert gov.overloaded
    baseline = gov.baseline_s
    assert gov.observe_step(10.0)           # stuck (absolute watchdog)
    assert gov.baseline_s == baseline       # violations never move the EMA
    assert not gov.observe_step(0.1)        # healthy 1/2
    assert gov.overloaded                   # still overloaded
    assert not gov.observe_step(0.1)        # healthy 2/2: recovered
    assert gov.state == "nominal"
    st = gov.stats()
    assert st["slow_steps"] == 1 and st["stuck_steps"] == 1
    assert st["trips"] == 2 and st["recoveries"] == 1


def test_governor_watchdog_catches_stuck_step_during_warmup():
    gov = OverloadGovernor(watchdog_s=5.0, warmup_steps=3)
    assert gov.observe_step(6.0)
    assert gov.overloaded and gov.baseline_s is None


def test_engine_overload_sheds_queued_and_degrades_admission(setup):
    """watchdog_s=0 makes every real decode step a violation: each step
    sheds the lowest-priority queued request, and while overloaded the
    front door rejects priority<=0 work but still admits priority>0."""
    cfg, model, params, prompts = setup
    engine = Engine(model, params,
                    _ecfg(max_slots=1, queue_depth=8, watchdog_s=0.0))
    running = engine.submit(prompts[0], N_NEW, name="running")
    low = engine.submit(prompts[1], N_NEW, priority=0, name="low")
    high = engine.submit(prompts[2], N_NEW, priority=1, name="high")
    engine.step()            # decode step trips the watchdog
    assert engine.governor.overloaded
    # the LOWEST priority queued request was shed, the higher one kept
    assert low.state == "shed" and low.detail == "overload"
    assert high.state == "queued"
    # overloaded admission: priority 0 rejected, priority > 0 admitted
    r0 = engine.submit(prompts[3], N_NEW, priority=0, name="walk-in")
    r1 = engine.submit(prompts[3], N_NEW, priority=1, name="vip")
    assert r0.state == "rejected" and r0.detail == "overloaded"
    assert r1.state == "queued"
    engine.run_until_idle()
    # the ADMITTED request finished untouched; under sustained overload
    # (every step trips here) the queued work is progressively shed —
    # admission degrades, admitted-request latency does not
    assert running.state == "done" and len(running.tokens) == N_NEW
    assert {high.state, r1.state} == {"shed"}
    assert engine.stats()["queue"]["rejected_overloaded"] == 1
    assert engine.stats()["engine"]["shed"] >= 3


# ---------------------------------------------------------------------------
# admission-layer unit tests (no model)
# ---------------------------------------------------------------------------

def test_admission_queue_sheds_lowest_priority_newest_first():
    q = AdmissionQueue(depth=8)
    reqs = [Request(prompt=np.zeros(1, np.int32), max_new_tokens=1,
                    priority=p, name=f"a{i}")
            for i, p in enumerate([1, 0, 0, 2])]
    for r in reqs:
        assert q.offer(r)[0]
    shed = q.shed_lowest_priority(2, reason="overload")
    # ties on priority 0 break newest-first: a2 before a1
    assert [r.name for r in shed] == ["a2", "a1"]
    assert len(q) == 2 and q.counters["shed_overload"] == 2


def test_admission_queue_reject_reasons_have_precedence():
    q = AdmissionQueue(depth=1)
    ok, _ = q.offer(Request(prompt=np.zeros(1, np.int32), max_new_tokens=1))
    assert ok
    full = Request(prompt=np.zeros(1, np.int32), max_new_tokens=1)
    assert q.offer(full) == (False, "queue_full")
    over = Request(prompt=np.zeros(1, np.int32), max_new_tokens=1)
    assert q.offer(over, overloaded=True) == (False, "overloaded")
    q.close()
    drained = Request(prompt=np.zeros(1, np.int32), max_new_tokens=1)
    assert q.offer(drained, overloaded=True) == (False, "draining")


def test_server_health_transitions_and_reset():
    h = ServerHealth()
    assert h.state == "initializing" and not h.ready()
    h.transition("ready")
    assert h.ready()
    h.transition("degraded", "one record on fallback")
    assert h.ready() and h.detail == "one record on fallback"
    h.transition("draining")
    assert not h.ready()
    with pytest.raises(ValueError, match="unknown health state"):
        h.transition("on-fire")
    h.reset()
    assert h.state == "initializing" and h.detail == ""
