"""The serving engine's observability: its jitted programs carry stable
names, its host work is wrapped in ``engine.*`` spans on the profiler's
clock (nested as docs/SERVING.md lists them, with request and step
identity as metadata), every garbage collection gets a ``python.gc``
span, and its counters and per-step phase records are exact."""
import dataclasses
import gc
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.runtime.engine import PHASES, Engine, EngineConfig

PROMPT_LEN = 6


class FakeClock:
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
    return model, params, prompts


def _engine(setup, max_slots=2, n_new=4, **kw):
    model, params, _ = setup
    return Engine(model, params, EngineConfig(
        max_slots=max_slots, max_prompt_len=PROMPT_LEN,
        max_new_tokens=n_new), **kw)


@dataclasses.dataclass
class Ev:
    name: str
    lo: int
    hi: int
    stats: dict

    def inside(self, other: "Ev") -> bool:
        return other.lo <= self.lo and self.hi <= other.hi


def _host_events(trace_dir) -> list:
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    return [Ev(ev.name, ev.start_ns, ev.end_ns,
               {k: v for k, v in ev.stats})
            for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events]


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    """Two requests served by a warm engine under the profiler, with one
    forced collection."""
    _, _, prompts = setup
    engine = _engine(setup)
    engine.submit(prompts[0])
    engine.submit(prompts[1])
    engine.run_until_idle()            # compiles outside the trace
    trace_dir = tmp_path_factory.mktemp("engine_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        reqs = [engine.submit(prompts[i], name=f"r{i}") for i in range(2)]
        engine.run_until_idle()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    return reqs, _host_events(trace_dir)


def _named(events, name):
    return [e for e in events if e.name == name]


def test_every_span_is_written_nested_with_its_metadata(traced):
    reqs, events = traced
    submits = _named(events, "engine.submit")
    assert sorted(e.stats["rid"] for e in submits) == \
        sorted(r.rid for r in reqs)

    steps, admits = _named(events, "engine.step"), _named(events,
                                                          "engine.admit")
    prefills, decodes = (_named(events, "engine.prefill"),
                         _named(events, "engine.decode"))
    # both requests join in the first step: one admission, two prefills
    assert len(admits) == 1 and len(prefills) == 2
    assert all(any(a.inside(s) for s in steps) for a in admits)
    assert all(p.inside(admits[0]) for p in prefills)
    assert sorted(p.stats["rid"] for p in prefills) == \
        sorted(r.rid for r in reqs)
    assert {(p.stats["plen"], p.stats["slot"]) for p in prefills} == \
        {(PROMPT_LEN, 0), (PROMPT_LEN, 1)}
    waits = _named(events, "engine.prefill.wait")
    assert len(waits) == 2 * len(prefills)
    assert all(any(w.inside(p) for p in prefills) for w in waits)

    # 4 tokens each: the prefill's, then three decode steps of both rows
    assert len(decodes) == len(steps) == 3
    assert all(d.stats == {"bucket": 2, "rows": 2} for d in decodes)
    assert [s.stats["prefills"] for s in steps] == [2, 0, 0]
    assert all(s.stats["rows"] == 2 for s in steps)
    assert all(any(d.inside(s) for s in steps) for d in decodes)
    for part in ("dispatch", "wait", "emit"):
        spans = _named(events, f"engine.decode.{part}")
        assert len(spans) == len(decodes), part
        assert all(any(x.inside(d) for d in decodes) for x in spans), part

    assert any("generation" in e.stats for e in _named(events, "python.gc"))


def test_programs_carry_their_names_on_the_host(traced):
    _, events = traced
    names = {e.name for e in events}
    for program in ("engine_prefill", "engine_install",
                    "engine_decode_step"):
        assert f"PjitFunction({program})" in names
    assert "PjitFunction(fn)" not in names


def test_step_hlo_lowers_the_named_decode_program(setup):
    hlo = _engine(setup).step_hlo()
    assert "module @jit_engine_decode_step" in hlo


def test_queue_wait_and_prefill_steps_are_exact(setup):
    """A scripted order on a fake clock: A and B join in the first step,
    C in the second, and a third step decodes C alone."""
    _, _, prompts = setup
    clock = FakeClock()
    engine = _engine(setup, n_new=3, clock=clock, sleep=lambda s: None)
    engine.submit(prompts[0], 2, name="A")            # t = 100
    clock.advance(0.5)
    engine.submit(prompts[1], 2, name="B")            # t = 100.5
    clock.advance(0.5)
    c = engine.submit(prompts[2], 3, name="C")        # t = 101
    for _ in range(3):
        clock.advance(1.0)
        engine.step()                                 # t = 102, 103, 104
    assert c.state == "done"
    st = engine.stats()["engine"]
    assert st["queue_wait_s"] == 2.0 + 1.5 + 2.0
    assert (st["steps"], st["steps_with_prefill"], st["prefills"]) == \
        (3, 2, 3)
    assert len(engine.step_phases) == len(engine.step_times_s) == 3


def _wrap_step(engine, before_call):
    make = engine._step_fn

    def step_fn(bucket):
        fn = make(bucket)

        def call(*args):
            before_call()
            return fn(*args)
        return call

    engine._step_fn = step_fn


def test_a_stalled_step_call_is_a_dispatch_phase(setup):
    _, _, prompts = setup
    engine = _engine(setup, clock=time.perf_counter)
    calls = []

    def stall_third():
        calls.append(1)
        if len(calls) == 3:
            time.sleep(0.05)

    _wrap_step(engine, stall_third)
    engine.submit(prompts[0])
    engine.run_until_idle()
    phases = engine.step_phases
    assert len(phases) == len(engine.step_times_s) == 3
    stalled = phases[2]
    assert stalled.largest() == "dispatch"
    assert stalled.dispatch >= 0.05
    assert all(getattr(stalled, p) < 0.05 for p in PHASES
               if p != "dispatch")


def test_a_collection_inside_a_step_is_its_gc_phase(setup):
    _, _, prompts = setup
    engine = _engine(setup, clock=time.perf_counter)
    _wrap_step(engine, gc.collect)
    engine.submit(prompts[0])
    engine.run_until_idle()
    assert all(p.gc > 0 for p in engine.step_phases)
