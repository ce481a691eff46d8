"""Device time by named program and idle time by program span
(``bench/programs.py``), and the per-layer metrics that read the engine's
spans, on synthetic events, on the trace recorded on a TPU v5e
(``bench/data/``) and on a CPU run of a smoke cell."""
import gzip
import time
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import harness, programs, spec, xplane
from bench.tests.smoke import smoke_cell

DATA = Path(__file__).resolve().parents[1] / "data"
SEED = 2**31 + 7
SPAN_METRICS = ("engine.host_ms", "engine.prefill_step_share")


@pytest.mark.parametrize("event, name", [
    ("jit_engine_decode_step(16407404657872579928)", "engine_decode_step"),
    ("jit_fn(9198221281748001085)", "fn"),
    ("jit_engine_install", "engine_install"),
    ("main(3)", "main"),
])
def test_program_names_drop_the_jit_prefix_and_the_hash(event, name):
    assert programs.program_name(event) == name


def _spans(*spans):
    """``(lo, hi, name)`` triples in the form ``xplane._host_spans`` gives."""
    names = sorted({n for _, _, n in spans})
    return (np.asarray([s[0] for s in spans], np.float64),
            np.asarray([s[1] for s in spans], np.float64),
            np.asarray([names.index(s[2]) for s in spans], np.int64),
            names)


def test_idle_goes_to_the_innermost_program_span():
    spans = _spans((0, 100, "bench.step"), (10, 90, "engine.step"),
                   (20, 60, "engine.decode"), (40, 60, "engine.decode.wait"),
                   (45, 50, "np.asarray(jax.Array)"),
                   (70, 80, "python.gc"))
    gaps = [(46e0, 48e0),          # in the fetch: the runtime event is no
            (21e0, 25e0),          # program span; in the decode
            (72e0, 75e0),          # a collection
            (92e0, 95e0),          # the harness, outside the engine's step
            (150e0, 160e0)]        # no span at all
    got = dict(programs.idle_by_span(gaps, spans))
    assert got == pytest.approx({
        "engine.decode.wait": 2e-9, "engine.decode": 4e-9,
        "python.gc": 3e-9, "bench.step": 3e-9, programs.OUTSIDE: 10e-9})
    assert programs.idle_by_span([], spans) == []


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "stablelm-3b.dense.xplane.pb.gz").read_bytes()))
    return programs.summarize(path), xplane.summarize(path)


def test_recorded_trace_groups_every_variant_under_one_program(recorded):
    s, x = recorded
    # the recorded program was still called `fn`: four compiled variants
    assert s.module_counts == {"fn": 7}
    assert s.busy_s == x.busy_s and s.window_s == x.window_s
    assert sum(s.module_seconds.values()) == pytest.approx(x.busy_s,
                                                           rel=0.05)
    # only the harness's spans were written then
    assert dict(s.idle_by_span) == pytest.approx(
        {"bench.step": x.window_s - x.busy_s}, rel=1e-6)


def _host_summary(trace_dir):
    """What ``xplane.summarize`` reads of the host in a CPU trace (which
    has no device plane): the window and the spans inside it."""
    pd = ProfileData.from_file(str(xplane.find_xplane(trace_dir)))
    w0, w1 = xplane._window(pd)
    lo, hi, idx, names = xplane._host_spans(pd, w0, w1)
    seconds, counts = {}, {}
    for a, b, i in zip(lo.clip(w0, w1), hi.clip(w0, w1), idx):
        seconds[names[i]] = seconds.get(names[i], 0.0) + (b - a) / 1e9
        counts[names[i]] = counts.get(names[i], 0) + 1
    return xplane.TraceSummary(
        window_s=(w1 - w0) / 1e9, busy_s=0.0, op_seconds={},
        staged_seconds={}, span_seconds=seconds, span_counts=counts,
        idle_gaps=[], devices=1)


@pytest.fixture(scope="module")
def traced_cpu_run(tmp_path_factory):
    """A smoke cell served with the profiler on, and for each of its
    ``step()`` calls whether it counted a step with a prefill."""
    prefilled = []

    def count_prefill_steps(engine):
        step = engine.step

        def counted():
            before = engine.counters["steps_with_prefill"]
            out = step()
            prefilled.append(engine.counters["steps_with_prefill"] - before)
            return out

        engine.step = counted

    trace_dir = tmp_path_factory.mktemp("cpu_trace")
    run, _ = harness.serve_cell(
        smoke_cell("stablelm-3b.fused.chat", "dense"), SEED, 0.5, peaks={},
        t_process=time.perf_counter(), trace_dir=trace_dir,
        engine_hook=count_prefill_steps)
    run.trace = _host_summary(trace_dir)
    return run, prefilled


def test_span_metrics_read_the_engine_spans(traced_cpu_run):
    run, prefilled = traced_cpu_run
    values = {m: spec.metric_module(m).compute(run) for m in SPAN_METRICS}
    counts = run.trace.span_counts
    # every window step decodes, and its decode is one engine.decode span
    assert counts["engine.decode"] == counts["engine.step"] == \
        len(run.step_s) > 0
    assert 0 < values["engine.host_ms"] < 1e3 * run.window.seconds
    # the spans' share is the counter's, over the window's steps
    window = prefilled[-len(run.step_s):]
    assert values["engine.prefill_step_share"] == pytest.approx(
        100.0 * sum(window) / len(window))
    assert 0 < values["engine.prefill_step_share"] <= 100


def test_span_metrics_need_a_trace_with_engine_spans():
    untraced = type("Run", (), {"trace": None})()
    before = type("Run", (), {"trace": xplane.TraceSummary(
        window_s=1.0, busy_s=1.0, op_seconds={}, staged_seconds={},
        span_seconds={"bench.step": 1.0}, span_counts={"bench.step": 3},
        idle_gaps=[], devices=1)})()
    for m in SPAN_METRICS:
        assert spec.metric_module(m).compute(untraced) is None
        assert spec.metric_module(m).compute(before) is None

