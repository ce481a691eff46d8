"""``correct`` comes out false when the timed path is broken underneath,
and the float8-weight control reads above the committed limit.  A run is
driven as the benchmark drives it, minus the look for a chip."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

import bench.run as bench_run
from bench import check, harness, spec
from bench.tests.smoke import smoke_cell

SEED = 2**31 + 5
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture
def no_chip_peaks(monkeypatch):
    monkeypatch.setattr(spec, "peaks", lambda kind: PEAKS)


def measure(workload, fault=None, weights=None):
    cell = smoke_cell(workload, weights)
    return bench_run.measure(cell, SEED, 0.5, False, jax.devices(),
                             t_process=time.perf_counter(),
                             engine_hook=fault)


def _wrap_step(engine, change):
    """Every decode step's outputs pass through ``change``."""
    make = engine._step_fn

    def step_fn(bucket):
        fn = make(bucket)
        return lambda *args: change(args, *fn(*args))

    engine._step_fn = step_fn


def token_altered(engine):
    vocab = engine.cfg.vocab_size
    _wrap_step(engine, lambda args, tok, logits, entries:
               ((tok + 1) % vocab, logits, entries))


def state_unchanged(engine):
    _wrap_step(engine, lambda args, tok, logits, entries:
               (tok, logits, args[1]))


def decoded_weights_altered(engine):
    """The first compressed leaf decodes with every raw byte's top bit
    flipped."""
    from repro.runtime.weights import FusedWeight, StreamedWeight, is_handle

    leaves, treedef = jax.tree_util.tree_flatten(engine.params,
                                                 is_leaf=is_handle)
    i = next(i for i, x in enumerate(leaves)
             if isinstance(x, (FusedWeight, StreamedWeight)))
    ct = leaves[i].ct
    streams = ct.streams._replace(raw=ct.streams.raw ^ jnp.uint8(0x80))
    leaves[i] = dataclasses.replace(
        leaves[i], ct=dataclasses.replace(ct, streams=streams))
    engine.params = jax.tree_util.tree_unflatten(treedef, leaves)


def test_sound_run_is_correct(no_chip_peaks):
    res = measure("qwen3-32b.stream.chat")
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   decoded_weights_altered])
def test_broken_path_is_not_correct(no_chip_peaks, fault):
    res = measure("qwen3-32b.stream.chat", fault)
    assert res["correct"] is False, res["checks"]


def test_float8_control_fails_the_committed_limit():
    """The control: the reference with float8 weights in the program's
    place, read at the positions of tokens the program served and judged
    by the benchmark's own comparison."""
    cell = smoke_cell("stablelm-3b.fused.chat", "dense")
    run, served = harness.serve_cell(cell, SEED, 0.5, peaks={},
                                     t_process=0.0)
    ref = spec.reference_module("dense_decoder").Reference(
        cell.config, SEED, max_len=16 + 16)
    limit = spec.cell("stablelm-3b.fused.chat").config["check"][
        "max_logit_gap"]
    program = check.judge(ref.served_gaps, served, SEED, limit)
    control = check.judge(lambda p, t: ref.control_gaps(p, t)[1], served,
                          SEED, limit)
    assert program.correct and program.tokens == control.tokens
    assert not control.correct, control


def test_a_stalled_step_rejects_no_request():
    """A closed loop cannot overload the engine: a step that stands still
    is listed as a stall and costs time, but no client's next request is
    rejected for it."""
    calls = []

    def stall_every_fourth(engine):
        def change(args, *out):
            calls.append(1)
            if len(calls) % 4 == 0:
                time.sleep(0.05)
            return out

        _wrap_step(engine, change)

    cell = smoke_cell("stablelm-3b.fused.chat", "dense")
    run, served = harness.serve_cell(cell, SEED, 0.5, peaks={},
                                     t_process=0.0,
                                     engine_hook=stall_every_fourth)
    assert served and run.stalls_ms
    assert run.window.failed == 0 and run.window.attempted > 0
