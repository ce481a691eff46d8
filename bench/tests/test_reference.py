"""The float32 reference against the program at smoke size on the CPU."""
import jax
import numpy as np
import pytest

from bench import check, harness, spec
from bench.tests.smoke import smoke_cell

SEED = 2**31 + 99
# served tokens' gaps at smoke size read about 0.01 in every weight format
# (bf16 activations through two layers); the float8-weight control reads
# 0.15 or more on the same tokens (test_faults.py)
TOL = 0.05


def test_reference_weights_are_the_programs():
    from repro.models import build_model

    cell = smoke_cell("qwen3-32b.stream.chat")
    ref = spec.reference_module("dense_decoder")
    s = ref.sizes(cell.config)
    w = ref.init_weights(SEED, s)
    params = build_model(harness.arch_config(cell.config)).init(
        jax.random.key(SEED % 2**64))
    np.testing.assert_array_equal(w["embed"], params["embed"])
    np.testing.assert_array_equal(w["head"], params["head"])
    period = params["period"][0]
    for name in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(w["layers"][name],
                                      period["attn"][name])
    for name in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(w["layers"][name], period["mlp"][name])


@pytest.mark.parametrize("workload,weights", [
    ("stablelm-3b.fused.chat", "dense"),
    ("qwen3-32b.stream.chat", "stream"),
    ("stablelm-3b.fused.chat", "fused"),
])
def test_engine_serves_the_references_greedy_tokens(workload, weights):
    cell = smoke_cell(workload, weights)
    run, served = harness.serve_cell(cell, SEED, 0.5, peaks={},
                                     t_process=0.0)
    assert run.mode == weights
    assert served and run.window.failed == 0
    ref = spec.reference_module("dense_decoder").Reference(
        cell.config, SEED, max_len=16 + 16)
    verdict = check.judge(ref.served_gaps, served, SEED, limit=TOL)
    assert verdict.tokens >= 100
    assert verdict.correct, verdict
