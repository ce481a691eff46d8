"""Every layer driver of the decode step (``lax.scan`` or unrolled, the
weight-prefetch pipeline on or off) in every weight mode serves, through
the engine's in-place KV ring, the tokens of a full-sequence forward, with
the same logit bits in all three weight modes."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model, lm
from repro.models.layers import lm_logits
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.streaming import assign_weight_modes

PROMPT_LEN = 6
N_NEW = 8


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3_2_1b")
    params = build_model(cfg).init(jax.random.key(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (2, PROMPT_LEN), 0, cfg.vocab_size), np.int32)
    return cfg, params, prompts


def _serve_staggered(model, tree, prompts):
    """Slot 0 decodes alone, then a request with a shorter prompt is
    installed in slot 1 mid-run: a bucket of 2 on a 4-slot ring, rows of
    unequal lengths, and a free row inside the bucket once slot 0 is done."""
    # compiles inside the first steps are no load: the governor is held off
    engine = Engine(model, tree, EngineConfig(
        max_slots=4, max_prompt_len=PROMPT_LEN, max_new_tokens=N_NEW,
        collect_logits=True, watchdog_s=float("inf"),
        overload_factor=float("inf")))
    first = engine.submit(prompts[0], N_NEW, name="first")
    for _ in range(3):
        engine.step()
    late = engine.submit(prompts[1][:3], N_NEW, name="late")
    engine.run_until_idle()
    assert engine.stats()["engine"]["compiled_buckets"] == [1, 2]
    for req in (first, late):
        assert req.state == "done", (req.state, req.detail)
    return [(r.prompt, r.tokens, np.stack(r.logits)) for r in (first, late)]


_SERVED: dict = {}


def _served(setup, scan, overlap, mode):
    key = (scan, overlap, mode)
    if key not in _SERVED:
        base, params, prompts = setup
        cfg = dataclasses.replace(base, scan_layers=scan, overlap=overlap)
        tree = assign_weight_modes(params, mode=mode, min_bytes=1024,
                                   shards=2)
        _SERVED[key] = (cfg, _serve_staggered(build_model(cfg), tree,
                                              prompts))
    return _SERVED[key]


@pytest.mark.parametrize("mode", ["dense", "stream", "fused"])
@pytest.mark.parametrize("overlap", ["on", "off"])
@pytest.mark.parametrize("scan", [True, False])
def test_every_layer_driver_serves_the_full_forward_tokens(setup, scan,
                                                          overlap, mode):
    """Every layer driver (scan or unrolled, prefetch pipeline or not) in
    every weight mode: the served logits lie within bf16 noise (the 0.1
    of tests/test_models.py) of a full-sequence forward over each
    request's prompt and tokens, each greedy token is the forward's best
    within that noise, and the logits are the same bits in all three
    weight modes."""
    _, params, _ = setup
    cfg, served = _served(setup, scan, overlap, mode)
    for prompt, tokens, logits in served:
        seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        x, _, _, head, _ = lm.forward(params, cfg, {"tokens": seq[None]})
        ref = np.asarray(lm_logits(x, head)[0, len(prompt) - 1:])
        assert logits.shape == ref.shape
        assert float(np.abs(logits - ref).max()) < 0.1
        best = ref.max(-1)
        chosen = ref[np.arange(len(tokens)), tokens]
        assert float((best - chosen).max()) < 0.1
    _, dense = _served(setup, scan, overlap, "dense")
    for (_, toks, got), (_, ref_toks, ref) in zip(served, dense):
        assert toks == ref_toks
        np.testing.assert_array_equal(got.view(np.uint32),
                                      ref.view(np.uint32), err_msg=mode)
