"""ISSUE 7: the double-buffered decode-prefetch pipeline (runtime/overlap).

The pipeline is a pure scheduling transform: logits with overlap on/off
must be BIT-identical in every serving mode and family (the prefetch
decode is the same exact inverse of the lossless coder as the serial
per-leaf path, finished by the same moveaxis+astype, consumed by the same
canonical contraction).  The scan and unrolled drivers must agree, and the
per-step prefetch must cost exactly ``buckets_per_layer`` decode
dispatches — one batched decode per decoder bucket, never one per leaf.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.codec_api import Codec, use_codec
from repro.models import build_model
from repro.runtime.overlap import (build_schedule, decode_layer,
                                   overlap_enabled, pipeline_scan)
from repro.runtime.streaming import assign_weight_modes, stream_stats
from repro.runtime.weights import StreamedWeight, is_handle


def _serve(model, tree, pb, max_len, steps=2):
    logits, cache = model.prefill_fn(tree, pb, max_len)
    outs = [np.asarray(logits)]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(steps):
        logits, cache = model.decode_fn(tree, cache, tok)
        outs.append(np.asarray(logits))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    return outs


def _assert_bit_equal(ref, got, msg):
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32),
                                      err_msg=msg)


@pytest.mark.parametrize("arch,scan", [
    ("llama3_2_1b", True),          # dense, multi-period scan
    ("llama3_2_1b", False),         # dense, unrolled
    ("phi3_5_moe_42b_a6_6b", True),   # MoE: materialize-execution experts
    ("xlstm_125m", True),           # SSM: n_periods == 1 (epilogue-only)
])
def test_overlap_logits_bit_identical_stream_mode(arch, scan):
    cfg = dataclasses.replace(get_smoke_config(arch), scan_layers=scan)
    model_off = build_model(dataclasses.replace(cfg, overlap="off"))
    model_on = build_model(dataclasses.replace(cfg, overlap="on"))
    params = model_off.init(jax.random.key(0))
    tree = assign_weight_modes(params, mode="stream", min_bytes=1024,
                               shards=2)
    assert stream_stats(tree)["streamed_tensors"] > 0
    pb = {"tokens": jax.random.randint(jax.random.key(1), (2, 8), 0,
                                       cfg.vocab_size)}
    ref = _serve(model_off, tree, pb, 16)
    got = _serve(model_on, tree, pb, 16)
    _assert_bit_equal(ref, got, f"{arch} scan={scan} overlap on vs off")


@pytest.mark.parametrize("mode", ["dense", "stream", "fused"])
def test_overlap_logits_bit_identical_all_modes(mode):
    """--overlap on is safe in EVERY weight-execution mode: with no
    streamed leaves (dense; fused without materialize-leaves) the pipeline
    disables itself, with streams it reschedules without changing bits."""
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True)
    model_off = build_model(dataclasses.replace(cfg, overlap="off"))
    model_on = build_model(dataclasses.replace(cfg, overlap="on"))
    params = model_off.init(jax.random.key(0))
    tree = assign_weight_modes(params, mode=mode, min_bytes=1024, shards=2)
    pb = {"tokens": jax.random.randint(jax.random.key(1), (2, 8), 0,
                                       cfg.vocab_size)}
    _assert_bit_equal(_serve(model_off, tree, pb, 16),
                      _serve(model_on, tree, pb, 16),
                      f"mode={mode} overlap on vs off")


def test_overlap_one_layer_per_body_bit_identical(monkeypatch):
    """The TPU schedule of the pipelined scan (one layer per scan body,
    ``overlap.default_unroll`` there) keeps the overlap-on/off bit
    identity of the windowed schedule other backends use."""
    import repro.runtime.overlap as overlap_mod
    monkeypatch.setattr(overlap_mod, "default_unroll", lambda n: 1)
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True, n_layers=3)
    model_off = build_model(dataclasses.replace(cfg, overlap="off"))
    model_on = build_model(dataclasses.replace(cfg, overlap="on"))
    params = model_off.init(jax.random.key(0))
    tree = assign_weight_modes(params, mode="stream", min_bytes=1024,
                               shards=2)
    pb = {"tokens": jax.random.randint(jax.random.key(1), (2, 8), 0,
                                       cfg.vocab_size)}
    _assert_bit_equal(_serve(model_off, tree, pb, 16),
                      _serve(model_on, tree, pb, 16),
                      "one layer per pipelined body vs serial scan")


def test_overlap_scan_unrolled_parity():
    """scan and unrolled pipelined drivers agree numerically; bit-equality
    across the two drivers is NOT required (XLA fuses — and rounds —
    scan-body math differently from inlined math, so even the SERIAL scan
    and unrolled drivers differ in final bits).  The hard bit-identity
    contract is overlap-vs-serial under the SAME driver, covered above."""
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              overlap="on")
    model_s = build_model(dataclasses.replace(cfg, scan_layers=True))
    model_u = build_model(dataclasses.replace(cfg, scan_layers=False))
    params = model_s.init(jax.random.key(0))
    tree = assign_weight_modes(params, mode="stream", min_bytes=1024,
                               shards=2)
    pb = {"tokens": jax.random.randint(jax.random.key(1), (2, 8), 0,
                                       cfg.vocab_size)}
    for a, b in zip(_serve(model_s, tree, pb, 16),
                    _serve(model_u, tree, pb, 16)):
        np.testing.assert_allclose(a.astype(np.float64),
                                   b.astype(np.float64),
                                   rtol=5e-2, atol=5e-2,
                                   err_msg="overlap scan vs unrolled")


def test_prefetch_costs_buckets_per_layer_dispatches():
    """The per-step prefetch is O(#decoder buckets per layer): tracing the
    pipelined decode step issues 2*B + E decode dispatches under scan
    (prologue + one body trace) and P*B + E unrolled, where B is the
    schedule's bucket count and E the flat (embed/head) decodes outside
    the layer loop — never one dispatch per streamed leaf per layer."""
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              overlap="on")
    codec = Codec()
    model_s = build_model(dataclasses.replace(cfg, scan_layers=True))
    model_u = build_model(dataclasses.replace(cfg, scan_layers=False))
    params = model_s.init(jax.random.key(0))
    with use_codec(codec):
        tree = assign_weight_modes(params, mode="stream", min_bytes=1024,
                                   shards=2, codec=codec)
        sched = build_schedule(tree["period"], cfg.n_layers, codec=codec)
        n_leaves = len(sched.slots)
        B = sched.buckets_per_layer
        assert 1 <= B <= n_leaves
        logits, cache = model_s.prefill_fn(tree, {"tokens": jnp.zeros(
            (1, 4), jnp.int32)}, 8)
        tok = jnp.zeros((1,), jnp.int32)

        # flat-handle decodes outside the layer loop (embed; tied head)
        codec.reset_decode_cache_stats()
        jax.eval_shape(lambda t: t["embed"].materialize(),
                       {"embed": tree["embed"]})
        E = codec.decode_cache_stats()["dispatches"]
        assert isinstance(tree["embed"], StreamedWeight)
        assert E >= 1

        codec.reset_decode_cache_stats()
        jax.eval_shape(model_s.decode_fn, tree, cache, tok)
        d_scan = codec.decode_cache_stats()["dispatches"]
        assert d_scan == 2 * B + E, (d_scan, B, E)

        codec.reset_decode_cache_stats()
        jax.eval_shape(model_u.decode_fn, tree, cache, tok)
        d_unr = codec.decode_cache_stats()["dispatches"]
        assert d_unr == cfg.n_layers * B + E, (d_unr, B, E)


def test_decode_layer_matches_materialize_bit_exact():
    """The batched exact-bucketed prefetch decode of one layer is
    bit-identical to per-leaf StreamedWeight.materialize on the slice."""
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tree = assign_weight_modes(params, mode="stream", min_bytes=1024,
                               shards=2)
    sched = build_schedule(tree["period"], cfg.n_layers)
    for layer in range(cfg.n_layers):
        decs = decode_layer(sched, layer)
        for slot, got in zip(sched.slots, decs):
            h = sched.leaves[slot]
            ref = jax.tree.map(lambda a: a[layer], h).materialize()
            np.testing.assert_array_equal(
                np.asarray(got).view(np.uint8),
                np.asarray(ref).view(np.uint8),
                err_msg=f"layer {layer} slot {slot}")


def test_overlap_enabled_policy():
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    streamed = assign_weight_modes(params, mode="stream", min_bytes=1024,
                                   shards=2)["period"]
    dense = assign_weight_modes(params, mode="dense",
                                min_bytes=1024)["period"]
    assert overlap_enabled("on", streamed)
    assert overlap_enabled("auto", streamed)
    assert not overlap_enabled("off", streamed)
    # nothing to prefetch -> auto/on degrade to the serial loop
    assert not overlap_enabled("auto", dense)
    assert not overlap_enabled("on", dense)
    with pytest.raises(ValueError):
        overlap_enabled("sideways", streamed)


def test_stream_stats_overlap_counters():
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tree = assign_weight_modes(params, mode="stream", min_bytes=1024,
                               shards=2)
    st = stream_stats(tree)
    assert st["flat_stream_tensors"] >= 1        # embed streams as L=1
    assert st["overlap_eligible_tensors"] >= 1   # period streams prefetch
    assert st["streamed_tensors"] == (st["flat_stream_tensors"]
                                      + st["overlap_eligible_tensors"])
    flats = [leaf for leaf in jax.tree.leaves(tree, is_leaf=is_handle)
             if isinstance(leaf, StreamedWeight) and leaf.flat]
    assert len(flats) == st["flat_stream_tensors"]


def test_pipeline_scan_xs_extra_and_ys_shape():
    """pipeline_scan stacks ys over all P layers exactly like lax.scan."""
    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True, n_layers=3)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tree = assign_weight_modes(params, mode="stream", min_bytes=1024,
                               shards=2)
    sched = build_schedule(tree["period"], cfg.n_layers)
    xs = jnp.arange(cfg.n_layers, dtype=jnp.float32)

    def apply_fn(carry, _sliced, extra, _i):
        return carry + extra, carry

    carry, ys = pipeline_scan(sched, apply_fn, jnp.float32(0), xs_extra=xs)
    assert float(carry) == float(xs.sum())
    np.testing.assert_allclose(np.asarray(ys), [0.0, 0.0, 1.0])
