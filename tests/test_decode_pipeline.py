"""Batched decompression pipeline (ISSUE 4 tentpole).

Covers: ``decompress_stacked_many`` parity vs the per-leaf path
(bit-identical, all formats, shards > 1, const/raw leaves mixed into the
batch), decoder compile-cache bucketing and hit/miss accounting, the Pallas
decode backend driving the same stacked path, the segment-local gather's
edge cases (all-anomaly / zero-anomaly / tail-padded blocks), batched
checkpoint restore dispatch counts, and the ``ops.idd_scan`` backend
resolution regression (ISSUE 4 satellite).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_realistic_bf16
from repro.core import api as enec_api
from repro.core import codec, params as params_mod
from repro.core.dtypes import BF16, format_for
from repro.core.params import EnecParams
from repro.kernels import ops, ref


def _bits(x):
    dt = np.uint16 if x.dtype != jnp.float32 else np.uint32
    return np.asarray(jax.device_get(x)).view(dt)


def _make(n, seed, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal(n) * 0.02
    w[rng.random(n) < 2e-3] *= 64.0
    return jnp.asarray(w.astype(np.float32)).astype(dtype)


def _make_stack(n_layers=3, per_layer=160_000, shape=(400, 400)):
    xs = jnp.stack([make_realistic_bf16(per_layer, seed=i + 20)
                    for i in range(n_layers)])
    return xs.reshape((n_layers,) + shape)


# ---------------------------------------------------------------------------
# decompress_stacked_many: parity with the per-leaf path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32])
def test_batched_decode_parity_per_leaf(dtype):
    xs = [_make(40_000, 1, dtype), _make(70_000, 2, dtype)]
    cts = [enec_api.compress_array(x) for x in xs]
    outs = enec_api.decompress_stacked_many(cts)
    for x, ct, out in zip(xs, cts, outs):
        ref_out = enec_api.decompress_array(ct)
        assert out.shape == x.shape and out.dtype == x.dtype
        np.testing.assert_array_equal(_bits(out), _bits(ref_out))
        np.testing.assert_array_equal(_bits(out), _bits(x))


def test_batched_decode_parity_mixed_stacked_shards_const_raw():
    xs = _make_stack()
    stacked = enec_api.compress_stacked(xs)
    sharded = enec_api.compress_array(_make(7 * 16384, 3), shards=4)
    plain = enec_api.compress_array(_make(50_000, 4))
    const = enec_api.compress_array(jnp.full((257,), 1.5, jnp.bfloat16))
    raw = enec_api.compress_array(jnp.arange(13, dtype=jnp.int32))
    batch = [None, stacked, sharded, const, plain, raw]
    outs = enec_api.decompress_stacked_many(batch)
    assert outs[0] is None
    np.testing.assert_array_equal(_bits(outs[1]), _bits(xs))
    np.testing.assert_array_equal(
        _bits(outs[2]), _bits(enec_api.decompress_array(sharded)))
    np.testing.assert_array_equal(
        _bits(outs[3]), _bits(jnp.full((257,), 1.5, jnp.bfloat16)))
    np.testing.assert_array_equal(
        _bits(outs[4]), _bits(enec_api.decompress_array(plain)))
    np.testing.assert_array_equal(np.asarray(outs[5]), np.arange(13))


def test_batched_decode_tail_single_element():
    # last block holds ONE valid element; the rest is encode padding that
    # the decode must slice away exactly
    x = _make(2 * 16384 + 1, 5)
    ct = enec_api.compress_array(x)
    out = enec_api.decompress_stacked_many([ct])[0]
    np.testing.assert_array_equal(_bits(out), _bits(x))


def test_batched_decode_shares_one_dispatch_across_params():
    # distinct tensors with distinct (b, l) but equal (n, m, L) must share
    # ONE concatenated decode dispatch ((b, l) ride as traced per-block
    # vectors on the reference backend)
    xs = [_make(60_000, 6), _make(90_000, 7)]
    ps = []
    for x in xs:
        exp = (_bits(x) >> 7) & 0xFF
        ps.append(EnecParams(b=int(exp.max()), n=6, m=3, L=16,
                             l=int(exp.min())))
    assert (ps[0].b, ps[0].l) != (ps[1].b, ps[1].l)
    cts = [enec_api.compress_array(x, p) for x, p in zip(xs, ps)]
    assert all(ct.mode == "enec" for ct in cts)
    enec_api.reset_decode_cache_stats()
    outs = enec_api.decompress_stacked_many(cts)
    assert enec_api.decode_cache_stats()["dispatches"] == 1
    for x, out in zip(xs, outs):
        np.testing.assert_array_equal(_bits(out), _bits(x))


def test_decompress_tree_batches_dispatches():
    tree = {"a": _make(70_000, 8), "b": _make(90_000, 9),
            "c": jnp.arange(5, dtype=jnp.int32)}
    ctree = enec_api.compress_tree(tree)
    enec_api.reset_decode_cache_stats()
    out = enec_api.decompress_tree(ctree)
    assert enec_api.decode_cache_stats()["dispatches"] == 1
    np.testing.assert_array_equal(_bits(out["a"]), _bits(tree["a"]))
    np.testing.assert_array_equal(np.asarray(out["c"]), np.arange(5))


# ---------------------------------------------------------------------------
# decoder compile-cache hygiene
# ---------------------------------------------------------------------------

def test_decoder_cache_buckets_block_counts():
    p = params_mod.search_for_array(
        np.asarray(jax.device_get(make_realistic_bf16(100_000))), BF16)
    ct3 = enec_api.compress_array(make_realistic_bf16(3 * 16384, seed=1), p)
    ct4 = enec_api.compress_array(make_realistic_bf16(4 * 16384, seed=2), p)
    enec_api.reset_decode_cache_stats(clear_cache=True)
    enec_api.decompress_array(ct3)
    enec_api.decompress_array(ct4)
    st = enec_api.decode_cache_stats()
    # 3 blocks buckets up to 4: both tensors share one compiled decoder
    assert st["compiles"] == 1 and st["dispatches"] == 2, st
    assert st["cache_hits"] == 1 and st["padded_blocks"] == 1


def test_decode_cache_stats_reset_and_unknown_backend():
    enec_api.reset_decode_cache_stats()
    st = enec_api.decode_cache_stats()
    assert st["dispatches"] == 0 and st["backend"] == "reference"
    with pytest.raises(ValueError):
        enec_api.set_decode_backend("cuda")


def test_pallas_decode_backend_stacked_parity():
    xs = jnp.stack([make_realistic_bf16(1024, seed=i) for i in range(2)])
    p = params_mod.search_for_array(np.asarray(jax.device_get(xs)), BF16,
                                    block_elems=256)
    ct = enec_api.compress_stacked(xs, p, block_elems=256)
    ref_out = enec_api.decompress_stacked(ct)
    try:
        enec_api.set_decode_backend("pallas")
        enec_api.reset_decode_cache_stats()
        out = enec_api.decompress_stacked_many([ct])[0]
        st = enec_api.decode_cache_stats()
        assert st["backend"] == "pallas" and st["dispatches"] == 1
    finally:
        enec_api.set_decode_backend("reference")
    np.testing.assert_array_equal(_bits(out), _bits(ref_out))
    np.testing.assert_array_equal(_bits(out), _bits(xs))


# ---------------------------------------------------------------------------
# segment-local gather vs the jnp oracle (decode kernel edge cases)
# ---------------------------------------------------------------------------

def _kernel_vs_oracle(x, p, n_elems):
    bits = codec.to_blocks(x, BF16, n_elems)
    s = codec.encode_blocks(bits, BF16, p)
    got = ops.decode_blocks(s, n_elems, BF16, p)                # Pallas
    want = ref.decode_blocks_ref(s, n_elems, BF16, p)           # jnp oracle
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(bits))
    return s


def test_segment_gather_all_anomalous():
    # every element shares one exponent and b sits below it, so y = 2^n - 1
    # everywhere: every group is anomalous, ranks run 0..G-1 and the
    # gather's 128-row windows slide across the full rank range
    n_elems, L = 4096, 16
    x = jnp.full((2 * n_elems,), 0.5, jnp.bfloat16)
    exp = int(_bits(x)[0] >> 7) & 0xFF
    p = EnecParams(b=exp - 1, n=6, m=3, L=L, l=exp - 1)
    s = _kernel_vs_oracle(x, p, n_elems)
    g = n_elems // L
    assert int(np.asarray(s.high_len)[0]) == g * L * (p.n - p.m)  # all anom


def test_segment_gather_zero_anomalies():
    # b equals the only exponent: y = 0 everywhere, mask empty, the gather
    # must produce all zeros (and the high stream carries no set bits)
    n_elems, L = 4096, 16
    x = jnp.full((2 * n_elems,), 0.5, jnp.bfloat16)
    exp = int(_bits(x)[0] >> 7) & 0xFF
    p = EnecParams(b=exp, n=6, m=3, L=L, l=exp)
    s = _kernel_vs_oracle(x, p, n_elems)
    assert int(np.asarray(s.high_len).sum()) == 0


def test_segment_gather_tail_padded_block():
    # ONE real element, the rest of the single block is zero padding whose
    # exponent (0) sits far from b — every pad group is anomalous while the
    # real element's group is not, so the gather's window starts sweep the
    # whole rank range with a one-element head
    n_elems = 4096
    x = jnp.full((1,), 0.5, jnp.bfloat16)
    exp = int(_bits(x)[0] >> 7) & 0xFF
    flat = jnp.concatenate([x, jnp.zeros((n_elems - 1,), jnp.bfloat16)])
    bits = jnp.ravel(flat).view(jnp.uint16)[None, :]
    p = EnecParams(b=exp, n=8, m=3, L=16, l=0)   # injective on [0, exp]
    s = codec.encode_blocks(bits, BF16, p)
    got = ops.decode_blocks(s, n_elems, BF16, p)
    want = ref.decode_blocks_ref(s, n_elems, BF16, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(bits))


@pytest.mark.parametrize("nblocks", [8, 20, 6])
def test_decode_kernel_multiple_blocks_per_grid_step(nblocks):
    # one block per sublane row, 8 per grid step; 20 blocks leave a partial
    # last step whose padding rows are never written back, and fewer than 8
    # blocks decode in a single step of exactly that many rows
    from repro.kernels.lanes import step_rows
    assert step_rows(nblocks) == min(8, nblocks)
    n_elems = 1024
    x = _make(nblocks * n_elems, 12)
    p = params_mod.search_for_array(np.asarray(jax.device_get(x)), BF16,
                                    block_elems=n_elems)
    bits = codec.to_blocks(x, BF16, n_elems)
    s = codec.encode_blocks(bits, BF16, p)
    got = ops.decode_blocks(s, n_elems, BF16, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(bits))


# ---------------------------------------------------------------------------
# consumers: whole-tree materialization + checkpoint restore stay batched
# ---------------------------------------------------------------------------

def test_materialize_weight_tree_batched_and_bit_exact():
    from repro.runtime.streaming import (compress_params_for_streaming,
                                         materialize_weight_tree)
    params = {"period": [{"wq": _make_stack(4), "wk": _make_stack(4),
                          "norm": jnp.ones((4, 400), jnp.bfloat16)}]}
    streamed = compress_params_for_streaming(params, min_bytes=1024,
                                             shards=2)
    assert sum(1 for l in jax.tree.leaves(
        streamed, is_leaf=lambda x: hasattr(x, "ct"))
        if hasattr(l, "ct")) == 2
    enec_api.reset_decode_cache_stats()
    out = materialize_weight_tree(streamed)
    st = enec_api.decode_cache_stats()
    assert st["dispatches"] == 1, st   # wq + wk share one decoder bucket
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        np.testing.assert_array_equal(_bits(a), _bits(b))


def test_ckpt_restore_batched_decode(tmp_path):
    from repro.checkpoint.ckpt import CheckpointManager
    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True, n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tree = {"params": params}
    mgr = CheckpointManager(tmp_path, serving_layout="fused",
                            serving_min_bytes=1024)
    mgr.save(1, tree, blocking=True)
    n_records = len(mgr.manifest()["leaves"])

    enec_api.reset_decode_cache_stats()
    out, _ = mgr.load(tree)
    st = enec_api.decode_cache_stats()
    # restore must cost O(#decoder buckets), never O(#records)
    assert st["dispatches"] < n_records / 2, (st, n_records)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(_bits(a), _bits(b))


# ---------------------------------------------------------------------------
# ops.idd_scan honors the backend selection (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_idd_scan_honors_encode_backend(monkeypatch):
    import repro.kernels.ops as ops_mod
    calls = []
    real = ops_mod._idd_scan_jit
    monkeypatch.setattr(
        ops_mod, "_idd_scan_jit",
        lambda x, up: (calls.append(up), real(x, up))[1])
    x = jnp.asarray((np.random.default_rng(0).random((2, 256)) < 0.3)
                    .astype(np.int32))
    out_ref_backend = ops_mod.idd_scan(x)
    assert calls[-1] is False             # default backend is "reference"
    try:
        enec_api.set_encode_backend("pallas")
        out_pallas_backend = ops_mod.idd_scan(x)
        assert calls[-1] is True
    finally:
        enec_api.set_encode_backend("reference")
    ops_mod.idd_scan(x, use_pallas=True)  # explicit override still wins
    assert calls[-1] is True
    np.testing.assert_array_equal(np.asarray(out_ref_backend),
                                  np.asarray(out_pallas_backend))
    np.testing.assert_array_equal(np.asarray(out_ref_backend),
                                  np.asarray(ref.idd_scan_ref(x)))


def test_idd_scan_kernel_interpret_default_resolves():
    from repro.kernels.idd_scan import idd_scan as raw_idd_scan
    x = jnp.ones((1, 256), jnp.int32)
    # on this (non-TPU) container the None default must resolve to the
    # interpreter and still produce the exact scan
    np.testing.assert_array_equal(
        np.asarray(raw_idd_scan(x)),
        np.asarray(ref.idd_scan_ref(x)))
