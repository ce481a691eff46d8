import types

import pytest

from bench.harness import Leaf
from bench.metrics import work

PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_matmul_work_counts_operations_and_bytes_once():
    ops, nbytes = work.matmul(4, 8, 16, weight_bytes=256)
    assert ops == 2 * 4 * 8 * 16
    assert nbytes == 256 + 4 * 8 * 2 + 4 * 16 * 4


def test_least_time_takes_the_binding_bound():
    assert work.least_seconds(1000, 10, PEAKS) == pytest.approx(10.0)
    assert work.least_seconds(10, 1000, PEAKS) == pytest.approx(100.0)


def _run(leaves, decode_rows=(), prefill_lens=(), kernel_s=None,
         layers=2):
    arch = types.SimpleNamespace(n_layers=layers, d_model=8, vocab_size=32,
                                 n_heads=2, head_dim_=lambda: 4)
    trace = None
    if kernel_s is not None:
        trace = types.SimpleNamespace(
            kernel_seconds=lambda k: kernel_s.get(k, 0.0), window_s=1.0)
    return types.SimpleNamespace(
        leaves=leaves, decode_rows=list(decode_rows),
        decode_ctx=[10] * len(decode_rows), prefill_lens=list(prefill_lens),
        peaks=PEAKS, trace=trace, arch=arch)


def test_calls_follow_the_weight_format():
    leaves = [
        Leaf("period/0/mlp/w_up", "layer", "fused", 2, 8, 16, 512, 300),
        Leaf("embed", "embed", "stream", 1, 32, 8, 512, 400),
        Leaf("head", "head", "stream", 1, 8, 32, 512, 400),
    ]
    by = work.calls(_run(leaves, decode_rows=[4], prefill_lens=[6]))
    # the fused layer weight: its device bytes once per call, per layer
    assert sorted(by["enec_decompress_matmul"]) == sorted([
        (*work.matmul(4, 8, 16, 150), 2), (*work.matmul(6, 8, 16, 150), 2)])
    # the embedding decodes only the rows gathered; the head decodes whole
    assert sorted(by["enec_decode"]) == sorted([
        (0.0, (400 + 512) * 4 / 32, 1), (0.0, 400 + 512, 1),
        (0.0, (400 + 512) * 6 / 32, 1), (0.0, 400 + 512, 1)])
    # the head matmul: every decode row, but one row of a prefill
    assert sorted(by["tiled_matmul"]) == sorted([
        (*work.matmul(4, 8, 32, 512), 1), (*work.matmul(1, 8, 32, 512), 1)])


def test_dense_weights_run_the_tiled_kernel_and_raw_embeddings_none():
    leaves = [Leaf("period/0/attn/wq", "layer", "dense", 2, 8, 8, 256, 256),
              Leaf("embed", "embed", "raw", 1, 32, 8, 512, 512)]
    by = work.calls(_run(leaves, decode_rows=[3]))
    assert by == {"tiled_matmul": [(*work.matmul(3, 8, 8, 128), 2)]}


def test_roofline_share_is_least_time_over_kernel_time():
    leaves = [Leaf("period/0/attn/wq", "layer", "dense", 2, 8, 8, 256, 256)]
    least = 2 * work.least_seconds(*work.matmul(3, 8, 8, 128), PEAKS)
    run = _run(leaves, decode_rows=[3], kernel_s={"tiled_matmul": 4 * least})
    assert work.roofline_share(run, "tiled_matmul") == pytest.approx(25.0)
    # nothing to read: no trace, or a kernel the window never ran
    assert work.roofline_share(_run(leaves, [3]), "tiled_matmul") is None
    assert work.roofline_share(run, "enec_decode") is None


def test_model_flops_count_weights_head_and_causal_attention():
    leaves = [Leaf("period/0/attn/wq", "layer", "dense", 2, 8, 8, 256, 256)]
    run = _run(leaves, decode_rows=[3], prefill_lens=[5])
    per_layer = 8 * 8
    head = 8 * 32
    attn = 4.0 * 2 * 4 * 2
    decode = 2 * 3 * (2 * per_layer + head) + attn * 10
    prefill = 2 * 5 * 2 * per_layer + 2 * head + attn * 5 * 6 / 2
    assert work.model_flops(run) == pytest.approx(decode + prefill)
