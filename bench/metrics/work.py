"""The work a kernel call needs, counted from shapes, and the roofline
arithmetic the per-layer metrics share.

Counted is what the call needs, never what an implementation happens to
read or compute:

* a matmul ``(M, K) @ (K, N)``: ``2*M*K*N`` operations; the weight's bytes
  once, at the device size of the array passed at run time (dense bf16,
  or the ENEC streams of a fused weight), the bf16 activations in and the
  float32 result out;
* an ENEC decode feeding a matmul: the weight's device bytes in and its
  dense bytes out; of the embedding, only the rows the step gathers;
* ``M`` is the rows the call serves: the requests a decode step advanced,
  the prompt of a prefill, and one row for the head of a prefill.

The calls of a window follow from the serving tree's leaves
(``harness.Leaf``) and the window's decode steps and prefills: every layer
runs each of its weight matmuls once per step and once per prefill.  A
kernel's roofline share is the least time of its calls (each call the
larger of operations over peak FLOP/s and bytes over peak bandwidth)
over the device time the trace gives the kernel, the slices that stage
its operands included (``bench/xplane.py``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

ACT_BYTES = 2     # bf16 activations in
OUT_BYTES = 4     # float32 matmul results out

Call = Tuple[float, float, int]     # (operations, bytes, how many)


def matmul(m: int, k: int, n: int, weight_bytes: float) -> Tuple[float, float]:
    return (2.0 * m * k * n,
            weight_bytes + m * k * ACT_BYTES + m * n * OUT_BYTES)


def decode(device_bytes: float, dense_bytes: float) -> Tuple[float, float]:
    return 0.0, device_bytes + dense_bytes


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def _passes(run) -> List[Tuple[int, int, bool]]:
    """(rows, how many, is_prefill) for every pass through the model in
    the window: decode steps by rows, prefills by prompt length."""
    out: Dict[Tuple[int, bool], int] = {}
    for rows in run.decode_rows:
        out[(rows, False)] = out.get((rows, False), 0) + 1
    for plen in run.prefill_lens:
        out[(plen, True)] = out.get((plen, True), 0) + 1
    return [(rows, n, pre) for (rows, pre), n in sorted(out.items())]


def calls(run) -> Dict[str, List[Call]]:
    """Every kernel call the window's passes need, by kernel name."""
    by: Dict[str, List[Call]] = {}

    def add(kernel, work, count):
        by.setdefault(kernel, []).append((work[0], work[1], count))

    for rows, times, prefill in _passes(run):
        for leaf in run.leaves:
            n_calls = times * leaf.layers
            dev = leaf.device_bytes / leaf.layers
            raw = leaf.raw_bytes / leaf.layers
            if leaf.role == "embed":
                if leaf.kind == "stream":
                    share = rows / leaf.k
                    add("enec_decode", decode(dev * share, raw * share),
                        n_calls)
                continue
            m = 1 if (prefill and leaf.role == "head") else rows
            if leaf.kind == "fused":
                add("enec_decompress_matmul", matmul(m, leaf.k, leaf.n, dev),
                    n_calls)
                continue
            if leaf.kind == "stream":
                add("enec_decode", decode(dev, raw), n_calls)
            add("tiled_matmul", matmul(m, leaf.k, leaf.n, raw), n_calls)
    return by


def roofline_share(run, kernel: str) -> Optional[float]:
    """Percent of the kernel's traced device time that its calls' least
    time fills; ``None`` where the trace or the window has no such call."""
    if run.trace is None:
        return None
    seconds = run.trace.kernel_seconds(kernel)
    need = calls(run).get(kernel)
    if not seconds or not need:
        return None
    least = sum(c * least_seconds(o, b, run.peaks) for o, b, c in need)
    return 100.0 * least / seconds


def model_flops(run) -> float:
    """Operations the model needs for every token the window processed:
    the weight matmuls (the head once per prefill) and attention over
    each token's causal context."""
    a = run.arch
    # every layer leaf's (k, n) is one layer's weight
    layer_params = sum(leaf.k * leaf.n for leaf in run.leaves
                       if leaf.role == "layer")
    head_params = a.d_model * a.vocab_size
    attn = 4.0 * a.n_heads * a.head_dim_() * a.n_layers  # per context slot
    total = 0.0
    for rows, ctx in zip(run.decode_rows, run.decode_ctx):
        total += 2.0 * rows * (a.n_layers * layer_params + head_params)
        total += attn * ctx
    for p in run.prefill_lens:
        total += 2.0 * p * a.n_layers * layer_params + 2.0 * head_params
        total += attn * p * (p + 1) / 2.0
    return total
