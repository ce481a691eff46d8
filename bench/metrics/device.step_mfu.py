"""The whole serving loop's share of the chip's peak: model operations of
every prefill and decode token processed in the traced window
(bench/metrics/work.py ``model_flops``) over the window's seconds times
the peak bf16 FLOP/s, in percent.  Moves ``out_tok_s``."""
from bench.metrics import work


def compute(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    flops = work.model_flops(run)
    if not flops:
        return None
    return 100.0 * flops / (run.trace.window_s
                            * run.peaks["bf16_flops_per_s"])
