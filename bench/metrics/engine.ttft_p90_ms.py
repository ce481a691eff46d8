"""Nearest-rank p90, in milliseconds, of the time from ``Engine.submit``
to the first token over every request whose first token fell in the
window.  With some 50-80 first tokens a window, p90 sits on the edge
between requests prefilled alone and those behind another prefill, and
the seed's order picks the side, so it is no end-to-end metric here.
Moves ``itl_p95_ms``: the same prefills stretch the step they run in."""
from bench.window import percentile


def compute(run):
    ttft = percentile(run.window.ttft_s, 90)
    return None if ttft is None else 1e3 * ttft
