"""Share of its roofline that the ``enec_decode`` kernel reaches in the traced
window: the least time of the calls the window needed (bench/metrics/
work.py) over the kernel's device time in the trace, in percent.  Moves
``out_tok_s``."""
from bench.metrics import work


def compute(run):
    return work.roofline_share(run, "enec_decode")
