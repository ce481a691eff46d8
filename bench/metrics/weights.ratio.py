"""Raw bytes of the served weight tree over the bytes it holds on the
device (``runtime.streaming.stream_stats``; 1.0 for dense weights).
Moves ``hbm_in_use_gb``."""


def compute(run):
    return run.weight_stats["hbm_ratio"]
