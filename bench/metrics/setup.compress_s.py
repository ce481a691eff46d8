"""Seconds of ``assign_weight_modes`` (encoding the weights into the
cell's format, compiles included on a cold cache), host clock, weights
ready on the device.  Moves ``setup_s``."""


def compute(run):
    return run.compress_s
