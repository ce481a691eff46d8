"""Mean milliseconds of host time per decode step in the traced window
with nothing queued on the device: the seconds of the engine's
``engine.step`` spans less the ``engine.prefill.wait`` and
``engine.decode.wait`` spans inside them (the host blocked on the device),
over the ``engine.decode`` spans (one per decode step).  Spans of
``runtime/engine.py``; ``None`` where the program writes none.  Moves
``out_tok_s``: the device waits while the host schedules."""


def compute(run):
    if run.trace is None:
        return None
    seconds, counts = run.trace.span_seconds, run.trace.span_counts
    steps = counts.get("engine.decode", 0)
    if not steps or "engine.step" not in seconds:
        return None
    host = (seconds["engine.step"] - seconds.get("engine.prefill.wait", 0.0)
            - seconds.get("engine.decode.wait", 0.0))
    return 1e3 * host / steps
