"""Percent of the traced window's decode steps that ran a prefill first:
the engine's ``engine.admit`` spans (one in each step that admits a
request, ``runtime/engine.py``) over its ``engine.decode`` spans (one per
decode step).  With no deadlines set, as in the benchmark, a step admits
exactly when it prefills, so this is the window's share of
``Engine.counters["steps_with_prefill"]`` in ``steps``.  ``None`` where
the program writes no such span.  Moves ``itl_p95_ms``: above 5%, the
p95 token gap is a step that carried a prefill."""


def compute(run):
    if run.trace is None:
        return None
    counts = run.trace.span_counts
    steps = counts.get("engine.decode", 0)
    if not steps:
        return None
    return 100.0 * counts.get("engine.admit", 0) / steps
