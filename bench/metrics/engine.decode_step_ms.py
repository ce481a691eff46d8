"""Mean milliseconds of the engine's decode steps in the window, as the
engine times them (``Engine.step_times_s``: the batched step up to its
token fetch).  Moves ``out_tok_s``."""


def compute(run):
    if not run.step_s:
        return None
    return 1e3 * sum(run.step_s) / len(run.step_s)
