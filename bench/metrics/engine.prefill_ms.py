"""Mean milliseconds from admission to first token of the prefills that
started in the window (``Request.first_token_s - admit_s``): one batch-1
prefill and its slot install.  Moves ``itl_p95_ms``: the engine runs
prefills inside a step, ahead of the batch's decode."""


def compute(run):
    if not run.prefill_s:
        return None
    return 1e3 * sum(run.prefill_s) / len(run.prefill_s)
