"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests completed in the window, drawn from the seed and always
holding the longest, is run through the configuration's plain reference
(prompt followed by the served tokens).  The number compared is the
widest gap by which a served token's reference logit lies below the
reference's best logit at that position: 0 where the program picked the
reference's greedy token, small where rounding flipped a near tie, large
where the served path computed something else.  Its limit is the
configuration's ``check.max_logit_gap``, set from measured readings
(PERF.md).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

SAMPLE_TOKENS = 400     # served tokens the sample reaches, at least ...
SAMPLE_REQUESTS = 16    # ... unless it holds this many requests


def sample(served: list, seed: int) -> list:
    """``served``: ``(prompt, tokens)`` pairs in completion order.  The
    longest (most served tokens), then others in a seeded order until the
    sample holds :data:`SAMPLE_TOKENS` tokens or :data:`SAMPLE_REQUESTS`
    requests."""
    if not served:
        return []
    longest = max(range(len(served)), key=lambda i: len(served[i][1]))
    rest = [i for i in range(len(served)) if i != longest]
    order = np.random.default_rng([int(seed) % 2**64, 4]).permutation(
        len(rest))
    picked, tokens = [longest], len(served[longest][1])
    for j in order:
        if tokens >= SAMPLE_TOKENS or len(picked) >= SAMPLE_REQUESTS:
            break
        picked.append(rest[j])
        tokens += len(served[rest[j]][1])
    return [served[i] for i in picked]


@dataclasses.dataclass
class Verdict:
    correct: bool
    max_logit_gap: Optional[float]    # None: nothing was compared
    limit: float
    requests: int
    tokens: int

    def checks(self) -> dict:
        return {"max_logit_gap": {"value": self.max_logit_gap,
                                  "limit": self.limit}}


def reference(cell, seed: int):
    """The configuration's plain reference for ``seed``, sized to the
    traffic's longest sequence."""
    from bench import spec
    from bench.loadgen import max_length

    traffic = cell.traffic
    max_len = (max_length(traffic["prompt_len"])
               + max_length(traffic["output_len"]))
    return spec.reference_module(cell.config["reference"]).Reference(
        cell.config, seed, max_len=max_len)


def judge(gaps_of, served: list, seed: int, limit: float) -> Verdict:
    """``gaps_of(prompt, tokens)`` gives the gap at each served position:
    the reference's ``served_gaps`` for the program, or a control's."""
    picked = sample(served, seed)
    gaps = [gaps_of(p, t) for p, t in picked]
    tokens = int(sum(g.size for g in gaps))
    widest = max((float(g.max()) for g in gaps if g.size), default=None)
    return Verdict(correct=widest is not None and widest <= limit,
                   max_logit_gap=widest, limit=float(limit),
                   requests=len(picked), tokens=tokens)
