"""Window accounting: what the clients saw between the window's opening
and closing, reduced to the end-to-end metrics.

All times are seconds on one host clock.  A token belongs to the window
when it reached its client inside ``(t_open, t_close]``; a request ended
in the window when its terminal state was observed there.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional


@dataclasses.dataclass
class RequestLog:
    """One request as its client saw it."""
    index: int
    prompt_len: int
    max_new_tokens: int
    submit_s: float
    admit_s: Optional[float] = None
    token_s: List[float] = dataclasses.field(default_factory=list)
    end_s: Optional[float] = None
    state: str = "queued"

    @property
    def first_token_s(self) -> Optional[float]:
        return self.token_s[0] if self.token_s else None


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of the samples at or below it.  ``None`` for no samples."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def inside(t: Optional[float], t_open: float, t_close: float) -> bool:
    return t is not None and t_open < t <= t_close


@dataclasses.dataclass
class WindowSummary:
    seconds: float
    tokens: int
    out_tok_s: float
    ttft_s: List[float]
    itl_s: List[float]
    attempted: int
    failed: int
    done: List[RequestLog]


def summarize(logs, t_open: float, t_close: float) -> WindowSummary:
    """A rate over the whole window, tails over every request and every
    token gap in it, failures counted against attempts."""
    seconds = t_close - t_open
    tokens = sum(inside(t, t_open, t_close) for r in logs for t in r.token_s)
    ttft = [r.first_token_s - r.submit_s for r in logs
            if inside(r.first_token_s, t_open, t_close)]
    itl = [b - a for r in logs for a, b in zip(r.token_s, r.token_s[1:])
           if inside(b, t_open, t_close)]
    ended = [r for r in logs if inside(r.end_s, t_open, t_close)]
    done = [r for r in ended if r.state == "done"]
    return WindowSummary(seconds=seconds, tokens=tokens,
                         out_tok_s=tokens / seconds, ttft_s=ttft, itl_s=itl,
                         attempted=len(ended),
                         failed=len(ended) - len(done), done=done)
