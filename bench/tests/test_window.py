import pytest

from bench.window import RequestLog, percentile, summarize


def _log(index, submit, tokens, end=None, state="running"):
    return RequestLog(index=index, prompt_len=8, max_new_tokens=len(tokens),
                      submit_s=submit, token_s=list(tokens), end_s=end,
                      state=state)


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert percentile(values, 90) == 90
    assert percentile(values, 95) == 95
    assert percentile([5.0], 90) == 5.0
    assert percentile([], 90) is None
    assert percentile([3, 1, 2], 50) == 2


def test_rate_is_over_the_whole_window():
    logs = [_log(0, 0.0, [1.0, 2.0, 3.0]), _log(1, 0.0, [11.5])]
    w = summarize(logs, t_open=0.5, t_close=10.5)
    # three tokens inside (0.5, 10.5], over all ten seconds of the window
    assert w.tokens == 3
    assert w.out_tok_s == pytest.approx(0.3)
    assert w.seconds == pytest.approx(10.0)


def test_tails_cover_every_request_and_gap_in_the_window():
    logs = [
        _log(0, 0.0, [0.4, 1.0, 2.5], end=2.5, state="done"),  # first before
        _log(1, 1.0, [3.0, 3.1], end=3.1, state="done"),
        _log(2, 2.0, [6.0], end=None),                          # in flight
    ]
    w = summarize(logs, t_open=0.5, t_close=5.0)
    # first tokens inside the window: request 1 only (request 0's came
    # before it opened, request 2's after it closed)
    assert w.ttft_s == pytest.approx([2.0])
    # gaps whose later token is inside: 0.6, 1.5 of request 0; 0.1 of 1
    assert sorted(w.itl_s) == pytest.approx([0.1, 0.6, 1.5])


def test_failures_count_against_attempts():
    logs = [
        _log(0, 0.0, [1.0, 2.0], end=2.0, state="done"),
        _log(1, 0.0, [], end=1.5, state="rejected"),
        _log(2, 0.0, [1.0], end=3.0, state="evicted"),
        _log(3, 0.0, [1.0], end=9.0, state="done"),   # ends after the close
    ]
    w = summarize(logs, t_open=0.5, t_close=5.0)
    assert (w.attempted, w.failed) == (3, 2)
    assert [r.index for r in w.done] == [0]
