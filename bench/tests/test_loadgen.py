from collections import Counter

import numpy as np
import pytest

from bench import spec
from bench.loadgen import ClosedLoop, check_traffic, lengths, max_length, \
    size_table

CHAT = spec.load_json(spec.BENCH_DIR / "traffic" / "chat.json")
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_same_seed_same_requests(seed):
    a, b = ClosedLoop(CHAT, 50304, seed), ClosedLoop(CHAT, 50304, seed)
    for client in (0, 5, 31):
        for k in range(3):
            ra, rb = a.for_client(client, k), b.for_client(client, k)
            assert ra.max_new_tokens == rb.max_new_tokens
            np.testing.assert_array_equal(ra.prompt, rb.prompt)


def test_seeds_differ_in_order_not_in_sizes():
    a, b = ClosedLoop(CHAT, 50304, 1), ClosedLoop(CHAT, 50304, 2)
    n = len(a.table)
    sizes = lambda loop: Counter((loop.request(j).prompt.size,  # noqa: E731
                                  loop.request(j).max_new_tokens)
                                 for j in range(n))
    assert sizes(a) == sizes(b)
    assert [a.request(j).max_new_tokens for j in range(32)] != \
        [b.request(j).max_new_tokens for j in range(32)]
    assert not np.array_equal(a.request(0).prompt[:16],
                              b.request(0).prompt[:16])


def _lognormal_share(mean, sd, lo, hi):
    """Mass of the log-normal with this mean and sd within (lo, hi]."""
    from statistics import NormalDist

    sigma2 = np.log1p((sd / mean) ** 2)
    mu, sigma = np.log(mean) - sigma2 / 2, np.sqrt(sigma2)
    cdf = lambda x: NormalDist().cdf((np.log(x) - mu) / sigma)  # noqa: E731
    return cdf(hi) - cdf(lo)


def test_table_follows_the_traffic_file():
    n = CHAT["table_size"]
    law = CHAT["prompt_len"]
    mean, sd = law["lognormal"]["mean"], law["lognormal"]["sd"]
    bins = law["bins"]
    counts = Counter(lengths(law, n))
    # each bin holds the law's mass between the geometric midpoints
    edges = [1e-9] + [np.sqrt(a * b) for a, b in zip(bins, bins[1:])] \
        + [1e12]
    for i, v in enumerate(bins):
        share = _lognormal_share(mean, sd, edges[i], edges[i + 1])
        assert abs(counts[v] - share * n) <= 1
    outs = lengths(CHAT["output_len"], n)
    lo, hi = CHAT["output_len"]["clip"]
    assert min(outs) == lo and max(outs) == hi
    # the clipped law's median is the log-normal's
    m, s = CHAT["output_len"]["lognormal"].values()
    median = m / np.sqrt(1 + (s / m) ** 2)
    assert np.median(outs) == pytest.approx(median, rel=0.01)
    assert len(size_table(CHAT)) == n


def test_chat_is_fitted_to_its_source():
    """Means within a few percent of the source's where the bins and the
    clip leave them (prompts), and below it by the clipped tail (outputs)."""
    n = CHAT["table_size"]
    assert "arXiv" in CHAT["source"]
    assert np.mean(lengths(CHAT["prompt_len"], n)) == pytest.approx(
        CHAT["prompt_len"]["lognormal"]["mean"], rel=0.05)
    out_mean = np.mean(lengths(CHAT["output_len"], n))
    assert 0.8 * CHAT["output_len"]["lognormal"]["mean"] < out_mean < \
        CHAT["output_len"]["lognormal"]["mean"]
    assert max_length(CHAT["prompt_len"]) + max_length(
        CHAT["output_len"]) == 1024


@pytest.mark.parametrize("change", [
    {"loop": "open"},
    {"rate_per_s": 4.0},
    {"prompt_len": {"lognormal": {"mean": 70, "sd": 140}}},
    {"output_len": {"values": [16, 32], "weights": [0.5, 0.5]}},
])
def test_traffic_it_would_not_serve_as_written_is_refused(change):
    with pytest.raises(ValueError):
        check_traffic({**CHAT, **change})
    with pytest.raises(ValueError):
        ClosedLoop({**CHAT, **change}, 50304, 0)


def test_tokens_cover_the_vocabulary_range():
    loop = ClosedLoop(CHAT, 1000, 3)
    toks = np.concatenate([loop.request(j).prompt for j in range(64)])
    assert toks.min() >= 0 and toks.max() < 1000
    assert toks.dtype == np.int32


def test_warmup_fills_the_ring_with_every_prompt_length():
    loop = ClosedLoop(CHAT, 50304, 0)
    reqs = loop.warmup(CHAT["slots"])
    assert len(reqs) == CHAT["slots"]
    assert {r.prompt.size for r in reqs} == set(CHAT["prompt_len"]["bins"])


def test_every_block_carries_the_same_mix():
    table = size_table(CHAT)
    width = CHAT["clients"]
    n = len(table)
    whole = Counter(p for p, _ in table)
    outs_all = lengths(CHAT["output_len"], n)
    for b in range(n // width):
        block = table[b * width:(b + 1) * width]
        counts = Counter(p for p, _ in block)
        for v, c in whole.items():
            assert abs(counts[v] - c * width / n) <= 1
        outs = sorted(o for _, o in block)
        # one output from each of `width` quantile strata
        for s, o in enumerate(outs):
            stratum = outs_all[s * (n // width):(s + 1) * (n // width)]
            assert stratum[0] <= o <= stratum[-1]


def test_a_clients_kth_request_comes_from_the_kth_block():
    loop = ClosedLoop(CHAT, 50304, BIG_SEED)
    width = CHAT["clients"]
    for k in range(3):
        got = Counter((loop.for_client(c, k).prompt.size,
                       loop.for_client(c, k).max_new_tokens)
                      for c in range(width))
        block = loop.order[k * width] // width
        want = Counter(size_table(CHAT)[block * width:(block + 1) * width])
        assert got == want
