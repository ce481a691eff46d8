"""The load generator: a traffic file's parameters and a seed give every
request a closed-loop client will send.

A traffic file (``bench/traffic/<name>.json``) states::

  "source": "..."          the public study its lengths are fitted to
  "loop": "closed"         each client sends its next request the moment
                           its previous one finishes (no other loop is
                           implemented: anything else is an error)
  "clients": 32            closed-loop clients
  "slots": 32              KV slots of the serving engine
  "table_size": 1024       requests in the size table
  "prompt_len": {"lognormal": {"mean": m, "sd": s}, "bins": [...]}
  "output_len": {"lognormal": {"mean": m, "sd": s}, "clip": [lo, hi]}

A length law is a log-normal with the source's mean and standard
deviation, read either onto ``bins`` (each draw served at the bin nearest
it in log scale, the end bins taking the tails) or clipped to
``[lo, hi]`` and rounded.  Any other key in a law is an error.

Every seed serves the same table of (prompt length, output length) pairs
in its own order, so the seed changes which requests meet in a step and
the token ids, not the amount of work.  The table is cut into blocks of
one request per client, and each block holds the same mix: prompt lengths
within one of their proportions, and one output length from each of
``clients`` equal strata of the output law's quantiles.  The seed orders
the blocks and the requests within each block.  Client ``c``'s ``k``-th
request is request ``c`` of the seed's ``k``-th block, whatever the
timing, so every window and every ramp sees nearly the same mix.  Token
ids are uniform over the vocabulary.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

TRAFFIC_KEYS = {"source", "loop", "clients", "slots", "table_size",
                "prompt_len", "output_len"}


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int               # position in the seed's request order
    prompt: np.ndarray       # (prompt_len,) int32
    max_new_tokens: int


def _seed_words(seed: int) -> int:
    return int(seed) % 2**64


def check_traffic(traffic: dict) -> None:
    """Refuse a traffic file this generator would not serve as written."""
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"traffic keys {sorted(unknown)} are not read")
    if traffic.get("loop") != "closed":
        raise ValueError(f"loop {traffic.get('loop')!r}: only a closed "
                         f"loop is implemented")
    for key in ("prompt_len", "output_len"):
        law = traffic[key]
        if set(law) not in ({"lognormal", "bins"}, {"lognormal", "clip"}):
            raise ValueError(f"{key}: a law is 'lognormal' with 'bins' or "
                             f"'clip', got {sorted(law)}")


def _quantiles(law: dict, n: int) -> list:
    """The log-normal's quantiles at ``(i + 0.5) / n``, i < n."""
    mean, sd = law["lognormal"]["mean"], law["lognormal"]["sd"]
    sigma2 = math.log1p((sd / mean) ** 2)
    mu, sigma = math.log(mean) - sigma2 / 2, math.sqrt(sigma2)
    z = statistics.NormalDist()
    return [math.exp(mu + sigma * z.inv_cdf((i + 0.5) / n))
            for i in range(n)]


def lengths(law: dict, n: int) -> list:
    """``n`` lengths at evenly spaced quantiles of the law, ascending."""
    qs = _quantiles(law, n)
    if "bins" in law:
        bins = sorted(law["bins"])
        logs = np.log(bins)
        return [bins[int(np.argmin(np.abs(logs - math.log(q))))]
                for q in qs]
    lo, hi = law["clip"]
    return [int(min(hi, max(lo, round(q)))) for q in qs]


def max_length(law: dict) -> int:
    return int(max(law["bins"]) if "bins" in law else law["clip"][1])


def size_table(traffic: dict) -> list:
    """The seed-independent ``(prompt_len, output_len)`` pairs, block by
    block (a block is ``clients`` requests, each block the same mix)."""
    n, width = int(traffic["table_size"]), int(traffic["clients"])
    if n % width:
        raise ValueError(f"table_size {n} is not a multiple of clients "
                         f"{width}")
    blocks = n // width
    prompts = lengths(traffic["prompt_len"], n)    # ascending
    outs = lengths(traffic["output_len"], n)       # ascending
    rng = np.random.default_rng(0)
    # stratum s of the sorted outputs gives block b its element pick[s][b]
    pick = [rng.permutation(blocks) for _ in range(width)]
    table = []
    for b in range(blocks):
        block_outs = [outs[s * blocks + pick[s][b]] for s in range(width)]
        pairing = rng.permutation(width)
        # every blocks-th prompt: each length within one of its share
        block_prompts = prompts[b::blocks]
        table += [(int(block_prompts[i]), int(block_outs[pairing[i]]))
                  for i in range(width)]
    return table


class ClosedLoop:
    """Requests of a closed-loop traffic mix for one seed."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        check_traffic(traffic)
        self.traffic = traffic
        self.clients = int(traffic["clients"])
        self.vocab = int(vocab_size)
        self.seed = _seed_words(seed)
        self.table = size_table(traffic)
        rng = np.random.default_rng([self.seed, 1])
        blocks = len(self.table) // self.clients
        self.order = np.concatenate(
            [b * self.clients + rng.permutation(self.clients)
             for b in rng.permutation(blocks)])

    def _tokens(self, stream: int, index: int, n: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, stream, index])
        return rng.integers(0, self.vocab, n, dtype=np.int64).astype(
            np.int32)

    def request(self, index: int) -> RequestSpec:
        plen, olen = self.table[self.order[index % len(self.table)]]
        return RequestSpec(index=index, prompt=self._tokens(2, index, plen),
                           max_new_tokens=olen)

    def for_client(self, client: int, k: int) -> RequestSpec:
        return self.request(client + k * self.clients)

    def warmup(self, slots: int) -> list:
        """Two-token requests that fill every slot: each distinct prompt
        length once, the rest at the shortest.  Served together, they
        compile every prefill shape, the slot install and the decode step
        over the whole ring, the only step shape a loop with as many
        clients as slots uses."""
        plens = sorted({p for p, _ in self.table})
        plens += [plens[0]] * max(0, slots - len(plens))
        return [RequestSpec(index=-1 - i, prompt=self._tokens(3, i, plen),
                            max_new_tokens=2)
                for i, plen in enumerate(plens)]

    @property
    def max_prompt_len(self) -> int:
        return max_length(self.traffic["prompt_len"])

    @property
    def max_new_tokens(self) -> int:
        return max_length(self.traffic["output_len"])
