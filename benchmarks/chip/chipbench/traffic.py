"""The one traffic generator: a mix is a JSON file of parameters.

Copied from the program's seeded workload generator (`obs/workload.py`:
log-normal prompt and output lengths clamped to the serving shape, Poisson
arrivals) and changed in one respect: every seed gets the same set of sizes
and the same set of gaps between arrivals, in another order. Lengths are the
quantiles of the clamped log-normal at (i + 1/2) / n, gaps the quantiles of
the exponential, so a seed only permutes them and picks the token ids. Runs
with different seeds then differ by arrangement and not by how much work
they hold, which is what lets a tail over a hundred requests repeat. The
sets are small blocks of `BLOCK` requests, so that any stretch of a run
holds nearly the same sizes and gaps for every seed.

Parameters (all lengths in tokens, times in seconds):

    loop                "open" (arrivals on a schedule) or "closed"
    rate_per_s          open loop: mean arrival rate
    in_flight_per_slot  closed loop: requests kept in flight per decode slot
    prompt_tokens       {"median", "sigma", "min", "max"} of the log-normal
    output_tokens       the same for the number of tokens to generate
    lead_in_s           load offered before the measured window opens
    greedy              every request decodes greedily (the check needs it)
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

BLOCK = 16      # requests per stratified block
# fixed, seed-independent pairing of prompt and output lengths
_PAIRING_STREAM = 0x9A1B
_N = NormalDist()


@dataclass
class Planned:
    """One request: due time (seconds from the window's start), tokens."""
    due_s: float
    prompt: List[int]
    max_new_tokens: int
    segment: str            # "lead", "window" or "tail"


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """Quantiles (i + 1/2) / n of a log-normal with the given median and
    sigma, rounded and clamped to [min, max], ascending."""
    mu = math.log(spec["median"])
    q = [(i + 0.5) / n for i in range(n)]
    raw = [math.exp(mu + spec["sigma"] * _N.inv_cdf(x)) for x in q]
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int, duration: float) -> np.ndarray:
    """Quantiles of the exponential at (i + 1/2) / n, scaled to sum to
    `duration` (so a segment of n arrivals spans exactly that long)."""
    g = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (duration / g.sum())


def size_pairs(traffic: dict, n: int) -> np.ndarray:
    """(n, 2) prompt and output lengths: stratified marginals, paired by
    a fixed permutation that no seed changes."""
    p = lognormal_lengths(traffic["prompt_tokens"], n)
    o = lognormal_lengths(traffic["output_tokens"], n)
    o = o[np.random.default_rng(_PAIRING_STREAM).permutation(n)]
    return np.stack([p, o], axis=1)


class Generator:
    """Seeded requests for one traffic mix and one vocabulary."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        if not traffic.get("greedy", False):
            raise ValueError("only greedy traffic can be checked against "
                             "the reference")
        self.traffic = traffic
        self.seed = int(seed)
        self.vocab = int(vocab)
        self._tokens = np.random.default_rng([self.seed, 1])
        # no two prompts of a run share their first token, so no prompt
        # can match a cached prefix of another, not even in part
        self._firsts = iter(np.random.default_rng([self.seed, 4])
                            .permutation(self.vocab).tolist())

    def _rng(self, *stream) -> np.random.Generator:
        return np.random.default_rng([self.seed, 2, *stream])

    def prompt(self, n: int) -> List[int]:
        """n token ids: a first token no other prompt of the run has, then
        uniform draws."""
        rest = self._tokens.integers(0, self.vocab, size=int(n) - 1)
        return [next(self._firsts)] + rest.tolist()

    def open_loop(self, seconds: float) -> Iterator[Planned]:
        """Arrivals from lead_in_s before the window's start onwards, in
        blocks of BLOCK requests: each block the same stratified set of
        sizes and of gaps (spanning BLOCK / rate seconds), in a seed-chosen
        order. A request is "lead", "window" (due in [0, seconds)) or
        "tail"; the stream goes on for as long as the caller reads (the
        load continues while the window's last requests are served)."""
        rate = float(self.traffic["rate_per_s"])
        n = BLOCK
        start = -float(self.traffic.get("lead_in_s", 0.0))
        for k in itertools.count():
            rng = self._rng(k)
            sizes = size_pairs(self.traffic, n)[rng.permutation(n)]
            gaps = exponential_gaps(rate, n, n / rate)[rng.permutation(n)]
            due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            for t, (p, o) in zip(due, sizes):
                seg = "lead" if t < 0 else "window" if t < seconds \
                    else "tail"
                yield Planned(float(t), self.prompt(p), int(o), seg)
            start += gaps.sum()

    def closed_loop(self, first: int) -> Iterator[Planned]:
        """First a stratified set of `first` sizes (the requests that take
        the decode slots at the start), then an endless sequence of blocks,
        each the same stratified set of BLOCK sizes; each set in a
        seed-chosen order. Small blocks keep the sizes that any stretch of
        the run admits close to the same set for every seed. Due times are
        not used: a closed loop sends when one finishes."""
        for k, n in enumerate(itertools.chain([first],
                                              itertools.repeat(BLOCK))):
            pairs = size_pairs(self.traffic, n)
            for p, o in pairs[self._rng(k).permutation(n)]:
                yield Planned(0.0, self.prompt(p), int(o), "closed")


def prefill_lengths(traffic: dict) -> List[int]:
    """Every prompt length the mix can send, for warming up the prefill
    programs (their shapes are bucketed by the program)."""
    spec = traffic["prompt_tokens"]
    return list(range(int(spec["min"]), int(spec["max"]) + 1))
