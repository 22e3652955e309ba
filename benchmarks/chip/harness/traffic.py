"""The one load generator: turns a traffic file and a seed into requests.

Sizes. The file's lognormal (median, sigma) lengths, clipped to [min, max],
are cut into `strata` stratified quantiles (the i-th of n sits at quantile
(i + 0.5) / n) and paired by a fixed shuffle: the `pairs`, the same for
every seed. They are the length profile the planner sizes the pool for and
the set of shapes set-up warms. Prompts at or below the largest of
`short_prompt_round_up` are rounded up to the next value of that list,
because whole-prompt prefill compiles once per length; longer prompts are
rounded up to a multiple of `long_prompt_multiple`, which bounds how many
final chunks one chunked-prefill tick can hold.

Open loop. The measured window of `seconds` holds exactly
round(rate * seconds) arrivals, whatever the seed: a fixed stratified
subsample of the pairs and as many stratified exponential gaps, scaled to
sum to `seconds`. They form one cycle in a fixed order (drawn from the
file's `schedule_seed`), which repeats before the window (the ramp) and
after it (the drain), period after period. The seed picks the place in the
cycle at which the window opens, so every request keeps the same
neighbours and gaps whatever the seed: a tail over a few tens of requests
then reads the same work on every seed, not a new draw of who arrives
together. Closed loop. Requests are drawn from the pairs, a whole pass at
a time, in an order the seed draws.

Order (and the open loop's place in its cycle) is all the seed changes,
besides the token ids, and it is balanced: each run of `balance`
consecutive requests (or gaps) holds one from each of `balance` bands of
size, so no order piles the large ones together.
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def lognormal_strata(spec: Dict, n: int) -> List[int]:
    """n stratified draws of a clipped lognormal, as whole tokens."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    return [int(min(max(round(math.exp(mu + sigma * nd.inv_cdf(u))),
                        spec["min"]), spec["max"]))
            for u in _quantiles(n)]


def round_prompt(p: int, steps: List[int], multiple: int) -> int:
    for s in sorted(steps):
        if p <= s:
            return s
    return -(-p // multiple) * multiple


def balanced_order(sizes: Sequence[float], k: int,
                   rng: random.Random) -> List[int]:
    """A seeded order of range(len(sizes)) in which every run of k
    consecutive places (the last may be shorter) takes one index from each
    of k bands of increasing size."""
    n = len(sizes)
    by_size = sorted(range(n), key=lambda i: (sizes[i], i))
    bands, lo = [], 0
    for j in range(k):
        hi = lo + len(range(j, n, k))      # band j fills place j of each run
        bands.append(by_size[lo:hi])
        lo = hi
    for b in bands:
        rng.shuffle(b)
    order = []
    for r in range(-(-n // k)):
        run = [b[r] for b in bands if r < len(b)]
        rng.shuffle(run)
        order += run
    return order


class Traffic:
    """Request sizes, arrival schedule and token ids of one run."""

    def __init__(self, spec: Dict, seed: int, vocab: int):
        self.spec = s = dict(spec)
        self.seed = int(seed)
        self.vocab = int(vocab)
        n = s["strata"]
        prompts = [round_prompt(p, s["short_prompt_round_up"],
                                s["long_prompt_multiple"])
                   for p in lognormal_strata(s["prompt"], n)]
        outputs = lognormal_strata(s["output"], n)
        # a fixed pairing, the same for every seed
        random.Random(s["pairing_seed"]).shuffle(outputs)
        self.pairs: List[Tuple[int, int]] = list(zip(prompts, outputs))
        self.open = s["loop"] == "open"
        self.balance = s["balance"]
        self._closed_order: List[int] = []
        self._passes = 0

    def _rng(self, *tag) -> random.Random:
        return random.Random(":".join(str(t) for t in (self.seed,) + tag))

    @staticmethod
    def _work(pair: Tuple[int, int]) -> int:
        return pair[0] + pair[1]

    # -- the planner's view ------------------------------------------------

    def plan_lengths(self) -> List[int]:
        """Written positions per request (prompt + output - 1) over the
        strata: the length profile the planner sizes the pool for."""
        return [p + o - 1 for p, o in self.pairs]

    def context(self) -> int:
        return max(p + o for p, o in self.pairs)

    # -- the open loop -------------------------------------------------------

    def window_set(self, seconds: float
                   ) -> Tuple[List[Tuple[int, int]], List[float]]:
        """The requests and gaps of one window: round(rate * seconds) pairs
        at stratified places of the pairs ordered by work (prompt +
        output), and as many stratified exponential gaps summing to
        `seconds`."""
        n = max(1, round(self.spec["rate_per_s"] * seconds))
        by_work = sorted(self.pairs, key=self._work)
        m = len(by_work)
        reqs = [by_work[min(m - 1, int(u * m))] for u in _quantiles(n)]
        gaps = [-math.log(1.0 - u) for u in _quantiles(n)]
        scale = seconds / sum(gaps)
        return reqs, [g * scale for g in gaps]

    def arrivals(self, seconds: float, ramp_s: float
                 ) -> Iterator[Tuple[float, Tuple[int, int]]]:
        """(due time from the window's start, (prompt, output)) in time
        order, from `ramp_s` before the window on, without end. The window
        [0, seconds) holds exactly the window set, in the fixed cycle
        entered at the seed's place; the periods before and after it repeat
        the cycle."""
        reqs, gaps = self.window_set(seconds)
        rng = random.Random(self.spec["schedule_seed"])
        ro = balanced_order([self._work(r) for r in reqs], self.balance, rng)
        go = balanced_order(gaps, self.balance, rng)
        cycle = [(reqs[i], gaps[j]) for i, j in zip(ro, go)]
        k = self._rng("phase").randrange(len(cycle))
        cycle = cycle[k:] + cycle[:k]
        period = -math.ceil(ramp_s / seconds) if ramp_s > 0 else 0
        while True:
            t = period * seconds
            for size, gap in cycle:
                if t >= -ramp_s:
                    yield t, size
                t += gap
            period += 1

    # -- the closed loop -----------------------------------------------------

    def next_size(self) -> Tuple[int, int]:
        """The next request of the closed loop's stream."""
        if not self._closed_order:
            rng = self._rng("pass", self._passes)
            self._passes += 1
            self._closed_order = balanced_order(
                [self._work(p) for p in self.pairs], self.balance, rng)[::-1]
        return self.pairs[self._closed_order.pop()]

    def tokens(self, rid: int, n: int) -> Tuple[int, ...]:
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF,
                                     self.seed >> 32, rid])
        return tuple(int(t) for t in rng.integers(0, self.vocab, n))
