"""Closed-loop request plan from a traffic file and a seed.

Every seed gets the same sizes in the same order: ``pool`` prompt
lengths and ``pool`` output lengths at evenly spaced quantiles of their
lognormal laws, clipped to ``[min, max]``, each set in one fixed
shuffled order (and so one pairing). A window holds only the first few
requests of a client, so an order drawn from the seed would change the
work a run does. A law is given by the mean and the standard deviation
that the traffic file's ``source`` publishes; the lognormal is the one
with that mean and deviation. The seed draws the prompt token ids. The
first request of each client (the one the server is warmed with) has
the median prompt and a fixed output quantile, so set-up too does the
same work on every seed.

A client sends its next request when its last one completes; the
clients share one sequence: the warm-up requests, then the pool,
cycled.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

# The one order of the size sets, the same for every run's seed.
ORDER_SEED = 1


def lognormal(law: dict) -> Tuple[float, float]:
    """(median, sigma) of the lognormal with the law's mean and sd."""
    cv2 = (law["sd"] / law["mean"]) ** 2
    return law["mean"] / math.sqrt(1.0 + cv2), math.sqrt(math.log1p(cv2))


def lognormal_quantiles(law: dict, n: int) -> List[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n of the law's
    lognormal, rounded and clipped to [min, max]."""
    median, sigma = lognormal(law)
    z = NormalDist()
    out = []
    for i in range(n):
        v = median * math.exp(sigma * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), law["min"]), law["max"])))
    return out


class Plan:
    """The request sequence of one run: ``size(i)`` and ``prompt(i)``
    of the i-th request sent, over all clients."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.clients = int(traffic["clients"])
        self.vocab = vocab
        self.seed = seed
        pool = int(traffic["pool"])
        prompts = lognormal_quantiles(traffic["prompt_len"], pool)
        outs = lognormal_quantiles(traffic["output_len"], pool)
        rng = np.random.default_rng(ORDER_SEED)
        self.pool: List[Tuple[int, int]] = [
            (prompts[i], outs[j]) for i, j in
            zip(rng.permutation(pool), rng.permutation(pool))]
        c = self.clients
        warm_out = lognormal_quantiles(traffic["output_len"], 2 * c)[c:]
        warm_in = round(lognormal(traffic["prompt_len"])[0])
        self.warm = [(warm_in, warm_out[i])
                     for i in range(c)]

    def size(self, i: int) -> Tuple[int, int]:
        """(prompt length, output length) of request i."""
        if i < self.clients:
            return self.warm[i]
        return self.pool[(i - self.clients) % len(self.pool)]

    def prompt(self, i: int) -> List[int]:
        n, _ = self.size(i)
        rng = np.random.default_rng([self.seed, 2, i])
        return [int(t) for t in rng.integers(1, self.vocab, n)]

    def max_total(self) -> int:
        """Most KV rows a request of the plan can hold: the longest
        prompt plus the longest output, whatever the pairing, so that
        every seed sizes the KV cache, and so the programs, alike."""
        sizes = self.pool + self.warm
        return max(p for p, _ in sizes) + max(o for _, o in sizes)
