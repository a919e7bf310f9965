"""Closed-loop traffic: a fixed number of clients, each sending its next
request as soon as the previous one has finished (zero think time).

One client per serve-loop slot.  Parameters, from the traffic file:

    strata             sizes are laid out in blocks of this many requests
    prompt / output    {"dist": "lognormal", "median", "sigma", "min",
                        "max"} or {"dist": "uniform", "min", "max"}: the
                       prompt (after any shared prefix) and output lengths
    shared_prefix      optional {"count", "length", "zipf_s"}: every
                       prompt starts with one of ``count`` prefixes of
                       ``length`` tokens, chosen with Zipf(s) popularity;
                       set-up sends each prefix once (``warm_prompts``),
                       as a long-running server would hold them

The requests in flight when the window opens have the remaining output
lengths of a steady stream (density proportional to P(output >= r)), so
completions are spread over the window and not in lockstep.

Every seed gets the same sizes in the same order.  Within each block of
``strata`` consecutive requests, the lengths (and the prefix choices) are
the distribution's quantiles (i + 0.5) / strata, i = 0 .. strata - 1, in
an order fixed by the block's index.  So a window serves the same sizes
under every seed, and the seed changes which tokens (and which client
holds which remaining output).  Token ids are uniform over the
vocabulary, drawn from the seed and the request's index.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def _inv_cdf(dist: dict, u: float) -> int:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        return lo + min(int(u * (hi - lo + 1)), hi - lo)
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(max(round(x), lo), hi))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def _residual_pmf(dist: dict) -> np.ndarray:
    """P(R = r), r = 0 .. max: remaining output of a request in flight,
    proportional to P(L >= r) for r >= 1 over a fine population of L."""
    n = 4096
    lengths = np.array([_inv_cdf(dist, (i + 0.5) / n) for i in range(n)])
    r = np.arange(int(dist["max"]) + 1)
    surv = (lengths[None, :] >= r[:, None]).mean(1)
    surv[0] = 0.0
    return surv / surv.sum()


class Generator:
    """The request stream of one run: ``warm_prompts`` for set-up,
    ``first`` for the clients' requests in flight when the window opens,
    then ``next`` for every later request."""

    def __init__(self, params: dict, *, vocab: int, slots: int, s_max: int,
                 seed: int):
        self.p = params
        self.vocab = vocab
        self.seed = seed
        self.clients = slots
        self.strata = int(params["strata"])
        self.j = 0
        shared = params.get("shared_prefix")
        self.prefixes = []
        self.prefix_cdf = None
        if shared:
            rng = np.random.default_rng([seed, 1 << 30])
            self.prefixes = [
                rng.integers(0, vocab, int(shared["length"])).astype(np.int32)
                for _ in range(int(shared["count"]))]
            w = 1.0 / np.arange(1, len(self.prefixes) + 1) ** float(
                shared["zipf_s"])
            self.prefix_cdf = np.cumsum(w / w.sum())
        longest = (len(self.prefixes[0]) if self.prefixes else 0) + int(
            params["prompt"]["max"]) + int(params["output"]["max"])
        if longest > s_max:
            raise ValueError(f"traffic reaches {longest} tokens, past the "
                             f"deployment's s_max {s_max}")

    def _u(self, j: int, stream: int) -> float:
        """The stratified quantile of request ``j`` in one stream."""
        block, i = divmod(j, self.strata)
        layout = np.random.default_rng([stream, block]).permutation(
            self.strata)
        return (layout[i] + 0.5) / self.strata

    def _prompt(self, j: int) -> np.ndarray:
        n = _inv_cdf(self.p["prompt"], self._u(j, 0))
        body = np.random.default_rng([self.seed, 2, j]).integers(
            0, self.vocab, n).astype(np.int32)
        if not self.prefixes:
            return body
        k = int(np.searchsorted(self.prefix_cdf, self._u(j, 3)))
        return np.concatenate([self.prefixes[k], body])

    def warm_prompts(self) -> list:
        return list(self.prefixes)

    def first(self) -> list:
        """(prompt, max_new) for each client's request in flight."""
        out = []
        pmf = _residual_pmf(self.p["output"])
        order = np.random.default_rng([self.seed, 4]).permutation(
            self.clients)
        for c in range(self.clients):
            r = int(np.searchsorted(np.cumsum(pmf),
                                    (order[c] + 0.5) / self.clients))
            out.append((self._prompt(self.j), max(r, 1)))
            self.j += 1
        return out

    def next(self) -> tuple:
        j = self.j
        self.j += 1
        return self._prompt(j), _inv_cdf(self.p["output"], self._u(j, 1))
