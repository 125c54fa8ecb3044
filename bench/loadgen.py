"""The one traffic generator: turns a mix's parameters into requests.

A mix (``traffic/<name>.json``) gives the loop (``open`` with a Poisson
``rate_per_s``, or ``closed`` with ``clients`` drawing from blocks of
``block`` requests), the prompt and output
length distributions, the prompt-length buckets (each one prefill
program, warmed in set-up) and the cache ``capacity``.

Every seed gets the same work: lengths and inter-arrival gaps are the
evenly spaced quantiles of their distributions, and the seed only chooses
their order and the token ids.  So two seeds differ in arrangement, not
in how much there is to do.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Req:
    rid: int
    prompt_len: int
    max_new: int
    tokens: np.ndarray = field(repr=False, default=None)
    due: float = 0.0  # seconds after the window opens (open loop)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles ((i + 0.5) / n) of a length
    distribution, clipped to its ``min``/``max``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "choice":
        vals = np.asarray(dist["values"], np.float64)
        x = vals[np.minimum((u * len(vals)).astype(int), len(vals) - 1)]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.round(x), dist.get("min", 1), dist.get("max", np.inf)).astype(int)


def bucket(n: int, buckets) -> int:
    """The smallest bucket that holds ``n`` tokens."""
    for b in sorted(buckets):
        if n <= b:
            return int(b)
    raise ValueError(f"prompt of {n} tokens exceeds the largest bucket {max(buckets)}")


class Traffic:
    """Requests of one mix for one seed."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.mix = mix
        self.rng = np.random.default_rng(int(seed))
        self.vocab = int(vocab)
        self.buckets = sorted(int(b) for b in mix["prompt"]["buckets"])
        self.open = mix["loop"] == "open"
        self._next_rid = 0
        if self.open:
            n = max(1, round(mix["rate_per_s"] * seconds))
            self.pool = self._make(n)
            gaps = -np.log1p(-(np.arange(n) + 0.5) / n)  # Exp(1) quantiles
            gaps = self.rng.permutation(gaps)
            # arrivals fill the window exactly: n requests over ``seconds``
            due = np.cumsum(gaps) / gaps.sum() * seconds
            for r, t in zip(self.pool, due - due[0] * 0.5):
                r.due = float(t)
        else:
            self.pool = []
        self._cursor = 0

    def _make(self, n: int) -> list[Req]:
        """``n`` requests: the same (prompt, output) pairs for every seed,
        in the seed's order."""
        fixed = np.random.default_rng(0)
        plen = quantiles(self.mix["prompt"], n)
        nout = fixed.permutation(quantiles(self.mix["output"], n))
        order = self.rng.permutation(n)
        return [self._req(bucket(int(plen[i]), self.buckets), int(nout[i])) for i in order]

    def _req(self, plen: int, max_new: int) -> Req:
        rid = self._next_rid
        self._next_rid += 1
        toks = self.rng.integers(1, self.vocab, size=plen, dtype=np.int32)
        return Req(rid, plen, max_new, toks)

    def steady_state(self, n: int) -> list[Req]:
        """``n`` requests already under way when the window opens: lengths
        from the mix, with an evenly spread share of each output already
        made (so their completions do not all fall together)."""
        fixed = np.random.default_rng(1)
        plen = quantiles(self.mix["prompt"], n)
        nout = fixed.permutation(quantiles(self.mix["output"], n))
        done = fixed.permutation((np.arange(n) + 0.5) / n)
        reqs = []
        for i in self.rng.permutation(n):
            r = self._req(bucket(int(plen[i]), self.buckets), int(nout[i]))
            # at least 2: the engine serves every request an admission's
            # token and at least one step's
            r.max_new = max(2, int(round(r.max_new * (1.0 - done[i]))))
            reqs.append(r)
        return reqs

    def warmup(self) -> list[Req]:
        """One short request per prompt bucket: compiles each prefill."""
        return [self._req(b, 2) for b in self.buckets]

    def next_closed(self) -> Req:
        """The next request of a closed-loop client.  Requests come in
        blocks of ``block`` that each hold the mix's whole set of sizes, so
        the first k requests are much the same work for every seed."""
        if self._cursor >= len(self.pool):
            self.pool += self._make(int(self.mix.get("block", 16)))
        r = self.pool[self._cursor]
        self._cursor += 1
        return r
