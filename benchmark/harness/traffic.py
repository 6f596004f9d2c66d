"""One general traffic generator, driven by a traffic file.

A traffic file fixes distributions; the generator takes lengths, and in an
open loop the gaps between arrivals, at evenly spaced quantiles of them, a
``block`` of requests at a time.  Every block therefore holds the same
multiset of prompt lengths, output lengths and gaps under every seed, and
its gaps add up to exactly ``block / rate_per_s`` seconds.  The seed decides
only the order inside each block, the pairing of prompt with output length,
and the token ids.  So a window is offered the same work whatever the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantiles(dist: dict, n: int) -> list:
    """``dist`` at the ``n`` quantiles ``(i + 0.5) / n``, as floats."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "constant":
        vals = [float(dist["value"])] * n
    elif kind == "uniform":
        vals = [dist["min"] + q * (dist["max"] - dist["min"]) for q in qs]
    elif kind == "lognormal":
        mu, nd = math.log(dist["median"]), NormalDist()
        vals = [math.exp(mu + dist["sigma"] * nd.inv_cdf(q)) for q in qs]
    elif kind == "exponential":
        vals = [-math.log(1.0 - q) for q in qs]     # mean 1; scaled below
    else:
        raise ValueError("unknown distribution %r" % (kind,))
    lo, hi = dist.get("min"), dist.get("max")
    return [min(max(v, lo if lo is not None else v),
                hi if hi is not None else v) for v in vals]


def length_multiset(dist: dict, n: int) -> list:
    return [int(round(v)) for v in quantiles(dist, n)]


def gap_multiset(traffic: dict) -> list:
    """The block's gaps in seconds, scaled to sum to ``block / rate``."""
    n = traffic["block"]
    raw = quantiles(traffic["arrivals"], n)
    scale = (n / traffic["rate_per_s"]) / sum(raw)
    return [g * scale for g in raw]


def _rng(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


class Schedule:
    """Requests in sending order: ``request(i)`` is a dict with ``index``,
    ``prompt_tokens``, ``output_tokens`` and, in an open loop, ``due_s``
    (seconds after the traffic starts)."""

    def __init__(self, traffic: dict, seed: int):
        self.traffic, self.seed = traffic, int(seed)
        self.block = int(traffic["block"])
        self.prompts = length_multiset(traffic["prompt_tokens"], self.block)
        self.outputs = length_multiset(traffic["output_tokens"], self.block)
        self.open = traffic["loop"] == "open"
        self.gaps = gap_multiset(traffic) if self.open else None
        self._blocks = {}

    def _block(self, b: int) -> list:
        if b not in self._blocks:
            rng = _rng(self.seed, 2, b)
            prompts = rng.permutation(self.prompts)
            outputs = rng.permutation(self.outputs)
            reqs = [{"index": b * self.block + j,
                     "prompt_tokens": int(prompts[j]),
                     "output_tokens": int(outputs[j])}
                    for j in range(self.block)]
            if self.open:
                start = b * self.block / self.traffic["rate_per_s"]
                due = start + np.cumsum(rng.permutation(self.gaps))
                for r, d in zip(reqs, due):
                    r["due_s"] = float(d)
            if b == 0 and self.traffic.get("stagger_first"):
                # a closed batch submitted at once would finish in waves:
                # the first requests get a seeded share of their output
                fr = _rng(self.seed, 3).uniform(
                    0.05, 1.0, self.traffic["stagger_first"])
                for r, f in zip(reqs, fr):
                    r["output_tokens"] = max(
                        1, int(round(r["output_tokens"] * f)))
                    r["staggered"] = True
            self._blocks[b] = reqs
        return self._blocks[b]

    def request(self, i: int) -> dict:
        return self._block(i // self.block)[i % self.block]

    def due_before(self, t_s: float) -> list:
        """Open loop: every request due before ``t_s``."""
        out, i = [], 0
        while True:
            r = self.request(i)
            if r["due_s"] >= t_s:
                return out
            out.append(r)
            i += 1

    def token_ids(self, index: int, n: int, vocab: int) -> list:
        return _rng(self.seed, 4, index).integers(0, vocab, n).tolist()


def offered(requests: list) -> dict:
    """What a list of requests offers: the totals printed before a run."""
    return {"requests": len(requests),
            "prompt_tokens": sum(r["prompt_tokens"] for r in requests),
            "output_tokens": sum(r["output_tokens"] for r in requests)}
