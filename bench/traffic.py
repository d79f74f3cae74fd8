"""The one general generator: every traffic mix is a data file it reads.

A seed changes the order of the work and the token values, never the
work itself: every seed gets the same multiset of prompt lengths, output
lengths and inter-arrival gaps (taken at fixed quantiles of the mix's
distributions), shuffled by the seed.  So two seeds differ as two runs of
one seed do, and the spread of a metric is the system's, not the mix's.

A length is drawn from an exponential distribution with the mean (or
median) that the mix's source publishes, clipped to ``[min, max]`` and,
where the mix lists ``buckets``, rounded up to the smallest bucket that
holds it.  The population is the set of requests in flight when the
window opens: the mix at its steady state, so that the window measures
the engine as it runs and not as it fills from idle.
"""
from __future__ import annotations

import bisect
import math
from typing import Any, Dict, List

import numpy as np

POPULATION_UID0 = 10 ** 6         # the population's uids: 10**6, 10**6 + 1, ...


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any non-negative int) and a stream."""
    return np.random.default_rng([int(seed), *stream])


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def _mean(spec: Dict[str, Any]) -> float:
    return float(spec["mean"]) if "mean" in spec \
        else float(spec["median"]) / math.log(2.0)


def _fit(spec: Dict[str, Any], x: float) -> int:
    """``x`` rounded, clipped to the spec's range and rounded up to its
    bucket."""
    n = int(min(spec["max"], max(spec["min"], round(x))))
    buckets = spec.get("buckets")
    if buckets:
        n = buckets[min(bisect.bisect_left(buckets, n), len(buckets) - 1)]
    return n


def lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths at fixed quantiles of ``spec`` (in rising order)."""
    if spec["dist"] != "exponential":
        raise ValueError(f"unknown length distribution {spec!r}")
    mean = _mean(spec)
    return [_fit(spec, -mean * math.log(1.0 - q)) for q in _quantiles(n)]


def longest(spec: Dict[str, Any]) -> int:
    """The longest length ``spec`` can give."""
    return _fit(spec, float(spec["max"]))


def gaps(arrival: Dict[str, Any], n: int) -> List[float]:
    """``n`` inter-arrival gaps at fixed quantiles, summing to n / rate."""
    rate = float(arrival["rate_per_s"])
    if arrival["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    g = [-math.log(1.0 - q) for q in _quantiles(n)]
    scale = n / rate / sum(g)
    return [x * scale for x in g]


def serve_schedule(traffic: Dict[str, Any], seconds: float, seed: int,
                   vocab: int) -> List[Dict[str, Any]]:
    """The requests due in a window of ``seconds``, by arrival time.

    Each is ``{"uid", "arrival_s", "prompt" (np.int32 [S]), "max_new"}``.
    """
    n = max(1, int(round(traffic["arrival"]["rate_per_s"] * seconds)))
    r = rng(seed, 0)
    plens = r.permutation(lengths(traffic["prompt_len"], n))
    outs = r.permutation(lengths(traffic["output_len"], n))
    g = r.permutation(gaps(traffic["arrival"], n))
    arrivals = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    toks = rng(seed, 1)
    return [{"uid": i, "arrival_s": float(arrivals[i]),
             "prompt": toks.integers(0, vocab, int(plens[i]), np.int32),
             "max_new": int(outs[i])} for i in range(n)]


def remaining(spec: Dict[str, Any], n: int, grid: int = 4096) -> List[int]:
    """``n`` tokens still to decode of requests in flight at a random
    moment, at fixed quantiles (in rising order).

    A request of ``o`` output tokens is in flight after each of its first
    ``o - 1`` tokens, so it is found there in proportion to its length,
    and with ``r`` tokens to go for each ``r`` in ``1 .. o - 1`` alike:
    ``P(r)`` is proportional to the share of outputs longer than ``r``.
    """
    outs = np.asarray(lengths(spec, grid))
    r = np.arange(1, int(outs.max()))
    weight = np.array([(outs > x).sum() for x in r], np.float64)
    cdf = np.cumsum(weight) / weight.sum()
    return [int(r[min(np.searchsorted(cdf, q), len(r) - 1)])
            for q in _quantiles(n)]


def population(traffic: Dict[str, Any], seed: int,
               vocab: int) -> List[Dict[str, Any]]:
    """The ``traffic["population"]`` requests in flight at the window's
    start: prompts from the mix, and ``max_new`` the tokens still to go
    plus the one that their prefill stands for.  Their caches hold the
    prompt alone, not the answer tokens served before the window."""
    n = int(traffic.get("population", 0))
    if n == 0:
        return []
    r = rng(seed, 4)
    plens = r.permutation(lengths(traffic["prompt_len"], n))
    left = r.permutation(remaining(traffic["output_len"], n))
    toks = rng(seed, 5)
    return [{"uid": POPULATION_UID0 + i,
             "prompt": toks.integers(0, vocab, int(plens[i]), np.int32),
             "max_new": int(left[i]) + 1} for i in range(n)]
