"""Serve cells that replay one order of work for every seed.

The ``serve`` runner, with one change: the mix's prompt lengths, output
lengths and inter-arrival gaps come in the one order that the mix's
``order_seed`` draws, whatever the run's ``--seed``.  The seed still draws
every prompt's tokens (from the streams ``bench/traffic.py`` uses for
them), the weights, and the requests that ``correct`` scores.

Why: a cell run near its knee has tails that are set by which long
prompts arrive close together.  ``serve`` shuffles the work anew for every
seed, so there the spread of a p95 over seeds is the shuffle's and not the
system's; ``bench/traffic.py`` means two seeds to differ as two runs of
one seed do, and replaying one order keeps them so.
"""
from __future__ import annotations

import numpy as np

from bench import traffic
from bench.runners import serve


class OneOrder:
    """``bench.traffic`` with the work in ``order_seed``'s order and the
    prompts' tokens from the run's seed."""

    def __init__(self, order_seed: int):
        self.order_seed = order_seed

    def __getattr__(self, name):
        return getattr(traffic, name)

    @staticmethod
    def _tokens(requests, seed, stream, vocab):
        toks = traffic.rng(seed, stream)
        return [dict(r, prompt=toks.integers(0, vocab, len(r["prompt"]),
                                             np.int32)) for r in requests]

    def serve_schedule(self, tr, seconds, seed, vocab):
        return self._tokens(traffic.serve_schedule(tr, seconds,
                                                   self.order_seed, vocab),
                            seed, 1, vocab)

    def population(self, tr, seed, vocab):
        return self._tokens(traffic.population(tr, self.order_seed, vocab),
                            seed, 5, vocab)


def run(rec, devices, **kw):
    """``serve.run`` drawing its requests through ``OneOrder``."""
    serve.traffic = OneOrder(int(rec.cell.traffic["order_seed"]))
    try:
        serve.run(rec, devices, **kw)
    finally:
        serve.traffic = traffic
