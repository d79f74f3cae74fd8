"""The float8 control, put in the program's place for ``correct``.

Not part of a benchmark run: the tests and ``bench/calibrate.py control``
use it to show that ``correct`` comes out false when a lower precision
stands in for the program.  Inside ``float8_control()`` a serve run
judges, at each position of the same prompts and served tokens, the token
that the float8 reference puts first, through the same ``judge`` and
``Record.correct`` as the program's own tokens.  The program's numbers
from that run are kept in ``rec.info["program_checks"]``.
"""
from __future__ import annotations

import contextlib

from bench import harness
from bench.runners import serve


@contextlib.contextmanager
def float8_control():
    import numpy as np
    real = serve.judge

    def judge(rec, c, params, seqs, n_wrong_length):
        real(rec, c, params, seqs, n_wrong_length)
        rec.info["program_checks"] = {k: dict(v)
                                      for k, v in rec.checks.items()}
        ref = harness.reference(c["family"])
        control = {
            uid: None if st is None else
            (st[0], np.asarray(ref.control_targets(
                c, params, *st, block=c["reference_block"])))
            for uid, st in seqs.items()}
        real(rec, c, params, control, n_wrong_length)

    serve.judge = judge
    try:
        yield
    finally:
        serve.judge = real
