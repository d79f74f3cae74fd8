"""The serving engine's own record: host spans and integer counters, in memory.

An ``Engine`` built with a ``Trace`` records a span at each of its steps
(``SPANS``) and counts its work at the same places (``COUNTERS``).  Each
span is ``(name, t0, t1, parent, uid)``: ``time.perf_counter()`` times,
the index in ``Trace.spans`` of the span that was open when it began, and
the request it serves.  A span opened with ``span()`` is also a
``jax.profiler.TraceAnnotation("engine.<name>")``, so a profiler trace
holds it on its host plane, on the clock of the device planes.  A queue
wait spans two engine calls (``submit`` and the admission), so it is
recorded in memory only.

Without a ``Trace`` the engine records nothing.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

PREFIX = "engine."

SPANS: Dict[str, str] = {
    "queued": "one request from submit to the start of its admission "
              "(memory only: not a host step)",
    "tick": "one Engine.tick: admission, decode, the wait and the pulls",
    "admit": "one request's admission: prefill, splice, first-token pulls",
    "prefill": "dispatch of the request's batch-1 serve_prefill",
    "splice": "the eager copy of the request's cache into its slot",
    "first_token": "one host pull of the request's first token (two per "
                   "admission); the host waits here for prefill and splice",
    "decode": "dispatch of serve_decode, of the argmax over its logits "
              "and of the tokens' copy to the host",
    "device_wait": "block_until_ready on the tick's tokens: the host waits "
                   "for the decode step on the device",
    "pull": "one host pull of the tick's whole token vector, and the live "
            "slots' bookkeeping on the host",
}

COUNTERS: Dict[str, str] = {
    "ticks": "Engine.tick calls",
    "admitted": "requests admitted (prefilled and spliced into a slot)",
    "prefill_tokens": "prompt tokens prefilled",
    "decoded_tokens": "tokens made by decode steps (one per live slot per "
                      "tick)",
    "host_syncs": "device-to-host transfers the engine forces: two per "
                  "admission, one per tick that decoded",
}

QUEUED = "queued"


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: Optional[float]              # None while the span is open
    parent: Optional[int]
    uid: Optional[int]


class Trace:
    """Spans and counters of one engine, kept until the caller reads them."""

    def __init__(self):
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = dict.fromkeys(COUNTERS, 0)
        self._open: List[int] = []
        self._submitted: Dict[int, float] = {}

    @contextlib.contextmanager
    def span(self, name: str, uid: Optional[int] = None):
        """A span under the innermost open one; it takes that one's uid
        unless given its own."""
        if name not in SPANS:
            raise KeyError(f"span {name!r} is not in SPANS")
        parent = self._open[-1] if self._open else None
        if uid is None and parent is not None:
            uid = self.spans[parent].uid
        i = len(self.spans)
        with jax.profiler.TraceAnnotation(PREFIX + name):
            self.spans.append(Span(name, time.perf_counter(), None, parent,
                                   uid))
            self._open.append(i)
            try:
                yield
            finally:
                self._open.pop()
                self.spans[i].t1 = time.perf_counter()

    def count(self, name: str, n: int = 1):
        self.counters[name] += n

    def submitted(self, uid: int):
        self._submitted[uid] = time.perf_counter()

    def admitting(self, uid: int):
        """Closes the request's queue wait, if its submit was seen."""
        t0 = self._submitted.pop(uid, None)
        if t0 is not None:
            self.spans.append(Span(QUEUED, t0, time.perf_counter(), None,
                                   uid))

    def summary(self) -> Dict[str, object]:
        """The counters, host syncs per tick, the queue wait's 95th
        percentile and each host span's self time (its time less its
        children's)."""
        self_s: Dict[str, float] = collections.defaultdict(float)
        waits = []
        for s in self.spans:
            if s.t1 is None:
                continue
            if s.name == QUEUED:
                waits.append(s.t1 - s.t0)
                continue
            self_s[s.name] += s.t1 - s.t0
            if s.parent is not None:
                self_s[self.spans[s.parent].name] -= s.t1 - s.t0
        ticks = self.counters["ticks"]
        return {
            **self.counters,
            "host_syncs_per_tick":
                self.counters["host_syncs"] / ticks if ticks else None,
            "queue_wait_p95_ms":
                1e3 * float(np.percentile(waits, 95)) if waits else None,
            "self_s": dict(self_s)}

    def summary_line(self) -> str:
        s = self.summary()

        def num(v, fmt):
            return "n/a" if v is None else format(v, fmt)

        spans = " ".join(f"{k} {v:.3f}" for k, v in
                         sorted(s["self_s"].items(), key=lambda x: -x[1]))
        return (f"engine: {s['ticks']} ticks, {s['admitted']} admitted "
                f"({s['prefill_tokens']} prompt tokens), "
                f"{s['decoded_tokens']} decoded, "
                f"{num(s['host_syncs_per_tick'], '.2f')} host syncs/tick, "
                f"queue wait p95 {num(s['queue_wait_p95_ms'], '.1f')} ms; "
                f"self s: {spans}")
