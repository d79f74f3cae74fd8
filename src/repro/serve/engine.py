"""Batched serving engine with continuous batching.

A fixed pool of B decode slots shares one batched KV cache.  Requests queue
up; whenever a slot frees, the next request is prefilled (its per-request
cache spliced into the batch cache at the slot index) and decoding proceeds
for all active slots in lock-step — one ``decode_step`` per engine tick, the
standard continuous-batching serving loop (prefill-on-admit, iteration-level
scheduling).

The benchmark's serve cells (``bench/runners/serve.py``) drive it on the
chip at full width, and ``examples/serve_batch.py`` drives it end-to-end on
CPU with a reduced config.

An engine given a ``repro.serve.trace.Trace`` records its steps as spans
and counts its work and its host syncs (``serve/trace.py`` lists each);
without one it records nothing.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.serve.trace import Trace


@dataclasses.dataclass
class Request:
    uid: int
    prompt: jnp.ndarray              # [S] int32
    max_new_tokens: int = 16
    eos_id: int = -2                 # improbable default: run to max tokens


@dataclasses.dataclass
class Finished:
    uid: int
    tokens: List[int]


class EngineIncomplete(RuntimeError):
    """``run_to_completion`` hit ``max_ticks`` with work still pending.

    The partial results are *not* silently returned: requests still queued
    or mid-decode would be dropped on the floor.  The exception carries
    everything the caller needs to decide (drain with more ticks, report,
    or accept ``finished`` explicitly)."""

    def __init__(self, finished: List[Finished], n_queued: int,
                 n_in_flight: int, max_ticks: int):
        self.finished = finished
        self.n_queued = n_queued
        self.n_in_flight = n_in_flight
        self.max_ticks = max_ticks
        super().__init__(
            f"engine incomplete after {max_ticks} ticks: "
            f"{n_queued} request(s) still queued, "
            f"{n_in_flight} still in flight "
            f"({len(finished)} finished)")


_OFF = contextlib.nullcontext()


def _to_host(x) -> np.ndarray:
    """The one device-to-host transfer of a decoding tick: the whole [B]
    token vector."""
    return np.asarray(x)


class Engine:
    def __init__(self, cfg, params, batch_slots: int, cache_len: int,
                 ctx: M.Ctx = M.Ctx(), dtype=jnp.float32,
                 trace: Optional[Trace] = None):
        self.cfg, self.params, self.ctx = cfg, params, ctx
        self.trace = trace
        self.B, self.cache_len = batch_slots, cache_len
        self.state = M.init_decode_state(cfg, batch_slots, cache_len, dtype)
        # each cache leaf's batch axis, from its logical axes: guessing it
        # from sizes fails when a stacked cache's n_units equals B
        self._batch_axis = jax.tree.map(
            lambda ax: ax.index("batch"), M.decode_state_axes(cfg)["caches"],
            is_leaf=lambda x: isinstance(x, tuple))
        self.last_logits = None      # [B, Vp] of the latest decode step
        self.cur_tok = jnp.zeros((batch_slots,), jnp.int32)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_out: List[List[int]] = [[] for _ in range(batch_slots)]
        self.slot_budget = [0] * batch_slots
        self.queue: Deque[Request] = collections.deque()
        self.finished: List[Finished] = []

        # named, so that a profiler trace names their programs
        # jit_serve_decode and jit_serve_prefill
        def serve_decode(p, t, s):
            return M.decode_step(cfg, p, t, s, ctx)

        def serve_prefill(p, t):
            return M.prefill(cfg, p, t, cache_len, ctx)

        # the decode state is donated: the step writes its new rows into
        # the cache where it lies, and the engine keeps only the output
        self._decode = jax.jit(serve_decode, donate_argnums=2)
        self._prefill = jax.jit(serve_prefill)

    def _span(self, name: str, uid: Optional[int] = None):
        return _OFF if self.trace is None else self.trace.span(name, uid)

    def _count(self, name: str, n: int = 1):
        if self.trace is not None:
            self.trace.count(name, n)

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.queue.append(req)
        if self.trace is not None:
            self.trace.submitted(req.uid)

    def prefill(self, prompt):
        """Prefill one prompt [S] -> (logits [1, Vp], its decode state)."""
        return self._prefill(self.params, prompt[None, :])

    def _splice_slot(self, slot: int, logits, pstate):
        """Insert a prefilled request's cache into the batch cache."""
        def put(batch_leaf, single_leaf, ax):
            idx = [slice(None)] * batch_leaf.ndim
            idx[ax] = slice(slot, slot + 1)
            take = [slice(None)] * single_leaf.ndim
            take[ax] = slice(0, 1)
            return batch_leaf.at[tuple(idx)].set(single_leaf[tuple(take)])

        with self._span("splice"):
            self.state["caches"] = jax.tree.map(
                put, self.state["caches"], pstate["caches"],
                self._batch_axis)
            self.state["pos"] = self.state["pos"].at[slot].set(
                pstate["pos"][0])
        with self._span("first_token"):
            self._count("host_syncs")
            tok = int(jnp.argmax(logits[0]))
        self.cur_tok = self.cur_tok.at[slot].set(tok)

    def _finish_slot(self, slot: int):
        req = self.slot_req[slot]
        self.finished.append(Finished(req.uid, self.slot_out[slot]))
        self.slot_req[slot] = None
        self.slot_out[slot] = []

    def _admit(self):
        for slot in range(self.B):
            # loop: a request whose budget is exhausted at admit time (or
            # whose prefill-sampled token is already EOS) finishes
            # immediately and frees the slot for the next queued request
            # within the same admit pass.
            while self.slot_req[slot] is None and self.queue:
                req = self.queue.popleft()
                if self.trace is not None:
                    self.trace.admitting(req.uid)
                with self._span("admit", req.uid):
                    self._count("admitted")
                    self._count("prefill_tokens", len(req.prompt))
                    with self._span("prefill"):
                        logits, pstate = self.prefill(req.prompt)
                    self._splice_slot(slot, logits, pstate)
                    self.slot_req[slot] = req
                    with self._span("first_token"):
                        self._count("host_syncs")
                        tok = int(self.cur_tok[slot])
                    self.slot_out[slot] = [tok]
                    # the prefill-sampled token is the first emitted
                    # token, so only max_new_tokens - 1 decode steps remain.
                    self.slot_budget[slot] = req.max_new_tokens - 1
                    if self.slot_budget[slot] <= 0 or tok == req.eos_id:
                        self._finish_slot(slot)

    def tick(self) -> int:
        """One engine iteration: admit, decode one token for all slots."""
        with self._span("tick"):
            self._count("ticks")
            self._admit()
            active = [s for s in range(self.B)
                      if self.slot_req[s] is not None]
            if not active:
                return 0
            with self._span("decode"):
                logits, self.state = self._decode(self.params, self.cur_tok,
                                                  self.state)
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # start the transfer now: it runs as soon as the argmax ends
                next_tok.copy_to_host_async()
            self.last_logits = logits
            self.cur_tok = next_tok
            # the pull below would wait here too; waiting first keeps the
            # wait for the device apart from the pull
            with self._span("device_wait"):
                jax.block_until_ready(next_tok)
            with self._span("pull"):
                self._count("host_syncs")
                self._count("decoded_tokens", len(active))
                toks = _to_host(next_tok)
                for s in active:
                    tok = int(toks[s])
                    self.slot_out[s].append(tok)
                    self.slot_budget[s] -= 1
                    req = self.slot_req[s]
                    if self.slot_budget[s] <= 0 or tok == req.eos_id:
                        self._finish_slot(s)
            return len(active)

    def run_to_completion(self, max_ticks: int = 10_000) -> List[Finished]:
        ticks = 0
        while self.queue or any(r is not None for r in self.slot_req):
            if ticks >= max_ticks:
                raise EngineIncomplete(
                    self.finished, len(self.queue),
                    sum(r is not None for r in self.slot_req), max_ticks)
            self.tick()
            ticks += 1
        return self.finished
