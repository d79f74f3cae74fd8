"""Serve cells: an open-loop request stream through the program's engine.

Set-up makes the weights from the seed (``bench/weights.py``), builds
``repro.serve.engine.Engine`` over them at the mix's slots and cache
length, warms every shape the run uses (one prefill per prompt length,
the cache splice and the decode step) and admits the mix's steady-state
population.  The window then submits each request when it is due
(``Engine.submit``) and drives ``Engine.tick`` until ``--seconds`` have
passed.  Each request is timed from its scheduled arrival, so a stall
delays every request behind it.

Afterwards a sample of the finished requests, drawn from the seed and
holding the longest, is scored by the plain reference (``judge``): the
widest gap by which a served token's reference logit lies below the
reference's best.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Dict, List

from bench import flops, harness, program, tracing, traffic, weights


def build(c, tr, seed, cfg):
    """Weights from ``seed`` and an engine over them (set-up)."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.serve.engine import Engine
    ref = harness.reference(c["family"])
    dtype = jnp.dtype(c["serve_dtype"])
    shapes = jax.eval_shape(
        lambda k: M.init_params(cfg, k, dtype, max_seq=tr["cache_len"]),
        jax.random.PRNGKey(0))
    params = weights.make(shapes, seed, ref.weight_rules(c))
    eng = Engine(cfg, params, batch_slots=tr["slots"],
                 cache_len=tr["cache_len"], dtype=dtype)
    return params, eng


def warm_up(eng, requests, vocab):
    """One short request per prompt length of ``requests``: compiles each
    prefill, the splice and the decode step, and nothing the run does not
    use."""
    import numpy as np
    from repro.serve.engine import Request
    for i, n in enumerate(sorted({len(r["prompt"]) for r in requests})):
        eng.submit(Request(uid=-1 - i, prompt=np.full((n,), i % vocab,
                                                      np.int32),
                           max_new_tokens=2))
    eng.run_to_completion()
    eng.finished.clear()


def admit(eng, population):
    """Put the steady-state population into the engine's slots."""
    from repro.serve.engine import Request
    if len(population) > eng.B:
        raise harness.SpecError(f"population {len(population)} exceeds "
                                f"{eng.B} slots")
    for r in population:
        eng.submit(Request(uid=r["uid"], prompt=r["prompt"],
                           max_new_tokens=r["max_new"]))
    eng._admit()


WRAPPED = ("prefill", "_splice_slot", "_admit")


def unwrap(eng):
    """Take a previous window's wrappers off the engine instance."""
    for attr in WRAPPED:
        eng.__dict__.pop(attr, None)


class Window:
    """The open-loop window over one engine; fills per-request records."""

    def __init__(self, eng, schedule, spans):
        unwrap(eng)
        self.eng, self.spans = eng, spans
        self.schedule = schedule
        self.uid_of = {}
        self.first = {}                  # uid -> host time of token 1
        self.times = collections.defaultdict(list)   # uid -> token times
        # requests already in the slots (the population) are timed from
        # their first token in the window
        self.seen = {q.uid: len(out) for q, out in
                     zip(eng.slot_req, eng.slot_out) if q is not None}
        self.submitted = {}
        self.occupancy = []              # (host time, live slots, queued)
        self._admitting = None
        prefill, splice = eng.prefill, eng._splice_slot

        def prefill_w(prompt):
            self._admitting = self.uid_of.get(id(prompt))
            return prefill(prompt)

        def splice_w(slot, logits, pstate):
            # the splice ends by pulling the first token to the host
            splice(slot, logits, pstate)
            if self._admitting is not None:
                t = time.perf_counter()
                self.first[self._admitting] = t
                self.times[self._admitting].append(t)
                self.seen[self._admitting] = 1

        eng.prefill, eng._splice_slot = prefill_w, splice_w
        spans.wrap(eng, "_admit", "admit")

    def _receipts(self, t):
        eng = self.eng
        for s, req in enumerate(eng.slot_req):
            if req is not None:
                self._got(req.uid, len(eng.slot_out[s]), t)
        for f in eng.finished[self._n_fin:]:
            self._got(f.uid, len(f.tokens), t)
        self._n_fin = len(eng.finished)

    def _got(self, uid, n, t):
        new = n - self.seen.get(uid, 0)
        if new > 0:
            self.times[uid].extend([t] * new)
            self.seen[uid] = n

    def run(self, seconds, profiler=None, trace_seconds=0.0):
        import jax
        from repro.serve.engine import Request
        eng, spans = self.eng, self.spans
        pending = collections.deque(self.schedule)
        self._n_fin = len(eng.finished)
        w0 = time.perf_counter()
        deadline = w0 + seconds
        trace_at = deadline - trace_seconds if profiler else None
        ann = None
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            if trace_at is not None and now >= trace_at:
                profiler.start()
                ann = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
                ann.__enter__()
                trace_at = None
            while pending and w0 + pending[0]["arrival_s"] <= now:
                r = pending.popleft()
                self.uid_of[id(r["prompt"])] = r["uid"]
                eng.submit(Request(uid=r["uid"], prompt=r["prompt"],
                                   max_new_tokens=r["max_new"]))
                self.submitted[r["uid"]] = now
            if not eng.queue and all(q is None for q in eng.slot_req):
                due = w0 + pending[0]["arrival_s"] if pending else deadline
                with spans.span("wait"):
                    time.sleep(max(0.0, min(due, deadline)
                                   - time.perf_counter()))
                continue
            with spans.span("tick"):
                eng.tick()
            t = time.perf_counter()
            self._receipts(t)
            self.occupancy.append(
                (t, sum(q is not None for q in eng.slot_req), len(eng.queue)))
        w1 = time.perf_counter()
        summary = None
        if ann is not None:
            jax.block_until_ready(eng.state)
            ann.__exit__(None, None, None)
            summary = profiler.stop()
        return w0, w1, summary


def _records(win, schedule, population, w0, w1):
    """One record per request due in the window, then one per request of
    the population (``ttft_s`` None: it was admitted before the window).
    ``decoded`` counts the tokens that decode steps made in the window."""
    out = []
    for r in schedule:
        uid, due = r["uid"], w0 + r["arrival_s"]
        t = win.times.get(uid, [])
        first = win.first.get(uid)
        out.append({
            "uid": uid, "prompt_len": len(r["prompt"]),
            "max_new": r["max_new"], "due": due,
            "ttft_s": (first - due) if first is not None else (w1 - due),
            "answered": first is not None,
            "n_tokens": len(t), "decoded": max(0, len(t) - 1),
            "gaps_s": [b - a for a, b in zip(t, t[1:])],
            "late_s": win.submitted[uid] - due
            if uid in win.submitted else None})
    for r in population:
        t = win.times.get(r["uid"], [])
        out.append({
            "uid": r["uid"], "prompt_len": len(r["prompt"]),
            "max_new": r["max_new"], "due": None, "ttft_s": None,
            "answered": True, "n_tokens": len(t), "decoded": len(t),
            "gaps_s": [b - a for a, b in zip(t, t[1:])], "late_s": None})
    return out


def occupancy(win, eng, w0, w1):
    """Live slots over the window's ticks and at its close, and the cache
    positions that the live slots hold at the close."""
    import numpy as np
    ticks = [(a, q) for t, a, q in win.occupancy if w0 <= t <= w1]
    pos = np.asarray(eng.state["pos"])
    live = [s for s, q in enumerate(eng.slot_req) if q is not None]
    return {"slots": eng.B, "cache_len": eng.cache_len,
            "live_slots_mean": float(np.mean([a for a, _ in ticks]))
            if ticks else 0.0,
            "live_slots_at_close": len(live),
            "queued_at_close": len(eng.queue),
            "live_positions_at_close": int(pos[live].sum()) if live else 0}


def sample(finished: Dict[int, List[int]], prompt_len_of, seed, chk):
    """Finished requests to score: the longest, then others drawn from the
    seed until ``min_tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    uids = sorted(finished)
    longest = max(uids, key=lambda u: (prompt_len_of[u] + len(finished[u]),
                                       -u))
    order = [u for u in traffic.rng(seed, 3).permutation(uids)
             if u != longest]
    picked, n_tok = [longest], len(finished[longest])
    for u in order:
        if n_tok >= chk["min_tokens"] or len(picked) >= chk["max_requests"]:
            break
        picked.append(int(u))
        n_tok += len(finished[u])
    return picked


def sequences(c, tr, prompts, served):
    """uid -> (tokens, targets): the prompt and the served tokens, padded
    to one length for every request; ``targets[t]`` is the token served
    after tokens[: t + 1], -1 where none was.  None for a request that
    served an id outside the vocabulary."""
    import numpy as np
    block = c["reference_block"]
    span = traffic.longest(tr["prompt_len"]) \
        + traffic.longest(tr["output_len"]) + 1
    n = -(-span // block) * block
    out = {}
    for uid, toks in served.items():
        p = prompts[uid]
        if any(not 0 <= t < c["vocab_size"] for t in toks):
            out[uid] = None
            continue
        seq = np.zeros((n,), np.int32)
        tgt = np.full((n,), -1, np.int32)
        full = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
        seq[:len(full)] = full
        tgt[len(p) - 1:len(p) - 1 + len(toks)] = toks
        out[uid] = (seq, tgt)
    return out


def judge(rec, c, params, seqs, n_wrong_length):
    """``correct``'s numbers: the widest gap over every scored token (an id
    outside the vocabulary reads infinite), and the finished requests
    whose token count is not their ``max_new``."""
    import numpy as np
    ref = harness.reference(c["family"])
    gaps = {uid: float(np.asarray(ref.score(c, params, *st,
                                            block=c["reference_block"])
                                  ).max()) if st is not None
            else float("inf") for uid, st in seqs.items()}
    limit = c["check"]["serve_logit_gap"]
    rec.checks["logit_gap"] = {
        "value": max(gaps.values()) if gaps else float("inf"),
        "limit": limit}
    rec.checks["wrong_length"] = {"value": float(n_wrong_length),
                                  "limit": 0.0}
    rec.failed = n_wrong_length + sum(1 for g in gaps.values()
                                      if not g <= limit)
    rec.info["gaps"] = gaps


def run(rec, devices, *, t0, shrink=None, fault=None, log=print):
    import jax
    cell = rec.cell
    c, tr = cell.config, cell.traffic
    cfg = program.config(c)
    if shrink is not None:
        cfg, c, tr = shrink(cfg, c, tr)
    parts = rec.info.setdefault("setup_parts", {})
    with jax.default_device(devices[0]):
        t = time.perf_counter()
        params, eng = build(c, tr, rec.seed, cfg)
        jax.block_until_ready((eng.state, params))
        parts["build"] = time.perf_counter() - t
        if fault is not None:
            fault(eng)
        schedule = traffic.serve_schedule(tr, rec.seconds, rec.seed,
                                          c["vocab_size"])
        pop = traffic.population(tr, rec.seed, c["vocab_size"])
        t = time.perf_counter()
        warm_up(eng, schedule + pop, c["vocab_size"])
        jax.block_until_ready((eng.state, params))
        parts["warm_up"] = time.perf_counter() - t
        t = time.perf_counter()
        admit(eng, pop)
        jax.block_until_ready(eng.state)
        parts["population"] = time.perf_counter() - t
        spans = tracing.Spans(annotate=rec.trace)
        win = Window(eng, schedule, spans)
        compiles = _CompileCounter()
        profiler = tracing.Profiler(harness.ROOT / ".bench_cache"
                                    / "trace") if rec.trace else None
        trace_s = min(float(tr.get("trace_seconds", rec.seconds)),
                      rec.seconds)
        rec.setup_s = time.perf_counter() - t0
        with compiles:
            w0, w1, rec.trace_summary = win.run(rec.seconds, profiler,
                                                trace_s)
        rec.window_s = w1 - w0
        rec.memory = harness.memory_stats(devices)
        rec.occupancy = occupancy(win, eng, w0, w1)
        everyone = schedule + pop
        prompts = {r["uid"]: r["prompt"] for r in everyone}
        rec.requests = _records(win, schedule, pop, w0, w1)
        rec.spans = spans.within(w0, w1)
        finished = {f.uid: list(f.tokens) for f in eng.finished}
        want = {r["uid"]: r["max_new"] for r in everyone}
        bad = [u for u, t in finished.items() if len(t) != want[u]]
        _count(rec, c, finished, compiles)
        del eng, win
        gc.collect()
        picked = sample(finished, {u: len(p) for u, p in prompts.items()},
                        rec.seed, c["check"])
        served = {u: finished[u] for u in picked}
        rec.attempted = len(everyone)
        judge(rec, c, params, sequences(c, tr, prompts, served), len(bad))
        rec.info.update(sampled=len(picked),
                        sampled_tokens=sum(len(v) for v in served.values()))
    log(f"info {cell.name}: setup_s={rec.setup_s:.3f} "
        f"window_s={rec.window_s:.3f} requests={len(schedule)} "
        f"population={len(pop)} occupancy={rec.occupancy} "
        f"counters={rec.counters} setup_parts={parts}")


def _count(rec, c, finished, compiles):
    """Counters and required FLOPs of the work done in the window."""
    pre = dec = 0.0
    n_admit = n_dec = 0
    for r in rec.requests:
        if not r["answered"]:
            continue
        if r["due"] is not None:        # prefilled in the window
            n_admit += 1
            pre += flops.prefill(c, r["prompt_len"])
        # the k-th decoded token sees the prompt, the prefill's token and
        # the k - 1 decoded before it
        for k in range(1, r["decoded"] + 1):
            dec += flops.decode(c, r["prompt_len"] + k)
            n_dec += 1
    rec.flops = {"prefill": pre, "decode": dec}
    late = sorted(x["late_s"] for x in rec.requests
                  if x["late_s"] is not None)
    rec.counters = {
        "admitted": n_admit, "decoded_tokens": n_dec,
        "finished": len(finished),
        "ticks": len(rec.spans.get("tick", [])),
        "compiles_in_window": compiles.n,
        "generator_late_p95_s": late[int(0.95 * (len(late) - 1))]
        if late else 0.0,
        "generator_late_max_s": late[-1] if late else 0.0}


COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class _CompileCounter:
    """Counts compilations (and compile-cache loads) while active."""

    def __init__(self):
        self.n = 0
        self._on = False
        from jax import monitoring

        def listen(name, *_a, **_k):
            if self._on and name in COMPILE_EVENTS:
                self.n += 1

        monitoring.register_event_duration_secs_listener(listen)

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
