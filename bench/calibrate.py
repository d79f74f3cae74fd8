"""Measurements that set a cell's fixed numbers; not part of a run.

    python3 bench/calibrate.py sweep   --workload W --rates 1,2,3 \\
        --populations 20,40,60 --ramp 60 --seconds 40
    python3 bench/calibrate.py control --workload W --seeds 1,2,3 --seconds 45
    python3 bench/calibrate.py repeat  --workload W --seeds 1,2,3 --seconds 45

``sweep`` offers a serve cell's mix at each rate in one process (one
engine, compiled once): from the given population in flight, an untimed
ramp of ``--ramp`` seconds, then ``--seconds`` measured.  Per rate it
prints what was completed, how long requests waited, and the slots in
flight and the queue over the measured part: the knee is the highest rate
whose queue does not grow, and the mean in flight there is the steady
state's population.  ``control`` runs the cell as it stands once per seed
in one process, with the float8 control in the program's place
(``bench/control.py``), and prints the program's numbers beside the
control's: the two readings a limit is set between.  ``repeat`` runs
``bench/run.py`` once per seed, each a process of its own, and prints each
metric's median and quartile spread.  Lines go to stdout and, with
``--out``, to a file.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _emit(obj, out):
    line = json.dumps(obj)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def _setup_jax():
    # a cache the machine provides outlives this process; else the checkout's
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".bench_cache" / "jax"))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def sweep(args):
    _setup_jax()
    import numpy as np
    from bench import harness, program, tracing, traffic
    from bench.runners import serve
    cell = harness.find_cell(args.workload)
    devices = harness.chips_for(cell)
    c, tr = cell.config, cell.traffic
    vocab = c["vocab_size"]
    cfg = program.config(c)
    params, eng = serve.build(c, tr, args.seed, cfg)
    rates = [float(r) for r in args.rates.split(",")]
    pops = [int(p) for p in args.populations.split(",")] \
        if args.populations else [0] * len(rates)
    total = args.ramp + args.seconds
    runs = []
    for k, (rate, pop_n) in enumerate(zip(rates, pops)):
        mix = dict(tr, arrival=dict(tr["arrival"], rate_per_s=rate),
                   population=pop_n)
        runs.append((rate, traffic.serve_schedule(mix, total, args.seed + k,
                                                  vocab),
                     traffic.population(mix, args.seed + k, vocab)))
    serve.warm_up(eng, [r for _, s, p in runs for r in s + p], vocab)
    for rate, sched, pop in runs:
        eng.queue.clear()
        eng.slot_req = [None] * eng.B
        eng.slot_out = [[] for _ in range(eng.B)]
        eng.finished.clear()
        serve.admit(eng, pop)
        win = serve.Window(eng, sched, tracing.Spans())
        w0, w1, _ = win.run(total)
        m0 = w0 + args.ramp
        recs = [r for r in serve._records(win, sched, pop, w0, w1)
                if r["due"] is not None and r["due"] >= m0]
        ttft = [r["ttft_s"] for r in recs]
        gaps = [b - a for t in win.times.values()
                for a, b in zip(t, t[1:]) if a >= m0]
        toks = sum(1 for t in win.times.values() for x in t if x >= m0)
        occ = [(a, q) for t, a, q in win.occupancy if t >= m0]
        _emit({"rate_per_s": rate, "population": len(pop),
               "measured_s": w1 - m0, "requests": len(recs),
               "answered": sum(r["answered"] for r in recs),
               "live_slots_start": occ[0][0] if occ else None,
               "live_slots_mean": float(np.mean([a for a, _ in occ]))
               if occ else None,
               "live_slots_close": occ[-1][0] if occ else None,
               "queued_start": occ[0][1] if occ else None,
               "queued_close": occ[-1][1] if occ else None,
               "tokens_per_s": toks / (w1 - m0),
               "offered_tokens_per_s": rate * np.mean(
                   [r["max_new"] for r in sched]),
               "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50))
               if ttft else None,
               "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95))
               if ttft else None,
               "itl_p50_ms": 1e3 * float(np.percentile(gaps, 50))
               if gaps else None,
               "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95))
               if gaps else None,
               "device": devices[0].device_kind}, args.out)


def control(args):
    _setup_jax()
    from bench import harness
    from bench.control import float8_control
    cell = harness.find_cell(args.workload)
    devices = harness.chips_for(cell)
    drv = harness.runner(cell.traffic["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = harness.Record(cell=cell, seed=seed, seconds=args.seconds,
                             trace=False,
                             peak=harness.peaks(devices[0].device_kind))
        with float8_control():
            drv.run(rec, devices, t0=time.perf_counter(),
                    log=lambda s: print(s, file=sys.stderr, flush=True))
        _emit({"seed": seed, "program": rec.info["program_checks"],
               "control": rec.checks, "control_correct": rec.correct(),
               "occupancy": rec.occupancy,
               "sampled_tokens": rec.info["sampled_tokens"],
               "device": devices[0].device_kind}, args.out)


def spread(values):
    """(median, (q3 - q1) / median) by ``statistics.quantiles(n=4)``."""
    med = statistics.median(values)
    if len(values) < 2 or not med:
        return med, None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med


def repeat(args):
    by_metric = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", args.workload,
             "--seed", str(seed), "--seconds", str(args.seconds),
             "--trace", str(args.trace)],
            cwd=ROOT, capture_output=True, text=True, timeout=1500)
        lines = p.stdout.strip().splitlines()
        result = json.loads(lines[-1]) if p.returncode == 0 and lines \
            else None
        _emit({"seed": seed, "rc": p.returncode,
               "wall_s": time.perf_counter() - t, "result": result,
               "stderr_tail": p.stderr[-1500:]}, args.out)
        for name, m in (result or {}).get("metrics", {}).items():
            by_metric.setdefault(name, []).append(m["value"])
    _emit({"summary": {k: dict(zip(("median", "spread"), spread(v)),
                               n=len(v), values=v)
                       for k, v in by_metric.items()}}, args.out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("sweep", "control", "repeat"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, required=True)
        p.add_argument("--out", default=None)
        if name == "sweep":
            p.add_argument("--rates", required=True)
            p.add_argument("--populations", default=None)
            p.add_argument("--ramp", type=float, default=0.0)
            p.add_argument("--seed", type=int, default=1)
        else:
            p.add_argument("--seeds", required=True)
        if name == "repeat":
            p.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    {"sweep": sweep, "control": control, "repeat": repeat}[args.cmd](args)


if __name__ == "__main__":
    main()
