"""Benchmark harness: one entry per paper table/figure and subsystem sweep.

Prints ``name,us_per_call,derived`` CSV at the end (harness contract) and
mirrors the rows into ``BENCH_sched.json`` so perf trajectory is machine-
readable across PRs.

  python -m benchmarks.run [--only exp1|exp2|exp3|sched|backfill|preempt|faults|net_topo|sim_scale|telemetry|serve_fleet]
                           [--smoke]

``--smoke`` runs a reduced sweep of the jobs in ``SMOKE_JOBS``, each of
which shrinks its fleet sizes; the full paper-scale experiment replays are
skipped.
"""
import argparse
import inspect
import json


SMOKE_JOBS = ("sched", "sim_scale", "preempt", "backfill", "faults",
              "net_topo", "telemetry", "serve_fleet")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep (the SMOKE_JOBS only)")
    ap.add_argument("--json", default=None,
                    help="output path (default BENCH_sched.json, or "
                         "BENCH_sched_smoke.json under --smoke; not "
                         "written for --only partial runs)")
    args = ap.parse_args()
    json_path = args.json or ("BENCH_sched_smoke.json" if args.smoke
                              else "BENCH_sched.json")
    csv_rows = []
    from benchmarks import (backfill, exp1_single_type, exp2_mixed,
                            exp3_frameworks, faults, net_topo, preempt,
                            sched_efficiency, serve_fleet, sim_scale,
                            telemetry)
    jobs = {"exp1": exp1_single_type.run, "exp2": exp2_mixed.run,
            "exp3": exp3_frameworks.run, "sched": sched_efficiency.run,
            "backfill": backfill.run, "preempt": preempt.run,
            "faults": faults.run, "net_topo": net_topo.run,
            "sim_scale": sim_scale.run,
            "telemetry": telemetry.run, "serve_fleet": serve_fleet.run}
    for name, fn in jobs.items():
        if args.only and args.only != name:
            continue
        if args.smoke and not args.only and name not in SMOKE_JOBS:
            continue
        if "smoke" in inspect.signature(fn).parameters:
            fn(csv_rows, smoke=args.smoke)
        else:
            fn(csv_rows)
    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")
    if args.only and not args.json:
        print("(partial --only run: not overwriting BENCH_sched.json; "
              "pass --json PATH to write)")
        return
    with open(json_path, "w") as f:
        json.dump({"smoke": args.smoke,
                   "rows": [{"name": n, "us_per_call": round(us, 1),
                             "derived": str(d)}
                            for n, us, d in csv_rows]}, f, indent=2)
    print(f"wrote {json_path}")


if __name__ == '__main__':
    main()
