"""Cluster-scheduling launcher: run a job mix through the two-layer
scheduler on either the paper's 4-node platform or the TPU fleet.

    PYTHONPATH=src python -m repro.launch.schedule --scenario CM_G_TG
    PYTHONPATH=src python -m repro.launch.schedule --fleet --jobs 40
"""
from __future__ import annotations

import argparse
import random

from repro.core.cluster import fleet_cluster, paper_cluster
from repro.core.profiles import PAPER_BENCHMARKS, Profile, Workload
from repro.core.scenarios import SCENARIOS
from repro.core.simulator import Simulator


def fleet_jobs(n_jobs: int, seed: int = 0):
    """Arch-derived workloads for fleet mode: profiles from a heuristic on
    each architecture's size and whether it is an MoE."""
    from repro.configs import list_configs
    rng = random.Random(seed)
    mix = []
    for name, cfg in list_configs().items():
        prof = (Profile.NETWORK if cfg.param_count() < 2e9 and not cfg.moe
                else Profile.CPU if cfg.moe or cfg.param_count() > 1e10
                else Profile.MIXED)
        # n_tasks = number of model shards (16-chip slices of a 256 pod)
        mix.append(Workload(name, prof, 16, 300.0 + 50 * rng.random(),
                            arch=name))
    jobs = [rng.choice(mix) for _ in range(n_jobs)]
    times = sorted(rng.uniform(0, 1200) for _ in jobs)
    return list(zip(jobs, times))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="CM_G_TG",
                    choices=sorted(SCENARIOS))
    ap.add_argument("--fleet", action="store_true",
                    help="TPU fleet (2 pods) instead of the paper platform")
    ap.add_argument("--jobs", type=int, default=20)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    if args.fleet:
        cluster = fleet_cluster()
        subs = fleet_jobs(args.jobs, args.seed)
    else:
        cluster = paper_cluster()
        rng = random.Random(args.seed)
        jobs = [w for w in PAPER_BENCHMARKS.values() for _ in range(4)]
        rng.shuffle(jobs)
        jobs = (jobs * ((args.jobs + 19) // 20))[:args.jobs]
        times = sorted(rng.uniform(0, 1200) for _ in jobs)
        subs = list(zip(jobs, times))

    sim = Simulator(cluster, SCENARIOS[args.scenario], seed=args.seed)
    done = sim.run(subs)
    resp = Simulator.overall_response(done)
    mk = Simulator.makespan(done)
    print(f"{args.scenario}: {len(done)} jobs  overall_response={resp:.0f}s"
          f"  makespan={mk:.0f}s")
    by_type = {}
    for j in done:
        by_type.setdefault(j.job.name, []).append(j.running_time)
    for name, rts in sorted(by_type.items()):
        print(f"  {name:20s} avg_rt={sum(rts)/len(rts):8.1f}s n={len(rts)}")
    return done


if __name__ == "__main__":
    main()
