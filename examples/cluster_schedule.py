"""End-to-end reproduction of the paper's Experiment 2 + a fleet-mode run.

    PYTHONPATH=src python examples/cluster_schedule.py

Left: the paper's 4-node platform, 20 mixed MPI jobs, all six scenarios.
Right: the same two-layer scheduler driving a 2-pod TPU fleet with
arch-derived workloads (profiles from each architecture's size and MoE).
"""
import random

from repro.core.cluster import fleet_cluster, paper_cluster
from repro.core.profiles import PAPER_BENCHMARKS
from repro.core.scenarios import SCENARIOS
from repro.core.simulator import Simulator
from repro.launch.schedule import fleet_jobs


def paper_mode():
    rng = random.Random(7)
    jobs = [w for w in PAPER_BENCHMARKS.values() for _ in range(4)]
    rng.shuffle(jobs)
    subs = list(zip(jobs, sorted(rng.uniform(0, 1200) for _ in jobs)))
    print("== paper platform: 20 mixed jobs, six scenarios ==")
    base = {}
    for scn in ("NONE", "CM", "CM_S", "CM_G", "CM_S_TG", "CM_G_TG"):
        sim = Simulator(paper_cluster(), SCENARIOS[scn], seed=7)
        done = sim.run(list(subs))
        resp = Simulator.overall_response(done)
        mk = Simulator.makespan(done)
        base[scn] = resp
        extra = ""
        if scn != "NONE":
            extra = f"  ({1 - resp / base['NONE']:+.1%} resp vs NONE)"
        print(f"  {scn:9s} response={resp:8.0f}s makespan={mk:7.0f}s{extra}")


def fleet_mode():
    """Fleet nodes = 16-chip ICI slices (TPU allocation granularity).
    With 4-chip host-granular nodes, coarse 16-chip workers are outright
    unschedulable — the fleet version of the paper's usability argument."""
    host_granular = fleet_cluster(2, 64, 4)
    sim = Simulator(host_granular, SCENARIOS["CM"], seed=3)
    sim.run(fleet_jobs(40, seed=3))
    print("\n== TPU fleet, host-granular nodes (4 chips) ==")
    print(f"  CM        UNSCHEDULABLE: {len(sim.unschedulable)} of 40 — "
          "16-chip coarse workers cannot fit 4-chip hosts")

    print("== TPU fleet, slice-granular nodes (2 pods x 16 slices x 16) ==")
    for scn in ("CM", "CM_S", "CM_G_TG"):
        sim = Simulator(fleet_cluster(2, 16, 16), SCENARIOS[scn], seed=3)
        done = sim.run(fleet_jobs(40, seed=3))
        print(f"  {scn:9s} response={Simulator.overall_response(done):8.0f}s"
              f" makespan={Simulator.makespan(done):7.0f}s"
              f" (unschedulable={len(sim.unschedulable)})")


if __name__ == "__main__":
    paper_mode()
    fleet_mode()
