"""Workload profiles (Algorithm 1 input).

The paper classifies MPI jobs as network / CPU / memory intensive (hand-
classified from MPI profiling, its Fig. 3).  For an accelerator job the
classes stand for the dominant roofline term of its step:

    collective-bound  <->  "network"  (keep the job coarse / inside one domain)
    compute-bound     <->  "CPU"      (fine granularity is free, exploit it)
    hbm-bound         <->  "memory"   (fine granularity + balance to spread bw)

An architecture's class is set by a heuristic on its size and on whether
it is an MoE (``meshplan.default_profile``, ``launch.schedule.fleet_jobs``).

The paper's five calibration benchmarks are also encoded here so the cluster
simulator can reproduce the paper's experiments 1:1.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Dict, Optional


class Profile(str, enum.Enum):
    NETWORK = "network"    # collective-bound
    CPU = "cpu"            # compute-bound
    MEMORY = "memory"      # HBM-bound
    MIXED = "cpu+memory"   # MiniFE-style


# memory-bandwidth demand weight per task of each roofline class — the
# single source of truth for the engine's live per-node mem-load accounting
# (``Simulator``) and the contention estimator's co-location predictions
# (``estimates``): a mixed job presses the memory controllers at half the
# weight of a pure STREAM-class job
MEM_WEIGHT: Dict[Profile, float] = {Profile.MEMORY: 1.0, Profile.MIXED: 0.5}


@dataclasses.dataclass(frozen=True)
class Workload:
    """A schedulable job type for the cluster simulator.

    ``uid`` is the optional *per-submission* identity (the K8s job UID):
    two submissions of the same job type share ``name`` but never ``uid``.
    Simulators running with ``job_ids="uid"`` key gang membership on it
    (generating one if unset), so concurrent same-name jobs never alias in
    Algorithm 4 scoring; the seed-compatible ``job_ids="name"`` mode keys
    on ``name`` and ignores it.

    ``tenant`` and ``priority`` are the multi-tenant queueing identities
    (the K8s namespace and PriorityClass): the queue disciplines in
    ``repro.core.queues`` read them for fair-share deficit accounting and
    priority ordering / gang preemption.  The defaults put every job in
    one tenant at class 0 — indistinguishable from the pre-queueing
    behaviour under any discipline's tie-breaks.

    ``elastic`` marks a malleable gang (Kub-style checkpoint/restart
    elasticity): under the fault engine's ``elastic_shrink`` policy a
    partial node failure shrinks the gang at a checkpoint boundary —
    surviving workers absorb the lost tasks at proportionally reduced
    speed — instead of killing and requeueing the whole gang.
    """
    name: str
    profile: Profile
    n_tasks: int            # N_t (MPI processes / model shards)
    base_runtime: float     # seconds, best-case standalone fine-grained run
    arch: Optional[str] = None   # assigned architecture id, if arch-derived
    uid: Optional[str] = None    # per-submission identity (K8s job UID)
    tenant: str = "default"      # namespace for fair-share accounting
    priority: int = 0            # priority class (higher = sooner)
    elastic: bool = False        # malleable gang: may shrink on failure


# --- the paper's five benchmarks (HPCC + MiniFE), 16 MPI processes each ----
# base_runtime chosen so that the simulated Table III makespans land on the
# paper's reported values (see benchmarks/exp3_frameworks.py).
PAPER_BENCHMARKS: Dict[str, Workload] = {
    "EP-DGEMM": Workload("EP-DGEMM", Profile.CPU, 16, 700.0),
    "EP-STREAM": Workload("EP-STREAM", Profile.MEMORY, 16, 645.0),
    "G-FFT": Workload("G-FFT", Profile.NETWORK, 16, 560.0),
    "G-RandomRing": Workload("G-RandomRing", Profile.NETWORK, 16, 590.0),
    "MiniFE": Workload("MiniFE", Profile.MIXED, 16, 730.0),
}
