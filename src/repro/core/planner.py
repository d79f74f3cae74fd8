"""Algorithm 1 — Granularity Selection (the application-layer planner agent).

Faithful transcription of the paper's pseudocode:

    if policy == "scale":
        network:      N_n = 1, N_w = 1,   N_g = 1
        cpu|memory:   N_n = min(N_n, N_t), N_w = N_n, N_g = N_n
    elif policy == "granularity":
        network:      N_n = 1, N_w = 1,   N_g = 1
        cpu|memory:   N_n = min(N_n, N_t), N_w = N_t, N_g = N_n
    else:
        N_n = 1, N_w = N_w (user default), N_g = N_n

The planner's inputs are the job metadata (N_t fixed by the user — the
``mpirun -np`` count / number of model shards), the *profile* (see
``profiles.py``), and the
cluster size (the paper reads it from Prometheus; we read it from the
Cluster object).

Granularity is a pure function of (profile, N_t, cluster size) — the
per-submission ``Workload.uid`` rides through untouched and first matters
downstream, when the controller stamps it onto the gang's ``WorkerSpec``s
for Algorithm 4's group keys.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.cluster import Cluster
from repro.core.profiles import Profile, Workload


@dataclasses.dataclass(frozen=True)
class Granularity:
    n_tasks: int      # N_t (fixed)
    n_nodes: int      # N_n
    n_workers: int    # N_w
    n_groups: int     # N_g
    policy: str

    @property
    def tasks_per_worker(self) -> int:
        return -(-self.n_tasks // self.n_workers)


def select_granularity(job: Workload, cluster: Cluster,
                       policy: Optional[str],
                       default_n_workers: int = 1) -> Granularity:
    """Algorithm 1.  ``policy`` in {"scale", "granularity", None}."""
    n_t = job.n_tasks
    n_w = default_n_workers
    n_n = len(cluster.nodes)                 # SystemInfo (max available)

    if policy == "scale":
        if job.profile == Profile.NETWORK:
            n_n, n_w, n_g = 1, 1, 1
        else:                                # CPU || memory (incl. mixed)
            n_n = min(n_n, n_t)
            n_w, n_g = n_n, n_n
    elif policy == "granularity":
        if job.profile == Profile.NETWORK:
            n_n, n_w, n_g = 1, 1, 1
        else:
            n_n = min(n_n, n_t)
            n_w, n_g = n_t, n_n
    else:
        n_n, n_g = 1, 1
        n_w = max(1, n_w)

    return Granularity(n_tasks=n_t, n_nodes=n_n, n_workers=n_w, n_groups=n_g,
                       policy=policy or "default")
