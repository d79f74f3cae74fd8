"""The paper's contribution, grown to a three-layer scheduling stack.

**Application layer** — decides *what to ask for* and *who goes first*:

* ``planner`` (Algorithm 1) — granularity selection: the job's
  profile (network / CPU / memory, ``profiles``) picks how many workers,
  nodes and groups a submission should request;
* ``controller`` (Algorithm 2) — the MPI-aware task->worker mapping,
  per-worker resource requests and the hostfile; it also stamps the
  per-submission JobId (``Workload.uid``) onto every worker of the gang;
* ``queues`` — pluggable :class:`~repro.core.queues.QueueDiscipline`
  objects owning the *order* of the pending queue and the preemption
  decision: ``fifo`` (seed semantics, default), ``priority`` (classes +
  aging + gang preemption: a blocked high-class head kills-and-requeues
  the cheapest running gangs below its class — placement-aware under the
  contention estimator, clearing the *right* node for the head's widest
  worker), and ``fairshare`` (weighted multi-tenant deficit accounting
  over consumed slot-seconds).  ``Workload.tenant`` /
  ``Workload.priority`` are the identities they read;
* ``serving`` — the online serving tier (``Scenario.serving``, default
  ``None`` = off): a second application-layer workload species.
  SLO-classed request traffic (:class:`~repro.core.serving.SLOClass`,
  diurnal Poisson streams via ``scenarios.diurnal_request_stream``)
  served by autoscaled replica gangs that are *ordinary jobs* to the
  layers below — scale-up admission flows through the queue discipline
  and placement policy, replica speed is the engine's contention model
  (colocation with training slows serving, measurably), scale-down
  returns capacity through the reserved-capacity overlay with a
  ``downscale_hold`` warm-capacity window (the third overlay writer,
  coordinating via ``claimed_slots()`` with the discipline's resume
  claims and the fault engine's growth holds).  Request dispatch has
  its own discipline knob (``"slo"`` class-priority vs ``"fifo"``),
  the benchmark's two arms.  **Gating contract** (the
  faults/topology/telemetry pattern): ``Scenario.serving is None``
  constructs no tier, every engine hook is one ``is not None`` check,
  the request stream draws from its own RNG — all pre-serving golden
  trace hashes stay byte-identical;
* ``estimates`` — pluggable :class:`~repro.core.estimates
  .RuntimeEstimator` objects owning *runtime predictions*
  (``Scenario.estimator``): ``remaining`` (the seed's optimistic
  full-speed estimate, trace-pinned default) and ``contention`` (the
  job's roofline class + planned granularity run through the *engine's
  own speed model* — the pure ``estimates.job_speed`` shared with
  ``Simulator._speed`` — against current memory-bandwidth co-location
  and per-node ``mem_bw_tasks``).  Consumers: the EASY/conservative
  backfill window and preemption victim costing; every start stamps
  ``JobRun.predicted_finish_t`` for accuracy accounting
  (``benchmarks/backfill.py``).

**Infrastructure layer** — decides *where and when* those requests run,
with no knowledge of why they were shaped that way:

* ``policies`` — pluggable :class:`~repro.core.policies.PlacementPolicy`
  objects owning admission + binding: the K8s ``default`` scheduler
  (random feasible placement), ``taskgroup`` (Algorithms 3+4 via
  ``taskgroup``: balanced groups, affinity/anti-affinity scoring),
  ``easy-backfill`` (head-of-queue reservations over the *discipline's*
  head, beyond-paper) and ``conservative-backfill`` (drains-before-
  shadow skip-ahead only).  **Reservation-overlay contract**: a policy
  that must protect capacity during a placement passes a reserved-
  capacity overlay (``{node: slots withheld}``) through ``place()``;
  binders subtract it in every feasibility check exactly like their own
  staged demand, and shared cluster state — ``Node.used``, the Fenwick
  indexes, capacity listeners — never observes the reservation;
* ``cluster`` — the node/slot/domain model with a Fenwick free-capacity
  index serving O(log C) feasibility queries on heterogeneous fleets,
  per-value position Fenwick trees for order-statistic queries (count /
  select the j-th feasible node in cluster order), and per-node
  ``mem_bw_tasks`` so heterogeneous fleets are *modeled* (bandwidth
  saturation per host), not just schedulable;
* ``topology`` — the network-topology layer, sitting *between* the
  cluster model and the estimates: a node -> rack-switch -> spine tree
  (``Node.switch`` / ``Node.pod``) with per-link bandwidth derived from
  the cluster's ``intra_bw / inter_bw / cross_pod_bw`` fields and live
  per-link traffic accounting (registered on gang start, released on
  every teardown path including elastic shrink).  The gang's
  bottleneck-link stress replaces the flat ``net_internode`` factor in
  the pure ``job_speed`` — prediction and execution read one model —
  and the task-group binder packs NETWORK gangs under one switch via
  the per-switch dimension of ``taskgroup.ScoreIndex`` (admission stays
  O(polylog N)).  Links are first-class fault targets: the registry is
  symmetry-audited (every registered flow releases exactly once) and
  ``set_link_health`` scales one link's bandwidth and ripples a refresh
  to every gang riding it, so a degraded uplink slows exactly the
  traffic crossing it.  ``Scenario.topology is None`` (default) removes
  the layer entirely — every hook gated, flat traces byte-identical;
* gang admission and the progress-based event loop live in ``simulator``;
  admission cost is O(polylog N) per event: the task-group binder's
  argmax is a live ``taskgroup.ScoreIndex`` query maintained across
  gangs, its per-gang specials rescan is an incremental staged-score
  overlay (O(W log W) per gang), and EASY reservations are projected
  lazily from the engine's finish heap (per-phase counters in
  ``Simulator.perf`` attribute the remaining per-event cost, including
  preemption counts and wasted work);
* ``faults`` — the fault model + resilience subsystem, spanning both
  layers.  Infrastructure side: a seeded stochastic injector (per-node
  exponential/Weibull MTBF, correlated whole-domain failures, degraded
  nodes threaded through ``job_speed`` as a scale factor) drives a node
  **lifecycle contract**: ``healthy -> cordoned (draining) -> down ->
  recovering`` (or ``-> dead`` for permanent faults).  Cordoned nodes
  are excluded from placement via the reservation-overlay contract
  above — never by mutating ``Node.used`` — and draining gangs get a
  grace window to finish or reach a checkpoint boundary before
  teardown.  Application side: a per-scenario
  :class:`~repro.core.faults.ResiliencePolicy` gives fault-killed gangs
  retry budgets with exponential backoff + jitter, failure-domain
  avoidance on restart, Young/Daly-optimal per-job checkpoint intervals
  (``JobRun.ckpt_interval``, honoured by every checkpoint-quantized
  teardown), and elastic gang shrinking at checkpoint boundaries
  (``Workload.elastic``).  Recovery is *complete*, not just survival:
  link-scoped faults (``FaultConfig.link_mtbf``) down or degrade
  individual leaf/uplink/spine links through the topology layer's
  health hook; shrunken elastic gangs stage deterministic growth claims
  and re-expand to full width at their next checkpoint boundary
  (``ResiliencePolicy.regrow`` — claims are staged at most
  ``regrow_lead`` seconds ahead of the boundary so reserved capacity
  never idles for a whole checkpoint interval, re-quantized if speeds
  drift, planned best-fit with an own-node preference so holds don't
  fragment whole-host capacity); and preemption victims get
  resume-reservations (``queue_cfg["resume_reservation"]``) — the
  discipline withholds the victim's freed slots in the reserved-
  capacity overlay until it restarts, exempting only the victim itself.
  The two overlay writers coordinate through
  ``QueueDiscipline.claimed_slots()``: the regrow planner treats
  resume-claimed capacity as occupied, so a growth hold can never lock
  a victim out of its own reservation.  All retry/regrow timers carry
  per-job sequence tokens; every teardown path bumps the token, so a
  stale event can never resurrect a cancelled recovery.  The
  estimator's predictions inflate by the expected rework under the
  active fault model.  ``Scenario.faults is None`` (the default)
  removes the subsystem entirely — every hook is gated on it, keeping
  fault-free traces byte-identical.

**Observability layer** — watches both layers without perturbing either:

* ``telemetry`` — the fleet telemetry layer (``Scenario.telemetry``,
  default ``None`` = off).  A structured trace stream (typed
  ``submit / admit / start / finish / preempt / checkpoint / shrink /
  regrow / fault / link_health / reservation`` records emitted from the
  engine's *shared* code paths into a pluggable
  :class:`~repro.core.telemetry.TraceSink`), the counter registry that
  *is* ``Simulator.perf`` (:data:`~repro.core.telemetry.COUNTERS`
  documents every counter; ``new_perf_counters`` builds the dict the
  simulator mutates, so existing ``sim.perf`` reads are read-through
  aliases), sim-time sampled gauges (utilization, per-tenant queue
  depth, reserved-overlay slots, link saturation, node lifecycle
  census), Chrome ``trace_event`` timeline export and an
  estimator-calibration audit.  **Gating contract**: every hook in
  ``simulator`` / ``queues`` / ``faults`` / ``topology`` / ``policies``
  is a single ``is not None`` check when the layer is off — no record
  is built, no RNG stream is touched, every golden trace hash stays
  byte-identical.  Because both event loops route lifecycle transitions
  through the same hooks, the stream doubles as a cross-loop
  correctness oracle (``telemetry.diff_streams``).

The stack composes freely — any queue discipline over any placement
policy (``Scenario.queue`` x ``Scenario.placement``), dispatched without
touching the event loop.  The layers meet only at the ``(Workload,
Granularity, WorkerSpec)`` hand-off and the queue list, which is what
makes them swappable: ``meshplan`` binds the same application-layer
decisions to JAX meshes/sharding for real jobs, while
``simulator``+``scenarios`` replay the paper's evaluation, the
fleet-scale heavy-traffic scenarios and the long-horizon diurnal
multi-tenant scenarios (``scenarios.diurnal_poisson``) against any
registered discipline/policy pair.
"""
from repro.core.cluster import (Cluster, Node, fleet_cluster, hetero_cluster,
                                paper_cluster)
from repro.core.controller import allocate_tasks, hostfile, make_workers
from repro.core.estimates import (ESTIMATORS, ContentionEstimator,
                                  RemainingEstimator, RuntimeEstimator,
                                  job_speed, make_estimator)
from repro.core.faults import (FaultConfig, FaultEngine, ResiliencePolicy,
                               make_faults)
from repro.core.planner import Granularity, select_granularity
from repro.core.policies import (POLICIES, ConservativeBackfillPolicy,
                                 DefaultPolicy, EasyBackfillPolicy,
                                 PlacementPolicy, TaskGroupPolicy,
                                 make_policy)
from repro.core.profiles import (MEM_WEIGHT, PAPER_BENCHMARKS, Profile,
                                 Workload)
from repro.core.queues import (QUEUES, FairShareQueue, FifoQueue,
                               PriorityQueue, QueueDiscipline, make_queue)
from repro.core.scenarios import (SCENARIOS, TENANT_CLASSES, diurnal_poisson,
                                  diurnal_request_stream, get_scenario,
                                  poisson_heavy_traffic)
from repro.core.serving import (DEFAULT_SLO_CLASSES, ServeRequest,
                                ServingConfig, ServingTier, SLOClass,
                                make_serving)
from repro.core.simulator import PerfParams, Scenario, Simulator
from repro.core.telemetry import (COUNTERS, RingSink, Telemetry,
                                  TelemetryConfig, TraceRecord, TraceSink,
                                  chrome_trace, describe_counters,
                                  diff_streams, make_telemetry)
from repro.core.topology import (NetworkTopology, TopologyConfig,
                                 make_topology)
from repro.core import taskgroup

__all__ = ["Cluster", "Node", "fleet_cluster", "hetero_cluster",
           "paper_cluster", "allocate_tasks", "hostfile", "make_workers",
           "ESTIMATORS", "RuntimeEstimator", "RemainingEstimator",
           "ContentionEstimator", "job_speed", "make_estimator",
           "FaultConfig", "FaultEngine", "ResiliencePolicy", "make_faults",
           "Granularity", "select_granularity", "POLICIES",
           "PlacementPolicy", "DefaultPolicy", "TaskGroupPolicy",
           "EasyBackfillPolicy", "ConservativeBackfillPolicy",
           "make_policy", "MEM_WEIGHT", "PAPER_BENCHMARKS",
           "Profile", "Workload", "QUEUES",
           "QueueDiscipline", "FifoQueue", "PriorityQueue",
           "FairShareQueue", "make_queue", "SCENARIOS", "TENANT_CLASSES",
           "diurnal_poisson", "diurnal_request_stream", "get_scenario",
           "poisson_heavy_traffic", "DEFAULT_SLO_CLASSES", "SLOClass",
           "ServeRequest", "ServingConfig", "ServingTier", "make_serving",
           "PerfParams", "Scenario", "Simulator", "COUNTERS",
           "RingSink", "Telemetry", "TelemetryConfig", "TraceRecord",
           "TraceSink", "chrome_trace", "describe_counters",
           "diff_streams", "make_telemetry", "NetworkTopology",
           "TopologyConfig", "make_topology", "taskgroup"]
