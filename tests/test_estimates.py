"""Contention-aware runtime prediction + reserved-capacity overlay.

Four layers of guarantees:

* **Overlay == masking**: the reserved-capacity overlay threaded through
  ``place()``/``schedule_job`` produces placements identical to the
  legacy ``Node.used`` masking it replaced, under random cluster churn
  (hypothesis twin-runs, with and without the live score index).
* **Estimator semantics**: resolution from the scenario, monotonicity in
  co-location (more sharers can never shorten a prediction), and the
  oracle twin-run — solo placed jobs are predicted *exactly*, contended
  ones within a bounded ratio, per roofline class.
* **Backfill behaviour**: the contention estimator defers a backfill
  whose full-speed estimate sneaks under the shadow time but whose
  contended runtime would overrun it — the head starts on time.
* **Invariant matrix** (estimator x easy/conservative x preemption, with
  failures): the PR-4 suite (no job lost, free >= 0 live, state drains)
  plus the reservation contract — a backfilled gang never consumes the
  withheld shadow-node capacity, and a failed placement leaves
  ``Node.used`` untouched (no masking side effects anywhere).
"""
import dataclasses as dc
import random

import pytest
from _hypothesis_compat import given, settings, st

from repro.core.cluster import Cluster, Node, hetero_cluster, paper_cluster
from repro.core.controller import make_workers
from repro.core.estimates import (ContentionEstimator, RemainingEstimator,
                                  job_speed, make_estimator)
from repro.core.planner import select_granularity
from repro.core.policies import DefaultPolicy
from repro.core.profiles import Profile, Workload
from repro.core.scenarios import SCENARIOS, diurnal_poisson
from repro.core.simulator import PerfParams, Scenario, Simulator
from repro.core import taskgroup as TG


def small_fleet(n_hosts=16, slots=4):
    return Cluster([Node(f"h{i}", n_slots=slots, n_domains=1)
                    for i in range(n_hosts)])


# ----------------------------------------------------------------------
# estimator resolution + the pure speed model
# ----------------------------------------------------------------------
def test_estimator_resolution_from_scenario():
    sim = Simulator(small_fleet(), SCENARIOS["CM_G_TG"])
    assert isinstance(sim.estimator, RemainingEstimator)
    assert isinstance(Simulator(small_fleet(),
                                SCENARIOS["FLEET_EASY_PRED"]).estimator,
                      ContentionEstimator)
    assert isinstance(Simulator(small_fleet(),
                                SCENARIOS["FLEET_CONS"]).estimator,
                      ContentionEstimator)
    bad = dc.replace(SCENARIOS["CM_G_TG"], estimator="nope")
    with pytest.raises(ValueError):
        Simulator(small_fleet(), bad)


@pytest.mark.property
@given(load=st.floats(0.0, 64.0), extra=st.floats(0.0, 64.0),
       sharing=st.integers(0, 4), tpw=st.integers(1, 16),
       prof=st.sampled_from(list(Profile)),
       affinity=st.booleans())
@settings(max_examples=200, deadline=None)
def test_job_speed_monotone_and_bounded(load, extra, sharing, tpw, prof,
                                        affinity):
    """speed <= 1 always; more memory load or more sharers can never
    speed a job up (the monotonicity the backfill window relies on)."""
    p = PerfParams()
    base = job_speed(p, affinity, prof, tpw, 1, 1,
                     ((load, p.mem_bw_tasks),), sharing)
    assert 0.0 < base <= 1.0
    loaded = job_speed(p, affinity, prof, tpw, 1, 1,
                       ((load + extra, p.mem_bw_tasks),), sharing)
    assert loaded <= base + 1e-12
    shared = job_speed(p, affinity, prof, tpw, 1, 1,
                       ((load, p.mem_bw_tasks),), sharing + 1)
    assert shared <= base + 1e-12


def test_contention_prediction_monotone_in_colocation():
    """Queued-gang predictions can only lengthen as sharers start: an
    impossible head stays queued while memory jobs are admitted one at a
    time, and its predicted runtime must be non-decreasing."""
    scn = SCENARIOS["FLEET_EASY_PRED"]
    sim = Simulator(small_fleet(8, slots=8), scn, seed=0)
    probe = Workload("probe", Profile.MEMORY, 512, 100.0)   # never fits
    sim.submit(probe, 0.0)
    head = sim.queue[0]
    prev = sim.estimator.runtime_queued(head)
    assert prev >= head.remaining            # never shorter than full speed
    for i in range(6):
        sim.submit(Workload(f"bg{i}", Profile.MEMORY, 8, 50.0, uid=f"b{i}"),
                   0.0)
        sim._try_admit(None)
        cur = sim.estimator.runtime_queued(head)
        assert cur >= prev - 1e-12
        prev = cur
    assert prev > head.remaining             # co-location became visible


# ----------------------------------------------------------------------
# oracle twin-run: predicted vs engine-actual finish
# ----------------------------------------------------------------------
SOLO_JOBS = [
    ("CM", Workload("cpu", Profile.CPU, 16, 100.0)),
    ("CM", Workload("mem", Profile.MEMORY, 16, 100.0)),   # self-saturating
    ("CM", Workload("mix", Profile.MIXED, 16, 100.0)),
    ("CM", Workload("net", Profile.NETWORK, 16, 100.0)),
    ("Volcano", Workload("net", Profile.NETWORK, 16, 100.0)),  # multi-node
]


@pytest.mark.parametrize("scn_name,job", SOLO_JOBS,
                         ids=[f"{s}-{j.name}" for s, j in SOLO_JOBS])
def test_solo_prediction_exact_per_class(scn_name, job):
    """A solo (uncontended) job's speed never changes, so the contention
    estimator — which shares the engine's speed model — must predict its
    finish to the float, for every roofline class and even under coarse
    granularity penalties the ``remaining`` estimate ignores."""
    scn = dc.replace(SCENARIOS[scn_name], estimator="contention")
    sim = Simulator(paper_cluster(), scn, seed=0)
    done = sim.run([(job, 0.0)])
    assert len(done) == 1
    jr = done[0]
    assert jr.predicted_finish_t == jr.finish_t          # float-exact
    # the optimistic estimator under-predicts whenever a penalty applies
    sim_r = Simulator(paper_cluster(), SCENARIOS[scn_name], seed=0)
    jr_r = sim_r.run([(job, 0.0)])[0]
    assert jr_r.predicted_finish_t <= jr_r.finish_t + 1e-9


def test_contended_predictions_bounded_per_class():
    """Contended predictions drift only as later events change
    co-location: per roofline class, the mean predicted/actual runtime
    ratio stays within a bounded band, and the contention estimator is
    tighter than ``remaining`` on the same trace."""
    mix = [Workload(f"m{i}", Profile.MEMORY, 16, 300.0) for i in range(6)] \
        + [Workload(f"x{i}", Profile.MIXED, 16, 250.0) for i in range(3)] \
        + [Workload(f"c{i}", Profile.CPU, 16, 200.0) for i in range(3)]
    subs = [(w, 0.0) for w in mix]

    def mean_err(est):
        scn = dc.replace(SCENARIOS["CM_G_TG"], estimator=est)
        sim = Simulator(paper_cluster(), scn, seed=0)
        done = sim.run(list(subs))
        assert len(done) == len(subs)
        by_class = {}
        for j in done:
            actual = j.finish_t - j.start_t
            pred = j.predicted_finish_t - j.start_t
            by_class.setdefault(j.job.profile, []).append(pred / actual)
        for prof, ratios in by_class.items():
            m = sum(ratios) / len(ratios)
            assert 0.25 <= m <= 4.0, (est, prof, m)
        return sum(abs(j.predicted_finish_t - j.finish_t)
                   / (j.finish_t - j.start_t) for j in done) / len(done)

    assert mean_err("contention") < mean_err("remaining")


# ----------------------------------------------------------------------
# reserved-capacity overlay == legacy Node.used masking (twin runs)
# ----------------------------------------------------------------------
def _rand_reserve(rng, cluster):
    """Random reserved-capacity overlay honouring the contract: a caller
    withholds part of a node's *existing* surplus (take <= free)."""
    out = {}
    for n in rng.sample(cluster.nodes, min(len(cluster.nodes),
                                           rng.randrange(0, 3))):
        if n.free > 0:
            out[n.name] = rng.randrange(1, n.free + 1)
    return out


@pytest.mark.property
@given(seed=st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_reserve_overlay_matches_legacy_masking(seed):
    """``schedule_job(..., reserve=...)`` must bind worker-for-worker like
    temporarily inflating ``Node.used`` by the reserved amounts (the
    masking hack it replaced), across random gangs, occupancy, releases
    and reserve shapes (takes up to the node's full surplus)."""
    rng = random.Random(seed)
    sizes = [rng.choice([2, 4, 8, 16, 32]) for _ in range(rng.randrange(4, 24))]

    def mk():
        return Cluster([Node(f"n{i}", n_slots=s, n_domains=1)
                        for i, s in enumerate(sizes)])

    c_ovl, c_msk = mk(), mk()
    b_ovl, b_msk = TG.BoundIndex(), TG.BoundIndex()
    for g in range(8):
        job = Workload(f"g{g % 3}", Profile.CPU, rng.randrange(2, 40), 100.0)
        gran = select_granularity(job, c_ovl, "granularity")
        uid = f"g{g}" if rng.random() < 0.5 else ""
        reserve = _rand_reserve(rng, c_ovl)
        w1 = make_workers(job, gran, uid=uid)
        w2 = make_workers(job, gran, uid=uid)
        p1 = TG.schedule_job(c_ovl, w1, gran.n_groups, bound=b_ovl,
                             reserve=reserve or None)
        for name, take in reserve.items():
            c_msk.node(name).used += take
        p2 = TG.schedule_job(c_msk, w2, gran.n_groups, bound=b_msk)
        for name, take in reserve.items():
            c_msk.node(name).used -= take
        assert (p1 is None) == (p2 is None)
        if p1 is not None:
            assert [w.node for w in p1] == [w.node for w in p2]
        # release a placed gang; the index may hold only emptied nodes
        placed = sorted({w.job for ws in b_ovl.workers.values() for w in ws})
        if rng.random() < 0.3 and placed:
            name = rng.choice(placed)
            for c, b in ((c_ovl, b_ovl), (c_msk, b_msk)):
                victims = [w for ws in b.workers.values()
                           for w in ws if w.job == name]
                for w in victims:
                    c.node(w.node).used -= w.n_tasks
                    b.remove(w)


@pytest.mark.property
@given(seed=st.integers(0, 5000))
@settings(max_examples=25, deadline=None)
def test_reserve_overlay_matches_masking_with_score_index(seed):
    """Same twin-run with a live ScoreIndex on the overlay side: the
    reserved-idx exclusion in ``best_plain`` must reproduce what masking
    (which moved nodes between index buckets) produced."""
    rng = random.Random(seed)
    sizes = [rng.choice([4, 8, 16]) for _ in range(12)]

    def mk():
        return Cluster([Node(f"n{i}", n_slots=s, n_domains=1)
                        for i, s in enumerate(sizes)])

    c_ovl, c_msk = mk(), mk()
    b_ovl, b_msk = TG.BoundIndex(), TG.BoundIndex()
    si = TG.ScoreIndex(c_ovl, b_ovl)
    for g in range(8):
        job = Workload(f"j{g % 4}", Profile.CPU, rng.randrange(2, 20), 50.0)
        gran = select_granularity(job, c_ovl, "granularity")
        uid = f"u{g}"
        reserve = _rand_reserve(rng, c_ovl)
        w1 = make_workers(job, gran, uid=uid)
        w2 = make_workers(job, gran, uid=uid)
        p1 = TG.schedule_job(c_ovl, w1, gran.n_groups, bound=b_ovl,
                             score_index=si, reserve=reserve or None)
        for name, take in reserve.items():
            c_msk.node(name).used += take
        p2 = TG.schedule_job(c_msk, w2, gran.n_groups, bound=b_msk)
        for name, take in reserve.items():
            c_msk.node(name).used -= take
        assert (p1 is None) == (p2 is None)
        if p1 is not None:
            assert [w.node for w in p1] == [w.node for w in p2]


@pytest.mark.property
@given(seed=st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_default_draw_overlay_matches_masking(seed):
    """The default binder expresses a reservation by seeding its staged
    map: the order-statistic keyed draw must pick the same node as a
    masked cluster would, for the same key."""
    rng = random.Random(seed)
    c = Cluster([Node(f"n{i}", n_slots=rng.choice([2, 4, 8, 32]),
                      n_domains=1) for i in range(rng.randrange(1, 40))])
    for n in c.nodes:
        n.used = rng.randrange(0, n.n_slots + 1)
    for _ in range(10):
        need = rng.randrange(1, 10)
        key = rng.randrange(1 << 30)
        reserve = _rand_reserve(rng, c)
        got = DefaultPolicy._draw_indexed(c, need, dict(reserve), key)
        for name, take in reserve.items():
            c.node(name).used += take
        feas = c.feasible_nodes(need)
        want = (feas[random.Random(key).randrange(len(feas))]
                if feas else None)
        masked = DefaultPolicy._draw_indexed(c, need, {}, key)
        for name, take in reserve.items():
            c.node(name).used -= take
        assert masked is want
        assert (got is None) == (want is None)
        if want is not None:
            assert got.name == want.name


# ----------------------------------------------------------------------
# backfill behaviour: the estimator actually protects the head
# ----------------------------------------------------------------------
def _head_protection_subs():
    filler = Workload("filler", Profile.CPU, 64, 50.0)
    head = Workload("head", Profile.CPU, 128, 100.0)     # needs every slot
    # 64 memory tasks -> 16/node: saturated (16 > mem_bw_tasks=13), so the
    # true runtime 40 x (16/13)^1.4 ~ 53.5s overruns the 50s shadow the
    # full-speed estimate (2 + 40 <= 50) sneaks under
    hog = Workload("hog", Profile.MEMORY, 64, 40.0)
    return [(filler, 0.0), (head, 1.0), (hog, 2.0)]


def test_contention_estimator_defers_contended_backfill():
    """Under ``remaining`` the hog backfills on its optimistic estimate,
    overruns the shadow time and delays the head; under ``contention``
    the predicted saturation keeps it out and the head starts exactly
    when the filler drains."""
    subs = _head_protection_subs()
    scn_r = SCENARIOS["CM_G_TG_EASY"]
    sim_r = Simulator(paper_cluster(), scn_r, seed=0)
    d_r = {j.job.name: j for j in sim_r.run(list(subs))}
    assert d_r["hog"].start_t == pytest.approx(2.0)      # backfilled...
    assert d_r["head"].start_t > d_r["filler"].finish_t + 1.0   # ...delayed

    scn_c = dc.replace(scn_r, estimator="contention")
    sim_c = Simulator(paper_cluster(), scn_c, seed=0)
    d_c = {j.job.name: j for j in sim_c.run(list(subs))}
    assert d_c["head"].start_t == pytest.approx(d_c["filler"].finish_t)
    assert d_c["hog"].start_t >= d_c["head"].start_t     # deferred
    assert d_c["head"].start_t < d_r["head"].start_t     # strictly better


def test_conservative_backfill_disables_slack_window():
    """A long narrow job that EASY would admit through the aggregate
    extra-slots exception must wait under conservative-backfill (only
    drains-before-shadow candidates skip ahead)."""
    filler = Workload("filler", Profile.CPU, 64, 50.0)
    head = Workload("head", Profile.CPU, 96, 100.0)      # extra slots: 32
    hog = Workload("hog", Profile.CPU, 32, 10_000.0)     # fits the slack
    subs = [(filler, 0.0), (head, 1.0), (hog, 2.0)]
    easy = Simulator(paper_cluster(),
                     dc.replace(SCENARIOS["CM_G_TG_EASY"],
                                estimator="contention"), seed=0)
    d_easy = {j.job.name: j for j in easy.run(list(subs))}
    assert d_easy["hog"].start_t == pytest.approx(2.0)   # slack window
    cons = Simulator(paper_cluster(),
                     dc.replace(SCENARIOS["CM_G_TG_EASY"],
                                placement="conservative-backfill",
                                estimator="contention"), seed=0)
    d_cons = {j.job.name: j for j in cons.run(list(subs))}
    assert d_cons["hog"].start_t >= d_cons["head"].start_t
    assert d_cons["head"].start_t == \
        pytest.approx(d_cons["filler"].finish_t)


# ----------------------------------------------------------------------
# placement-aware preemption victim costing
# ----------------------------------------------------------------------
def _victim_cluster():
    return Cluster([Node(f"h{i}", n_slots=4, n_domains=1) for i in range(4)]
                   + [Node("big", n_slots=8, n_domains=1)])


def _victim_subs():
    subs = [(Workload("batch8", Profile.NETWORK, 8, 500.0,
                      uid="b8", priority=0), 0.0)]
    for i in range(4):
        subs.append((Workload(f"batch4.{i}", Profile.NETWORK, 4, 500.0,
                              uid=f"b4{i}", priority=0), 0.001 * (i + 1)))
    subs.append((Workload("prod", Profile.NETWORK, 8, 100.0,
                          uid="p", priority=2), 10.0))
    return subs


@pytest.mark.parametrize("aware,max_kills", [(False, 5), (True, 1)])
def test_placement_aware_victim_choice_kills_fewer(aware, max_kills):
    """The prod head's widest worker (8 tasks) fits only the big node.
    Cheapest-first kills every cheap 4-wide gang on the small hosts
    before touching the one victim that actually helps; placement-aware
    costing clears the big node directly with a single kill."""
    cfg = {"preempt": True, "preempt_min_prio": 1, "preempt_delay": 0.0,
           "placement_aware": aware}
    scn = dc.replace(SCENARIOS["FLEET_PRIO"], queue_cfg=cfg,
                     estimator="contention" if aware else "remaining")
    sim = Simulator(_victim_cluster(), scn, seed=0)
    done = sim.run(_victim_subs())
    assert len(done) == 6
    d = {j.job.name: j for j in done}
    assert d["prod"].start_t == pytest.approx(10.0)
    assert sim.perf["preemptions"] == max_kills
    if aware:
        assert d["batch8"].preemptions == 1       # the right victim
        assert all(d[f"batch4.{i}"].preemptions == 0 for i in range(4))


def test_placement_aware_defaults_follow_estimator():
    """placement_aware defaults on exactly for contention scenarios."""
    cfg = {"preempt": True}
    scn = dc.replace(SCENARIOS["FLEET_PRIO"], queue_cfg=cfg)
    assert Simulator(small_fleet(), scn).discipline.placement_aware is False
    scn_c = dc.replace(scn, estimator="contention")
    assert Simulator(small_fleet(), scn_c).discipline.placement_aware is True


# ----------------------------------------------------------------------
# invariant matrix: estimator x backfill policy x preemption (+failures)
# ----------------------------------------------------------------------
MATRIX_WL = (
    Workload("fleet-cpu-16", Profile.CPU, 16, 150.0),
    Workload("fleet-mem-8", Profile.MEMORY, 8, 90.0),
    Workload("fleet-mem-16", Profile.MEMORY, 16, 120.0),
    Workload("fleet-mix-16", Profile.MIXED, 16, 180.0),
    Workload("fleet-net-4", Profile.NETWORK, 4, 60.0),
    # wide coarse gang: only the two 32-slot hosts qualify, so EASY's
    # shadow-node reservation (and its overlay) actually engages
    Workload("fleet-net-24", Profile.NETWORK, 24, 150.0),
)


def _matrix_scenario(estimator, placement, preempt):
    cfg = {"preempt": True, "preempt_min_prio": 2,
           "preempt_delay": 30.0} if preempt else None
    return Scenario(f"MATRIX_{estimator}_{placement}_{preempt}",
                    affinity=True, policy="granularity", taskgroup=True,
                    placement=placement, job_ids="uid",
                    queue="priority" if preempt else None, queue_cfg=cfg,
                    estimator=estimator)


@pytest.mark.property
@pytest.mark.parametrize("estimator", ["remaining", "contention"])
@pytest.mark.parametrize("placement", ["easy-backfill",
                                       "conservative-backfill"])
@pytest.mark.parametrize("preempt", [False, True])
def test_estimator_invariant_matrix(estimator, placement, preempt):
    """PR-4 invariants (no job lost, free >= 0 checked live, state drains
    clean) across the estimator/backfill/preemption matrix with node
    failures, plus the reservation contract: a slack-window backfill
    never consumes withheld shadow-node capacity, and a failed placement
    leaves ``Node.used`` byte-identical (the masking hack is gone)."""
    cluster = hetero_cluster(((12, 4), (2, 32)))

    class Guard:
        def on_free_change(self, name, free):
            node = cluster.node(name)
            assert 0 <= node.used <= node.n_slots
            assert free == node.n_slots - node.used

        def on_rebuild(self):
            pass

    cluster.attach(Guard())
    subs = diurnal_poisson(120, 112, seed=3, workloads=MATRIX_WL)
    sim = Simulator(cluster, _matrix_scenario(estimator, placement,
                                              preempt), seed=1)
    sim.failures = [(150.0, "h3", 200.0), (400.0, "h12", 100.0)]

    reserve_checks = [0]
    orig_place = sim.policy.place

    def checked_place(jr, use_index=True, reserve=None):
        if not reserve:
            return orig_place(jr, use_index, reserve)
        reserve_checks[0] += 1
        pre = {n: sim.cluster.node(n).used for n in reserve}
        placed = orig_place(jr, use_index, reserve)
        for n, take in reserve.items():
            node = sim.cluster.node(n)
            if placed is None:
                assert node.used == pre[n]       # no masking side effects
            else:
                got = sum(w.n_tasks for w in placed if w.node == n)
                # the withheld capacity was never consumed
                assert got <= max(0, node.n_slots - pre[n] - take)
        return placed

    sim.policy.place = checked_place
    done = sim.run(list(subs))
    assert len(done) + len(sim.unschedulable) == len(subs)
    assert len({j.uid for j in done}) == len(done)
    for j in done:
        assert j.finish_t is not None
        assert j.remaining <= 1e-6
        assert j.predicted_finish_t is not None
    assert not sim.running and not sim.queue
    assert sim.cluster.free_slots == sim.cluster.total_slots
    assert not sim._mem_load_live and not sim._node_jobs
    assert not sim.bound.by_key
    if placement == "easy-backfill":
        # the matrix really exercises the overlay: conservative backfill
        # never places past-shadow, so only the EASY cells assert it
        assert reserve_checks[0] > 0
    if preempt:
        assert sim.perf["preemptions"] >= 1
