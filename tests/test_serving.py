"""Online serving tier + decode-engine correctness.

Four layers of guarantees:

* **Gating is absolute**: ``Scenario.serving=None`` (the default) is the
  pre-serving engine — the golden trace hashes re-pinned here (paper
  scenario, PR-8 fault storm, PR-6 priority preemption) stay
  byte-identical, and no tier object is constructed.
* **Tier invariants**: no request is ever lost (arrived == completed +
  dropped, dropped == 0 while capacity exists), latency accounting is
  conserved (finish - arrive == wait + service for every request),
  replicas scale up *and* down through the shared engine paths, and the
  run drains completely — no replica, pending scale-up, overlay hold or
  claimed slot survives; both event loops agree on all of it.
* **SLO classes matter**: under an overloaded replica pool, class-aware
  dispatch keeps interactive latency where class-blind FIFO lets it
  collapse — the benchmark acceptance property, asserted small.
* **Engine regressions** (the PR's bugfixes): ``max_new_tokens=1`` emits
  exactly one token, an EOS sampled *at prefill* finishes the request,
  ``run_to_completion`` raises ``EngineIncomplete`` instead of silently
  returning partial results (both the still-queued and the in-flight
  path), and the deque admit queue preserves FIFO order.
"""
import dataclasses as dc
import hashlib
import random

import pytest

from repro.core import serving as SRV
from repro.core import telemetry as TEL
from repro.core.cluster import Cluster, Node, paper_cluster
from repro.core.faults import FaultConfig, ResiliencePolicy
from repro.core.profiles import PAPER_BENCHMARKS
from repro.core.scenarios import (SCENARIOS, diurnal_request_stream,
                                  poisson_heavy_traffic)
from repro.core.simulator import Simulator

pytestmark = pytest.mark.serving


def small_fleet(n_hosts=16, slots=4):
    return Cluster([Node(f"h{i}", n_slots=slots, n_domains=1)
                    for i in range(n_hosts)])


def exp2_subs(seed):
    rng = random.Random(seed)
    jobs = [w for w in PAPER_BENCHMARKS.values() for _ in range(4)]
    rng.shuffle(jobs)
    times = sorted(rng.uniform(0, 1200) for _ in jobs)
    return list(zip(jobs, times))


def trace_hash(sim, done):
    jobs = sorted(
        ((j.job.name, repr(j.submit_t), repr(j.start_t), repr(j.finish_t),
          tuple(sorted(j.nodes_used.items()))) for j in done),
        key=lambda t: (t[0], t[1]))
    uns = sorted((j.job.name, repr(j.submit_t)) for j in sim.unschedulable)
    return hashlib.sha256(repr((jobs, uns)).encode()).hexdigest()[:16]


def serve_scenario(**over):
    """FLEET_SERVE with a small, fast request stream."""
    base = SCENARIOS["FLEET_SERVE"]
    cfg = dc.replace(base.serving, n_requests=200, base_rps=4.0,
                     period=120.0, scale_interval=10.0,
                     scale_down_cooldown=30.0, downscale_hold=20.0,
                     max_replicas=4, **over)
    return dc.replace(base, serving=cfg)


def run_serving(scn=None, seed=0, n_jobs=30, legacy=False, n_hosts=16):
    scn = scn or serve_scenario()
    cluster = small_fleet(n_hosts)
    subs = poisson_heavy_traffic(n_jobs, cluster.total_slots, seed=seed,
                                 utilization=0.6)
    sim = Simulator(cluster, scn, seed=seed)
    done = sim.run(subs, legacy=legacy)
    return sim, done


# ----------------------------------------------------------------------
# gating: serving unset -> pre-PR-10 golden hashes byte-identical
# ----------------------------------------------------------------------
def test_serving_none_goldens_repinned():
    sim = Simulator(paper_cluster(), SCENARIOS["CM_G_TG"], seed=0)
    done = sim.run(exp2_subs(0))
    assert trace_hash(sim, done) == "a576e2d104c610df"
    assert sim.serving is None

    # the PR-8 fault-storm pin (FLEET_FAULTS + Daly ckpts + elastic)
    sc = dc.replace(SCENARIOS["FLEET_FAULTS"], ckpt_interval=250.0)
    subs = poisson_heavy_traffic(60, 64, seed=2, elastic_frac=0.3)
    sim = Simulator(small_fleet(16), sc, seed=2)
    done = sim.run(list(subs))
    assert trace_hash(sim, done) == "812dfa07a36af609"
    assert sim.serving is None

    # the PR-6 priority-preemption pin
    sc = dc.replace(SCENARIOS["FLEET_PRIO"],
                    queue_cfg={"preempt": True, "preempt_min_prio": 2,
                               "preempt_delay": 60.0})
    subs = [(dc.replace(w, priority=i % 3), t) for i, (w, t) in enumerate(
        poisson_heavy_traffic(60, 64, seed=2, unique_names=True))]
    sim = Simulator(small_fleet(16), sc, seed=2)
    done = sim.run(subs)
    assert trace_hash(sim, done) == "992fcda19f19cf0f"
    assert sim.serving is None


def test_explicit_none_matches_default():
    """``serving=None`` spelled out == the field's default."""
    sc = dc.replace(SCENARIOS["CM_G_TG"], serving=None)
    sim = Simulator(paper_cluster(), sc, seed=0)
    done = sim.run(exp2_subs(0))
    assert trace_hash(sim, done) == "a576e2d104c610df"


# ----------------------------------------------------------------------
# request stream determinism + shape
# ----------------------------------------------------------------------
def test_request_stream_deterministic_and_classed():
    a = diurnal_request_stream(300, seed=7)
    b = diurnal_request_stream(300, seed=7)
    assert [(r.rid, r.cls, r.t_arrive, r.prompt_tokens, r.decode_tokens)
            for r in a] == \
           [(r.rid, r.cls, r.t_arrive, r.prompt_tokens, r.decode_tokens)
            for r in b]
    assert [r.t_arrive for r in a] == sorted(r.t_arrive for r in a)
    classes = {r.cls for r in a}
    assert classes == {c.name for c in SRV.DEFAULT_SLO_CLASSES}
    assert all(r.prompt_tokens >= 1 and r.decode_tokens >= 1 for r in a)
    # a different seed gives a different stream
    c = diurnal_request_stream(300, seed=8)
    assert [r.t_arrive for r in a] != [r.t_arrive for r in c]


# ----------------------------------------------------------------------
# tier invariants: conservation, drain, scaling
# ----------------------------------------------------------------------
def test_no_request_lost_and_latency_conserved():
    sim, done = run_serving()
    srv = sim.serving
    n = srv.cfg.n_requests
    assert sim.perf["serve_requests"] == n
    assert len(srv.completed) + len(srv.dropped) == n
    assert not srv.dropped
    seen = set()
    for r in srv.completed:
        assert r.rid not in seen
        seen.add(r.rid)
        assert r.t_dispatch is not None and r.t_finish is not None
        assert r.t_arrive <= r.t_dispatch <= r.t_finish
        # conservation: end-to-end latency == queue wait + service
        assert abs(r.latency_s - (r.wait_s + r.service_s)) < 1e-9
        lat = srv.latency_stats()[r.cls]
        assert lat["n"] > 0


def test_run_drains_completely():
    sim, done = run_serving()
    srv = sim.serving
    cluster = sim.cluster
    assert cluster.free_slots == cluster.total_slots
    assert not sim.running and not sim.queue
    assert not srv.replicas and not srv._pending
    assert not srv._holds and srv.claimed_slots() == {}
    assert not srv.work_pending()
    # every staked hold was released (consumed or expired)
    assert sim.perf["serve_holds"] == sim.perf["serve_hold_released"]
    # the batch jobs all completed alongside the traffic
    batch = [jr for jr in done if jr.tenant != srv.cfg.tenant]
    assert len(batch) + len(sim.unschedulable) == 30


def test_autoscaler_scales_up_and_down():
    sim, done = run_serving()
    assert sim.perf["serve_scale_ups"] > 1      # beyond the warm floor
    assert sim.perf["serve_scale_downs"] > 0
    assert sim.perf["serve_scale_ups"] >= sim.perf["serve_scale_downs"]
    # replicas passed through the shared stop path into ``done``
    reps = [jr for jr in done if jr.tenant == sim.serving.cfg.tenant]
    assert len(reps) == sim.perf["serve_scale_downs"]


def test_heap_and_legacy_loops_agree():
    outs = []
    for legacy in (False, True):
        sim, done = run_serving(legacy=legacy)
        srv = sim.serving
        outs.append((
            round(sim.now, 9),
            sorted((jr.uid, round(jr.finish_t, 9)) for jr in done),
            [(r.rid, r.cls, round(r.t_dispatch, 9), round(r.t_finish, 9))
             for r in srv.completed],
            {k: v for k, v in sim.perf.items() if k.startswith("serve")}))
    assert outs[0] == outs[1]


def test_serving_survives_faults_without_losing_requests():
    """Node faults kill replicas mid-flight: their requests re-queue (the
    ``_ver`` stamp strands stale completions) and still all complete."""
    scn = serve_scenario()
    scn = dc.replace(scn, faults=FaultConfig(node_mtbf=1500.0),
                     resilience=ResiliencePolicy())
    sim, done = run_serving(scn=scn, seed=3)
    srv = sim.serving
    assert len(srv.completed) + len(srv.dropped) == srv.cfg.n_requests
    assert sim.perf["serve_completed"] == len(srv.completed)
    assert not srv.replicas and not srv._holds
    assert srv.claimed_slots() == {}


# ----------------------------------------------------------------------
# the overlay contract (third writer)
# ----------------------------------------------------------------------
def test_scale_down_hold_composes_and_exempts():
    sim, _ = run_serving(n_jobs=0)
    srv = sim.serving
    # stake a synthetic hold and check composition
    node = sim.cluster.nodes[0].name
    srv._holds[99] = {node: 2}

    class FakeJr:
        pass

    jr = FakeJr()
    merged = srv.merge_overlay(jr, None)
    assert merged == {node: 2}
    merged = srv.merge_overlay(jr, {node: 1})
    assert merged == {node: 3}
    # the tier's own pending scale-ups bypass the hold
    srv._pending[jr] = 42
    assert srv.is_exempt(jr)
    assert srv.merge_overlay(jr, {node: 1}) == {node: 1}
    del srv._pending[jr]
    # claimed_slots clamps to the node's free surplus
    assert srv.claimed_slots()[node] == 2
    srv._holds[99] = {node: 10_000}
    assert srv.claimed_slots()[node] == sim.cluster.node(node).free
    del srv._holds[99]


def test_replica_wider_than_fleet_rejected():
    scn = serve_scenario(replica_tasks=1000)
    with pytest.raises(ValueError):
        Simulator(small_fleet(4), scn, seed=0)


# ----------------------------------------------------------------------
# SLO-classed dispatch beats FIFO under overload (benchmark, small)
# ----------------------------------------------------------------------
def overload_scenario(discipline):
    base = SCENARIOS["FLEET_SERVE"]
    cfg = dc.replace(base.serving, n_requests=600, base_rps=8.0,
                     period=37.5, max_replicas=2, concurrency=8,
                     scale_interval=10.0, scale_down_cooldown=30.0,
                     downscale_hold=15.0, discipline=discipline)
    return dc.replace(base, serving=cfg)


def test_slo_dispatch_protects_interactive_under_overload():
    stats = {}
    for disc in ("slo", "fifo"):
        sim, _ = run_serving(scn=overload_scenario(disc), n_jobs=10)
        srv = sim.serving
        assert len(srv.completed) == srv.cfg.n_requests
        stats[disc] = srv.latency_stats()["interactive"]
    assert stats["slo"]["slo_attainment"] > stats["fifo"]["slo_attainment"]
    assert stats["slo"]["p99"] < stats["fifo"]["p99"]


# ----------------------------------------------------------------------
# telemetry integration
# ----------------------------------------------------------------------
def test_serving_counters_registered():
    for key in ("serve_requests", "serve_completed", "serve_requeued",
                "serve_dropped", "serve_slo_miss", "serve_scale_ups",
                "serve_scale_downs", "serve_holds", "serve_hold_released"):
        assert key in TEL.COUNTERS
    assert "scale" in TEL.KINDS


def test_serving_rides_telemetry():
    scn = dc.replace(serve_scenario(),
                     telemetry=TEL.TelemetryConfig(metrics_interval=20.0))
    sim, done = run_serving(scn=scn)
    tel = sim.telemetry
    kinds = {r.kind for r in tel.records()}
    assert "scale" in kinds
    scale_evs = [r for r in tel.records() if r.kind == "scale"]
    assert {r.get("event") for r in scale_evs} >= {"scale_up",
                                                   "replica_up",
                                                   "replica_down"}
    assert any("serving" in s for s in tel.samples)
    summ = tel.metrics_summary()
    assert summ["serving"]["completed"] == sim.serving.cfg.n_requests
    assert summ["counters"]["serve_requests"] == sim.serving.cfg.n_requests
    assert "interactive" in summ["serving"]["classes"]
    # the chrome exporter tolerates the new kind
    tel.chrome_trace()


# ----------------------------------------------------------------------
# decode-engine regressions (the PR's bugfixes)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine_setup():
    import jax
    from repro.configs import get_config, scaled_down
    from repro.models import model as M
    from repro.optim import get_optimizer, warmup_cosine
    from repro.train.trainer import init_state

    cfg = scaled_down(get_config("smollm-360m"), n_units=2)
    opt = get_optimizer("adamw", warmup_cosine(1e-3, 5, 200))
    state = init_state(cfg, jax.random.PRNGKey(0), opt, max_seq=64)
    return cfg, state.params, M.Ctx(remat=False, ce_chunk=0)


def make_engine(engine_setup, batch_slots=1):
    from repro.serve.engine import Engine
    cfg, params, ctx = engine_setup
    return Engine(cfg, params, batch_slots=batch_slots, cache_len=64,
                  ctx=ctx)


def test_max_new_tokens_one_emits_one_token(engine_setup):
    import jax.numpy as jnp
    from repro.serve.engine import Request
    eng = make_engine(engine_setup)
    eng.submit(Request(uid=0, prompt=jnp.arange(4, dtype=jnp.int32),
                       max_new_tokens=1))
    fins = eng.run_to_completion()
    assert len(fins) == 1
    assert len(fins[0].tokens) == 1          # was 2 before the fix


def test_budget_respected_for_every_n(engine_setup):
    import jax.numpy as jnp
    from repro.serve.engine import Request
    eng = make_engine(engine_setup, batch_slots=2)
    for n in (1, 2, 3, 5):
        eng.submit(Request(uid=n, prompt=jnp.arange(4, dtype=jnp.int32),
                           max_new_tokens=n))
    fins = eng.run_to_completion()
    assert {f.uid: len(f.tokens) for f in fins} == {1: 1, 2: 2, 3: 3, 5: 5}


def test_eos_on_prefill_token_finishes_immediately(engine_setup):
    import jax.numpy as jnp
    from repro.serve.engine import Request
    prompt = jnp.arange(6, dtype=jnp.int32)
    # reference run: what token does prefill sample first?
    eng = make_engine(engine_setup)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=2))
    first_tok = eng.run_to_completion()[0].tokens[0]
    # same prompt with that token as EOS: exactly one token, no decode
    eng = make_engine(engine_setup)
    eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=10,
                       eos_id=first_tok))
    fins = eng.run_to_completion()
    assert fins[0].tokens == [first_tok]


def test_run_to_completion_raises_with_queued_work(engine_setup):
    import jax.numpy as jnp
    from repro.serve.engine import EngineIncomplete, Request
    eng = make_engine(engine_setup)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=jnp.arange(4, dtype=jnp.int32),
                           max_new_tokens=8))
    with pytest.raises(EngineIncomplete) as ei:
        eng.run_to_completion(max_ticks=0)
    assert ei.value.n_queued == 3
    assert ei.value.n_in_flight == 0
    assert ei.value.finished == []


def test_run_to_completion_raises_with_in_flight_work(engine_setup):
    import jax.numpy as jnp
    from repro.serve.engine import EngineIncomplete, Request
    eng = make_engine(engine_setup)
    eng.submit(Request(uid=0, prompt=jnp.arange(4, dtype=jnp.int32),
                       max_new_tokens=2))
    eng.submit(Request(uid=1, prompt=jnp.arange(4, dtype=jnp.int32),
                       max_new_tokens=50))
    with pytest.raises(EngineIncomplete) as ei:
        eng.run_to_completion(max_ticks=3)
    # the short request finished inside the budget, the long one did not
    assert [f.uid for f in ei.value.finished] == [0]
    assert ei.value.n_in_flight == 1
    assert ei.value.n_queued == 0
    # the partial results are carried, and draining further completes
    fins = eng.run_to_completion()
    assert sorted(f.uid for f in fins) == [0, 1]
    assert len(fins[-1].tokens if fins[-1].uid == 1
               else fins[0].tokens) == 50


def test_admit_order_is_fifo(engine_setup):
    import jax.numpy as jnp
    from repro.serve.engine import Request
    eng = make_engine(engine_setup)               # one slot: strict serial
    for i in range(4):
        eng.submit(Request(uid=i, prompt=jnp.arange(3 + i,
                                                    dtype=jnp.int32),
                           max_new_tokens=2))
    fins = eng.run_to_completion()
    assert [f.uid for f in fins] == [0, 1, 2, 3]  # deque preserves order


def _offline_greedy(cfg, params, ctx, prompt, n_tokens):
    import jax.numpy as jnp
    from repro.models import model as M
    lg, st = M.prefill(cfg, params, prompt[None], 64, ctx)
    toks = [int(jnp.argmax(lg[0]))]
    for _ in range(n_tokens - 1):
        lg, st = M.decode_step(cfg, params, jnp.array([toks[-1]], jnp.int32),
                               st, ctx)
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def test_splice_finds_batch_axis_when_units_equal_slots(engine_setup):
    """A stacked cache is [n_units, B, ...]; with n_units == B the request
    must still land on the batch axis, not on the unit axis."""
    import jax.numpy as jnp
    from repro.serve.engine import Request
    cfg, params, ctx = engine_setup
    eng = make_engine(engine_setup, batch_slots=cfg.n_units)
    prompts = [jnp.arange(5, dtype=jnp.int32),
               jnp.arange(7, 16, dtype=jnp.int32)]
    for uid, prompt in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=4))
    got = {f.uid: f.tokens for f in eng.run_to_completion()}
    for uid, prompt in enumerate(prompts):
        assert got[uid] == _offline_greedy(cfg, params, ctx, prompt, 4)
