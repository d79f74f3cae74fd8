"""The serving engine's own record (``repro.serve.trace``) on a small engine:
tracing changes no token, spans nest under the step that caused them,
counters are exact, and an engine without a ``Trace`` records nothing."""
import pytest

pytestmark = pytest.mark.serving

# (prompt length, max_new_tokens) per request; 2 slots, so requests queue
REQUESTS = [(4, 3), (7, 1), (5, 4), (3, 2), (6, 3)]


@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, scaled_down
    from repro.models import model as M

    cfg = scaled_down(get_config("qwen2-0.5b"), n_units=2)
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32,
                           max_seq=64)
    return cfg, params, M.Ctx(remat=False, ce_chunk=0)


def _run(setup, trace):
    """Serves REQUESTS tick by tick; returns the engine, its finished
    tokens by uid, and the live slots of each tick."""
    import jax.numpy as jnp
    from repro.serve.engine import Engine, Request
    cfg, params, ctx = setup
    eng = Engine(cfg, params, batch_slots=2, cache_len=64, ctx=ctx,
                 trace=trace)
    for uid, (n, new) in enumerate(REQUESTS):
        eng.submit(Request(uid=uid, prompt=(jnp.arange(n, dtype=jnp.int32)
                                            * (uid + 3)) % cfg.vocab,
                           max_new_tokens=new))
    live = []
    while eng.queue or any(r is not None for r in eng.slot_req):
        live.append(eng.tick())
    return eng, {f.uid: f.tokens for f in eng.finished}, live


@pytest.fixture(scope="module")
def traced(setup):
    from repro.serve.trace import Trace
    trace = Trace()
    eng, tokens, live = _run(setup, trace)
    return trace, tokens, live


def test_tracing_changes_no_token(setup, traced):
    _, tokens, _ = traced
    _, plain, _ = _run(setup, None)
    assert tokens == plain
    assert {u: len(t) for u, t in tokens.items()} == \
        {u: new for u, (_, new) in enumerate(REQUESTS)}


def test_spans_nest_with_parents_and_uids(traced):
    from repro.serve.trace import SPANS
    trace, _, _ = traced
    spans = trace.spans
    assert {s.name for s in spans} <= set(SPANS)
    assert all(s.t1 is not None and s.t1 >= s.t0 for s in spans)
    queued = [s for s in spans if s.name == "queued"]
    assert sorted(s.uid for s in queued) == list(range(len(REQUESTS)))
    assert all(s.parent is None for s in queued)
    admits = [s for s in spans if s.name == "admit"]
    assert sorted(s.uid for s in admits) == list(range(len(REQUESTS)))
    for s in spans:
        if s.parent is None:
            assert s.name in ("tick", "queued")
            continue
        p = spans[s.parent]
        assert p.t0 <= s.t0 and s.t1 <= p.t1
        want = {"admit": "tick", "decode": "tick", "device_wait": "tick",
                "pull": "tick", "prefill": "admit", "splice": "admit",
                "first_token": "admit"}[s.name]
        assert p.name == want
        if want == "admit":
            assert s.uid == p.uid
    # a request waits until its admission starts
    for q in queued:
        a = next(s for s in admits if s.uid == q.uid)
        assert q.t1 <= a.t0
    per_admit = [sum(1 for s in spans if s.parent == i
                     and s.name == "first_token")
                 for i, s in enumerate(spans) if s.name == "admit"]
    assert per_admit == [2] * len(REQUESTS)


def test_counters_are_exact(traced):
    trace, tokens, live = traced
    c = trace.counters
    assert c["ticks"] == len(live)
    assert c["admitted"] == len(REQUESTS)
    assert c["prefill_tokens"] == sum(n for n, _ in REQUESTS)
    # two pulls per admission and one per tick that decoded, however many
    # slots it decoded
    assert c["host_syncs"] == 2 * c["admitted"] + sum(1 for n in live if n)
    assert c["decoded_tokens"] == sum(live) == \
        sum(len(t) - 1 for t in tokens.values())


def test_tick_children_cover_it(traced):
    trace, _, _ = traced
    spans = trace.spans
    ticks = [i for i, s in enumerate(spans) if s.name == "tick"]
    total = sum(spans[i].t1 - spans[i].t0 for i in ticks)
    covered = sum(s.t1 - s.t0 for s in spans if s.parent in set(ticks))
    assert covered <= total
    assert covered >= 0.95 * total


def test_summary(traced):
    trace, _, live = traced
    s = trace.summary()
    assert {k: s[k] for k in trace.counters} == trace.counters
    assert s["ticks"] == len(live)
    assert s["host_syncs_per_tick"] == pytest.approx(
        (2 * len(REQUESTS) + sum(1 for n in live if n)) / len(live))
    waits = [x.t1 - x.t0 for x in trace.spans if x.name == "queued"]
    assert 0 <= s["queue_wait_p95_ms"] <= 1e3 * max(waits)
    assert "queued" not in s["self_s"]
    busy = sum(x.t1 - x.t0 for x in trace.spans if x.name == "tick")
    assert sum(s["self_s"].values()) == pytest.approx(busy)
    assert trace.summary_line().startswith(f"engine: {len(live)} ticks")


def test_off_records_nothing(setup, monkeypatch):
    import jax
    from repro.serve.trace import Trace
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        made.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    idle = Trace()
    eng, _, _ = _run(setup, None)
    assert eng.trace is None and made == []
    assert idle.spans == [] and set(idle.counters.values()) == {0}
    trace = Trace()
    _run(setup, trace)
    hosted = [s for s in trace.spans if s.name != "queued"]
    assert made == [f"engine.{s.name}" for s in hosted]


def test_unregistered_names_are_refused():
    from repro.serve.trace import Trace
    t = Trace()
    with pytest.raises(KeyError):
        t.count("bogus")
    with pytest.raises(KeyError):
        with t.span("bogus"):
            pass
    assert t.spans == []


def test_programs_are_named(setup):
    import jax.numpy as jnp
    from repro.serve.engine import Engine
    cfg, params, ctx = setup
    eng = Engine(cfg, params, batch_slots=2, cache_len=64, ctx=ctx)
    assert eng._decode.__name__ == "serve_decode"
    assert eng._prefill.__name__ == "serve_prefill"
    text = eng._prefill.lower(params, jnp.zeros((1, 4), jnp.int32)).as_text()
    assert "@jit_serve_prefill" in text
    text = eng._decode.lower(params, eng.cur_tok, eng.state).as_text()
    assert "@jit_serve_decode" in text


def test_launcher_prints_the_engine_summary(capsys, monkeypatch):
    from repro.launch import serve
    # the persistent cache would stay on for this process's later tests
    monkeypatch.setattr(serve.compile_cache, "enable", lambda: "")
    fins = serve.main(["--arch", "qwen2-0.5b", "--reduced", "--requests", "3",
                       "--slots", "2", "--cache-len", "32",
                       "--prompt-lens", "4,8", "--max-new", "3"])
    assert len(fins) == 3
    lines = capsys.readouterr().out.splitlines()
    i = next(k for k, l in enumerate(lines) if l.startswith("served 3"))
    assert lines[i + 1].startswith("engine: ")
    assert "host syncs/tick" in lines[i + 1]
