"""The engine's tick pulls the decode step's tokens to the host once: one
transfer of the whole [B] token vector, whatever the number of live slots,
and the same served tokens, in the same order, as a loop that pulls each
live slot's token on its own."""
import pytest

pytestmark = pytest.mark.serving

SLOTS = 4
SEED = 1234


@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, scaled_down
    from repro.models import model as M

    cfg = scaled_down(get_config("qwen2-0.5b"), n_units=2)
    params = M.init_params(cfg, jax.random.PRNGKey(SEED), jnp.float32,
                           max_seq=64)
    return cfg, params, M.Ctx(remat=False, ce_chunk=0)


def _engine(setup, trace=None):
    from repro.serve.engine import Engine
    cfg, params, ctx = setup
    return Engine(cfg, params, batch_slots=SLOTS, cache_len=64, ctx=ctx,
                  trace=trace)


def _prompt(cfg, uid, n):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng([SEED, uid])
    return jnp.asarray(rng.integers(0, cfg.vocab, n), jnp.int32)


@pytest.mark.parametrize("live", [1, SLOTS // 2, SLOTS])
def test_a_decoding_tick_pulls_once(setup, live, monkeypatch):
    import jax.numpy as jnp
    from repro.serve import engine as E
    from repro.serve.engine import Request
    from repro.serve.trace import Trace
    cfg = setup[0]
    trace = Trace()
    eng = _engine(setup, trace)
    for uid in range(live):
        eng.submit(Request(uid=uid, prompt=_prompt(cfg, uid, 4 + uid),
                           max_new_tokens=8))
    assert eng.tick() == live          # admits and compiles; not counted

    pulls = []                         # the span open at each pull
    real = E._to_host

    def spy(x):
        pulls.append(trace.spans[trace._open[-1]].name)
        return real(x)

    # a per-slot pull would index the token vector or int() it on the
    # device; neither may happen in a tick that admits nothing
    device = type(jnp.zeros(()))
    on_device = []
    for attr in ("__int__", "__index__", "__getitem__"):
        def counting(self, *a, _real=getattr(device, attr), _attr=attr):
            on_device.append(_attr)
            return _real(self, *a)
        monkeypatch.setattr(device, attr, counting)
    monkeypatch.setattr(E, "_to_host", spy)
    for _ in range(3):
        syncs, n_spans = trace.counters["host_syncs"], len(trace.spans)
        pulls.clear()
        assert eng.tick() == live
        assert trace.counters["host_syncs"] == syncs + 1
        assert pulls == ["pull"]
        assert on_device == []
        mine = trace.spans[n_spans:]
        assert [s.name for s in mine if s.name == "pull"] == ["pull"]
        assert "first_token" not in {s.name for s in mine}


def _reference_tick(eng):
    """The tick as it was: one int() on the device per live slot."""
    import jax
    import jax.numpy as jnp
    eng._admit()
    active = [s for s in range(eng.B) if eng.slot_req[s] is not None]
    if not active:
        return 0
    logits, eng.state = eng._decode(eng.params, eng.cur_tok, eng.state)
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    eng.last_logits, eng.cur_tok = logits, next_tok
    jax.block_until_ready(next_tok)
    for s in active:
        tok = int(next_tok[s])
        eng.slot_out[s].append(tok)
        eng.slot_budget[s] -= 1
        if eng.slot_budget[s] <= 0 or tok == eng.slot_req[s].eos_id:
            eng._finish_slot(s)
    return len(active)


def _serve(setup, requests, tick):
    """Serves (uid, prompt length, max_new_tokens, eos_id) requests; returns
    the finished requests in the order they finished."""
    from repro.serve.engine import Request
    cfg = setup[0]
    eng = _engine(setup)
    for uid, n, new, eos in requests:
        eng.submit(Request(uid=uid, prompt=_prompt(cfg, uid, n),
                           max_new_tokens=new, eos_id=eos))
    while eng.queue or any(r is not None for r in eng.slot_req):
        tick(eng)
    return [(f.uid, f.tokens) for f in eng.finished]


def test_tokens_and_order_match_a_per_slot_loop(setup):
    from repro.serve.engine import Engine
    plain = [(uid, 3 + (5 * uid) % 9, 4 + (3 * uid) % 7, -2)
             for uid in range(9)]
    # EOS ids taken from what the requests serve without one: a token the
    # prefill samples, tokens the decode steps make, and none for the rest
    seen = dict(_serve(setup, plain, _reference_tick))
    at = {0: 0, 2: 1, 3: 2, 5: 2, 7: 3}
    requests = [(uid, n, new, seen[uid][at[uid]] if uid in at else -2)
                for uid, n, new, _ in plain]
    want = _serve(setup, requests, _reference_tick)
    got = _serve(setup, requests, Engine.tick)
    assert got == want
    new_of = {uid: new for uid, _, new, _ in requests}
    by_eos = [u for u, t in want if len(t) < new_of[u]]
    by_budget = [u for u, t in want if len(t) == new_of[u]]
    assert by_eos and by_budget
    assert 0 in by_eos and dict(want)[0] == seen[0][:1]
    # finishing order differs from submission order
    assert [u for u, _ in want] != sorted(new_of)
