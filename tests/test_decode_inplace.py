"""The decode step updates its state in place.

The engine donates the decode state, and the step carries the stacked caches
through its layer scan, writing each attention layer's B new rows (and each
recurrent layer's state) where they lie.  These tests hold it to

* the tokens that a no-cache ``M.forward`` picks greedily, for a dense
  attention config with slots at different positions, a sliding-window
  config run past its window (the ring wraps) and the recurrent archs;
* its compiled structure on the CPU: the cache leaves are aliased to the
  outputs, and the temporaries stay below the size of the stacked cache;
* the engine's use of it: admission and the splice after donated ticks;
* the benchmark's ``correct`` against a stale cache planted in the step.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, scaled_down
from repro.models import model as M
from repro.serve.engine import Engine, Request

CTX = M.Ctx()


def _forward_greedy(cfg, params, prompt, n_tokens):
    """Greedy tokens from a full forward over the whole sequence per token:
    no cache at all."""
    seq = [int(t) for t in prompt]
    fwd = jax.jit(lambda p, t: M.forward(cfg, p, t, CTX)[0][0, -1])
    out = []
    for _ in range(n_tokens):
        tok = int(jnp.argmax(fwd(params, jnp.asarray([seq], jnp.int32))))
        out.append(tok)
        seq.append(tok)
    return out


def _serve(cfg, params, prompts, n_tokens, slots, cache_len=64):
    eng = Engine(cfg, params, batch_slots=slots, cache_len=cache_len,
                 ctx=CTX)
    for uid, prompt in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=n_tokens))
    return {f.uid: f.tokens for f in eng.run_to_completion()}


def _prompts(cfg, lengths, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(lengths))
    return [jax.random.randint(k, (n,), 0, cfg.vocab, jnp.int32)
            for k, n in zip(keys, lengths)]


CASES = {
    # dense GQA attention; three slots whose positions differ every tick
    "qwen2-attn": (scaled_down(get_config("qwen2-0.5b")), (3, 11, 6), 8),
    # sliding-window layers with an 8-slot ring: 6 + 14 positions wrap it
    "gemma3-local-ring": (scaled_down(get_config("gemma3-1b"),
                                      local_window=8), (6, 9), 14),
    "recurrentgemma-rglru": (scaled_down(get_config("recurrentgemma-2b"),
                                         local_window=8), (5, 7), 10),
    "rwkv6": (scaled_down(get_config("rwkv6-3b")), (4, 9), 8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_in_place_decode_matches_no_cache_forward(case):
    cfg, lengths, n_tokens = CASES[case]
    if "local" in cfg.pattern_unit:              # the ring wraps
        assert max(lengths) + n_tokens > cfg.local_window
    params = M.init_params(cfg, jax.random.PRNGKey(1), jnp.float32,
                           max_seq=64)
    prompts = _prompts(cfg, lengths)
    got = _serve(cfg, params, prompts, n_tokens, slots=len(prompts))
    for uid, prompt in enumerate(prompts):
        assert got[uid] == _forward_greedy(cfg, params, prompt, n_tokens), \
            f"{case}: request {uid}"


def _cache_bytes(state):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(state["caches"]))


def test_compiled_decode_aliases_cache_and_holds_no_copy_of_it():
    """The engine's decode program at a small qwen2-like shape: every cache
    byte is aliased to an output (the donation took), and the temporaries
    stay below the stacked cache (a per-step copy of it would not)."""
    cfg = scaled_down(get_config("qwen2-0.5b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32,
                           max_seq=512)
    eng = Engine(cfg, params, batch_slots=8, cache_len=512, ctx=CTX)
    ma = eng._decode.lower(params, eng.cur_tok,
                           eng.state).compile().memory_analysis()
    cache = _cache_bytes(eng.state)
    assert ma.alias_size_in_bytes >= cache
    assert ma.temp_size_in_bytes < cache, (ma.temp_size_in_bytes, cache)


@pytest.fixture(scope="module")
def dense():
    cfg = scaled_down(get_config("qwen2-0.5b"))
    return cfg, M.init_params(cfg, jax.random.PRNGKey(2), jnp.float32,
                              max_seq=64)


def test_tick_consumes_the_state_it_was_given(dense):
    cfg, params = dense
    eng = Engine(cfg, params, batch_slots=2, cache_len=64, ctx=CTX)
    eng.submit(Request(uid=0, prompt=_prompts(cfg, (5,))[0],
                       max_new_tokens=4))
    eng.tick()                              # admits, then decodes
    before = eng.state
    eng.tick()
    assert all(a.is_deleted() for a in jax.tree.leaves(before["caches"]))
    assert not any(a.is_deleted() for a in jax.tree.leaves(eng.state))


def test_admissions_between_donated_ticks(dense):
    """Two slots, six requests of different lengths and budgets: each
    admission splices into a state that donated ticks produced."""
    cfg, params = dense
    lengths, budgets = (4, 9, 2, 12, 7, 5), (3, 6, 2, 5, 4, 7)
    prompts = _prompts(cfg, lengths, seed=3)
    eng = Engine(cfg, params, batch_slots=2, cache_len=64, ctx=CTX)
    for uid, (prompt, n) in enumerate(zip(prompts, budgets)):
        eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=n))
    got = {f.uid: f.tokens for f in eng.run_to_completion()}
    assert sorted(got) == list(range(len(prompts)))
    for uid, (prompt, n) in enumerate(zip(prompts, budgets)):
        assert got[uid] == _forward_greedy(cfg, params, prompt, n), uid


def test_stale_cache_inside_the_donated_step_is_not_correct():
    """The benchmark's ``correct`` still catches a decode step that leaves
    its state unchanged.  With a donating engine such a step can only be
    planted inside the jitted step (a wrapper outside it would hand back
    the consumed buffers), where the unchanged state is a live, stale
    cache."""
    from bench.tests.test_bench_correct import _serve

    def keeps_state(eng):
        step = eng._decode.__wrapped__
        eng._decode = jax.jit(lambda p, t, s: (step(p, t, s)[0], s),
                              donate_argnums=2)

    rec = _serve(fault=keeps_state)
    assert not rec.correct(), rec.checks
    assert rec.checks["logit_gap"]["value"] > rec.checks["logit_gap"]["limit"]
