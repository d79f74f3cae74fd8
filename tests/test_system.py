"""End-to-end system tests: train loop + checkpoint/restart + serving."""
import tempfile

import jax
import jax.numpy as jnp

from repro.configs import get_config, scaled_down
from repro.data import DataConfig, SyntheticLM
from repro.ckpt import checkpoint as CK
from repro.models import model as M
from repro.optim import get_optimizer, warmup_cosine
from repro.serve.engine import Engine, Request
from repro.train.trainer import init_state, make_train_step, train_loop


def _setup(arch="smollm-360m", n_units=2):
    cfg = scaled_down(get_config(arch), n_units=n_units)
    opt = get_optimizer("adamw", warmup_cosine(1e-3, 5, 200))
    state = init_state(cfg, jax.random.PRNGKey(0), opt, max_seq=64)
    ctx = M.Ctx(remat=False, ce_chunk=0)
    step = make_train_step(cfg, ctx, opt)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  global_batch=4))
    return cfg, opt, state, ctx, step, data


def test_loss_decreases_over_training():
    cfg, opt, state, ctx, step, data = _setup()
    jitted = jax.jit(step)
    tree = state.tree()
    losses = []
    it = iter(data)
    for _ in range(40):
        tok, lab = next(it)
        tree, mets = jitted(tree, tok, lab, {})
        losses.append(float(mets["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_checkpoint_restart_is_bit_exact():
    """Train 10 steps, checkpoint, train 5 more; restart from the checkpoint
    and replay — identical final state (fault-tolerance guarantee)."""
    cfg, opt, state, ctx, step, data = _setup()
    jitted = jax.jit(step)
    tree = state.tree()
    with tempfile.TemporaryDirectory() as d:
        it = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                    global_batch=4))
        for _ in range(10):
            tok, lab = next(it)
            tree, _ = jitted(tree, tok, lab, {})
        CK.save(d, tree, step=10)
        cont = tree
        for _ in range(5):
            tok, lab = next(it)
            cont, _ = jitted(cont, tok, lab, {})

        # simulated failure: restore and replay with a fresh pipeline
        restored = CK.restore(d, tree)
        it2 = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                     global_batch=4))
        it2.state.step = 10                      # resume the data stream
        for _ in range(5):
            tok, lab = next(it2)
            restored, _ = jitted(restored, tok, lab, {})
        for a, b in zip(jax.tree.leaves(cont), jax.tree.leaves(restored)):
            assert jnp.array_equal(a, b), "restart diverged"


def test_grad_accumulation_matches_large_batch():
    cfg, opt, state, ctx, _, _ = _setup()
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  global_batch=8))
    tok, lab = next(data)
    step1 = jax.jit(make_train_step(cfg, ctx, opt))
    stepA = jax.jit(make_train_step(cfg, ctx, opt, accum_steps=4))
    t1, m1 = step1(state.tree(), tok, lab, {})
    tA, mA = stepA(state.tree(), tok.reshape(4, 2, 32),
                   lab.reshape(4, 2, 32), {})
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(t1["params"]),
                            jax.tree.leaves(tA["params"])))
    assert d < 5e-5, d


def test_serving_engine_continuous_batching():
    cfg, opt, state, ctx, step, data = _setup()
    eng = Engine(cfg, state.params, batch_slots=2, cache_len=64, ctx=ctx)
    for i in range(5):                       # more requests than slots
        eng.submit(Request(uid=i, prompt=jnp.arange(4 + i,
                                                    dtype=jnp.int32),
                           max_new_tokens=3 + i % 2))
    fins = eng.run_to_completion()
    assert sorted(f.uid for f in fins) == [0, 1, 2, 3, 4]
    for f in fins:
        assert len(f.tokens) >= 3


def test_serving_matches_offline_decode():
    """Engine output == naive prefill+argmax-decode for the same prompt."""
    cfg, opt, state, ctx, step, data = _setup()
    params = state.params
    prompt = jnp.arange(6, dtype=jnp.int32)
    eng = Engine(cfg, params, batch_slots=1, cache_len=64, ctx=ctx)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
    out = eng.run_to_completion()[0].tokens

    lg, st_ = M.prefill(cfg, params, prompt[None], 64, ctx)
    toks = [int(jnp.argmax(lg[0]))]
    cur = jnp.array([toks[-1]], jnp.int32)
    for _ in range(3):
        lg, st_ = M.decode_step(cfg, params, cur, st_, ctx)
        toks.append(int(jnp.argmax(lg[0])))
        cur = jnp.array([toks[-1]], jnp.int32)
    assert out == toks


def test_donated_train_loop_matches_undonated_steps():
    """train_loop donates the state to each step: same losses as the plain
    jitted step, and the caller's input buffers are consumed."""
    cfg, opt, state, ctx, step, data = _setup()
    jitted = jax.jit(step)
    tree = state.tree()
    want = []
    for tok, lab in (next(data) for _ in range(3)):
        tree, mets = jitted(tree, tok, lab, {})
        want.append(float(mets["loss"]))

    fresh = init_state(cfg, jax.random.PRNGKey(0), opt, max_seq=64)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  global_batch=4))
    out, mets = train_loop(cfg, fresh, step, iter(data), 3, log_every=0)
    assert mets["loss_history"].tolist() == want
    assert int(out["step"]) == 3
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(fresh.params))
