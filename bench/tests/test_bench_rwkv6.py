"""RWKV-6 against its plain reference (``bench/reference/rwkv6.py``) at a
scaled-down size on the CPU, on the benchmark's own weights: the forward
pass, prefill then decode through the engine, and a shrunk run of the
``rwkv6-3b.serve-docs`` cell through its runner, sound and under the
float8 control."""
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.control import float8_control
from bench.reference import rwkv6

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
CELL = "rwkv6-3b.serve-docs"


def _small(c, vocab=2048):
    """The program's scaled-down rwkv6-3b and the benchmark config made to
    state its sizes."""
    from repro.configs import get_config, scaled_down
    cfg = scaled_down(get_config("rwkv6-3b"), vocab=vocab)
    c = dict(c, hidden_size=cfg.d_model, attention_hidden_size=cfg.d_model,
             head_size=cfg.d_model // cfg.n_heads,
             num_attention_heads=cfg.n_heads, intermediate_size=cfg.d_ff,
             num_hidden_layers=cfg.n_layers, vocab_size=cfg.vocab)
    return cfg, c


def _config(vocab=2048):
    return _small(json.loads((CONFIGS / "rwkv6-3b.json").read_text()), vocab)


def _params(cfg, c, seed=3):
    from repro.models import model as M
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    return weights.make(shapes, seed, rwkv6.weight_rules(c))


def _ref_logits(c, p, toks):
    h = rwkv6.hidden(c, p, jnp.asarray(toks))
    return h @ p["unembed"][:c["vocab_size"]].T


def test_every_leaf_has_a_weight_rule():
    cfg, c = _config()
    from repro.models import model as M
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    for path in weights.leaf_paths(shapes):
        weights._rule(rwkv6.weight_rules(c), path)


@pytest.mark.parametrize("vocab", [512, 2048])
def test_rwkv6_logits_match_program_forward(vocab):
    from repro.models import model as M
    cfg, c = _config(vocab)
    p = _params(cfg, c)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (75,), 0,
                                         vocab), np.int32)
    with jax.default_matmul_precision("highest"):
        want = M.forward(cfg, p, jnp.asarray(toks)[None])[0][0, :, :vocab]
        got = _ref_logits(c, p, toks)
    # both float32: the chunked WKV sums in another order than the
    # reference's token loop (measured 5e-7 at logits near 0.8)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    # served tokens are the argmax: gap 0; any other token: a positive gap
    tgt = np.asarray(jnp.argmax(want, -1), np.int32)
    pad = np.zeros((80,), np.int32)
    pad[:75] = toks
    tpad = np.full((80,), -1, np.int32)
    tpad[:75] = tgt
    assert float(jnp.max(rwkv6.score(c, p, pad, tpad, block=16))) < 1e-4
    tpad[:75] = (tgt + 1) % vocab
    gaps = np.asarray(rwkv6.score(c, p, pad, tpad, block=16))
    assert gaps[:75].min() > 0


def test_prefill_then_decode_through_engine_matches_reference():
    """Two requests, prompts of 13 and 21 tokens (not multiples of the WKV's
    block), prefilled and then decoded 10 and 12 steps in one batch: the
    logits of every step against the reference's full forward pass over
    the prompt and the tokens served."""
    from repro.serve.engine import Engine, Request
    cfg, c = _config()
    p = _params(cfg, c)
    prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(10 + i),
                                             (n,), 0, cfg.vocab), np.int32)
               for i, n in enumerate((13, 21))]
    budgets = (11, 13)
    with jax.default_matmul_precision("highest"):
        eng = Engine(cfg, p, batch_slots=2, cache_len=64)
        rows = {0: [], 1: []}             # uid -> logits of each step
        prefill, decode = eng.prefill, eng._decode

        def prefill_w(prompt):
            logits, st = prefill(prompt)
            rows[int(prompt.shape[0] == 21)].append(np.asarray(logits[0]))
            return logits, st

        def decode_w(params, tok, state):
            logits, state = decode(params, tok, state)
            for slot, req in enumerate(eng.slot_req):
                if req is not None:
                    rows[req.uid].append(np.asarray(logits[slot]))
            return logits, state

        eng.prefill, eng._decode = prefill_w, decode_w
        for uid, (pr, n) in enumerate(zip(prompts, budgets)):
            eng.submit(Request(uid=uid, prompt=jnp.asarray(pr),
                               max_new_tokens=n))
        eng.run_to_completion()
        served = {f.uid: f.tokens for f in eng.finished}
        for uid, pr in enumerate(prompts):
            toks = served[uid]
            assert len(toks) == budgets[uid]
            seq = np.concatenate([pr, np.asarray(toks[:-1], np.int32)])
            ref = np.asarray(_ref_logits(c, p, seq))[len(pr) - 1:]
            got = np.stack(rows[uid])[:, :cfg.vocab]
            assert got.shape == ref.shape == (budgets[uid], cfg.vocab)
            # float32 both: the prefill's chunked sums and the decode
            # steps' per-token sums against the reference's token loop
            np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5)
            assert toks == list(np.argmax(ref, -1))


def shrink(cfg, c, tr):
    cfg, c = _small(c)
    c = dict(c, reference_block=16)
    # set for this size from its own readings (the cell's limit, in
    # bench/configs, comes from chip readings at the published widths):
    # the program's widest gap read 0 to 0.0059 on seeds 1-12 (in bfloat16,
    # as the cell states) and the float8 control's 0.020 to 0.077, so 0.012
    c["check"] = dict(c["check"], serve_logit_gap=0.012)
    tr = dict(tr, slots=4, cache_len=64,
              prompt_len={"dist": "exponential", "mean": 12, "min": 1,
                          "max": 32, "buckets": [16, 32]},
              output_len={"dist": "exponential", "mean": 6, "min": 1,
                          "max": 16},
              arrival={"process": "poisson", "rate_per_s": 20.0},
              population=2, trace_seconds=1)
    return cfg, c, tr


def _serve(seed=2):
    cell = harness.find_cell(CELL)
    rec = harness.Record(cell=cell, seed=seed, seconds=2.0, trace=False,
                         peak={"bf16_flops": 1.0})
    harness.runner(cell.traffic["kind"]).run(
        rec, jax.devices(), t0=time.perf_counter(), shrink=shrink,
        log=lambda s: None)
    return rec


def test_shrunk_cell_is_correct():
    rec = _serve()
    assert rec.correct(), rec.checks
    assert rec.info["sampled_tokens"] >= 30
    pop = [r for r in rec.requests if r["due"] is None]
    assert len(pop) == 2 and all(r["decoded"] for r in pop)


def test_shrunk_cell_under_float8_control_is_not_correct():
    with float8_control():
        rec = _serve()
    assert all(harness._within(c)
               for c in rec.info["program_checks"].values())
    assert not rec.correct(), rec.checks
    assert rec.checks["logit_gap"]["value"] > rec.checks["logit_gap"]["limit"]
