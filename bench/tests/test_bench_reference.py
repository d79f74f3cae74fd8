"""Each plain reference against the program's model at a scaled-down size
on the CPU, on the benchmark's own weights."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import qwen2

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _small_qwen2(vocab=2048):
    from repro.configs import get_config, scaled_down
    cfg = scaled_down(get_config("qwen2-0.5b"), vocab=vocab)
    c = json.loads((CONFIGS / "qwen2-0.5b.json").read_text())
    c.update(hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
             num_hidden_layers=cfg.n_layers,
             num_attention_heads=cfg.n_heads,
             num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
             vocab_size=cfg.vocab)
    return cfg, c


def _params(cfg, c, seed=3):
    from repro.models import model as M
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    return weights.make(shapes, seed, qwen2.weight_rules(c))


@pytest.mark.parametrize("vocab", [512, 2048])
def test_qwen2_logits_match_program_forward(vocab):
    from repro.models import model as M
    cfg, c = _small_qwen2(vocab)
    p = _params(cfg, c)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (48,), 0,
                                         vocab), np.int32)
    with jax.default_matmul_precision("highest"):
        want = M.forward(cfg, p, jnp.asarray(toks)[None])[0][0, :, :vocab]
        h = qwen2.hidden(c, p, jnp.asarray(toks))
        got = h @ p["embed"][:vocab].T
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # served tokens are the argmax: gap 0; any other token: a positive gap
    tgt = np.asarray(jnp.argmax(want, -1), np.int32)
    gaps = qwen2.score(c, p, toks, tgt, block=16)
    assert float(jnp.max(gaps)) < 1e-4
    assert float(jnp.min(qwen2.score(c, p, toks, (tgt + 1) % vocab,
                                     block=16))) > 0


def test_qwen2_control_targets_are_the_float8_argmax():
    from repro.models import model as M
    cfg, c = _small_qwen2(2048)
    p = _params(cfg, c)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (64,), 0,
                                         2048), np.int32)
    tgt = np.full((64,), -1, np.int32)
    tgt[10:40] = 0
    got = np.asarray(qwen2.control_targets(c, p, toks, tgt, block=16))
    assert (got[tgt < 0] == -1).all()
    assert ((got[tgt >= 0] >= 0) & (got[tgt >= 0] < 2048)).all()
    with jax.default_matmul_precision("highest"):
        want = M.forward(cfg, p, jnp.asarray(toks)[None])[0][0, :, :2048]
    best = np.asarray(jnp.argmax(want, -1))
    # a step down in precision puts another token first at some positions,
    # and the float32 reference scores those above 0
    assert (got[10:40] != best[10:40]).any()
    assert float(jnp.max(qwen2.score(c, p, toks, got, block=16))) > 0
