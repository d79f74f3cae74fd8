"""Deliverable (f): per-architecture smoke tests.

Each assigned architecture is instantiated at a REDUCED config of the same
family and run through one forward/train step + prefill + decode on CPU,
asserting output shapes and finiteness.  The FULL configs are exercised only
by the dry-run (ShapeDtypeStruct, no allocation).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import list_configs, scaled_down
from repro.models import model as M

ARCHS = sorted(list_configs())
B, S = 2, 32


def _inputs(cfg, key):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    kwargs = {}
    if cfg.n_media_tokens:
        kwargs["media"] = jax.random.normal(
            key, (B, cfg.n_media_tokens, cfg.d_model))
    if cfg.encoder is not None:
        kwargs["frames"] = jax.random.normal(
            key, (B, cfg.encoder.n_ctx, cfg.encoder.d_model))
    return tokens, labels, kwargs


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_shapes_and_finiteness(arch):
    cfg = scaled_down(list_configs()[arch])
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key, jnp.float32, max_seq=64)
    tokens, labels, kwargs = _inputs(cfg, key)
    ctx = M.Ctx(ce_chunk=16)

    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: M.lm_loss(cfg, p, tokens, labels, ctx, **kwargs),
        has_aux=True))(params)
    assert jnp.isfinite(loss), f"{arch}: loss {loss}"
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(gnorm), f"{arch}: grad norm"
    assert float(gnorm) > 0

    logits, _ = jax.jit(lambda p: M.forward(cfg, p, tokens, M.Ctx(),
                                            **kwargs))(params)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert jnp.isfinite(logits).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistent_with_forward(arch):
    """Prefill + decode must reproduce teacher-forced forward logits."""
    cfg = scaled_down(list_configs()[arch])
    key = jax.random.PRNGKey(1)
    params = M.init_params(cfg, key, jnp.float32, max_seq=64)
    tokens, _, kwargs = _inputs(cfg, key)
    ctx = M.Ctx()

    full_logits, _ = jax.jit(
        lambda p: M.forward(cfg, p, tokens, ctx, **kwargs))(params)

    n_prompt = S - 4
    lg, state = jax.jit(lambda p, t: M.prefill(
        cfg, p, t, 64, ctx, **kwargs))(params, tokens[:, :n_prompt])
    # prefill last-position logits == forward logits at n_prompt-1
    assert jnp.allclose(lg, full_logits[:, n_prompt - 1], atol=2e-3), arch

    step = jax.jit(lambda p, t, s: M.decode_step(cfg, p, t, s, ctx))
    for i in range(n_prompt, S):
        lg, state = step(params, tokens[:, i], state)
        assert jnp.allclose(lg, full_logits[:, i], atol=2e-3), \
            f"{arch}: decode step {i} diverges " \
            f"({float(jnp.max(jnp.abs(lg - full_logits[:, i]))):.2e})"


@pytest.mark.parametrize("arch", ["gemma3-1b", "llama3.2-1b",
                                  "recurrentgemma-2b"])
def test_flash_impl_parity(arch):
    cfg = scaled_down(list_configs()[arch])
    key = jax.random.PRNGKey(2)
    params = M.init_params(cfg, key, jnp.float32, max_seq=64)
    tokens, _, kwargs = _inputs(cfg, key)
    lr, _ = jax.jit(lambda p: M.forward(
        cfg, p, tokens, M.Ctx(attn_impl="xla_rect"), **kwargs))(params)
    lf, _ = jax.jit(lambda p: M.forward(
        cfg, p, tokens, M.Ctx(attn_impl="xla_flash"), **kwargs))(params)
    assert jnp.max(jnp.abs(lr - lf)) < 2e-4


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "rwkv6-3b"])
def test_pallas_rnn_impl_parity(arch):
    cfg = scaled_down(list_configs()[arch])
    key = jax.random.PRNGKey(3)
    params = M.init_params(cfg, key, jnp.float32, max_seq=64)
    tokens, _, kwargs = _inputs(cfg, key)
    lx, _ = jax.jit(lambda p: M.forward(
        cfg, p, tokens, M.Ctx(rnn_impl="xla"), **kwargs))(params)
    ctx = M.Ctx(rnn_impl="pallas", interpret=True)
    lp, _ = jax.jit(lambda p: M.forward(
        cfg, p, tokens, ctx, **kwargs))(params)
    assert jnp.max(jnp.abs(lx - lp)) < 5e-3, \
        float(jnp.max(jnp.abs(lx - lp)))


def test_local_window_masks_differ_from_full():
    cfg = scaled_down(list_configs()["gemma3-1b"], local_window=8)
    key = jax.random.PRNGKey(4)
    params = M.init_params(cfg, key, jnp.float32, max_seq=64)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    l1, _ = M.forward(cfg, params, tokens, M.Ctx())
    import dataclasses
    cfg2 = dataclasses.replace(cfg, local_window=1024)
    l2, _ = M.forward(cfg2, params, tokens, M.Ctx())
    # long-range tokens must be affected by the window
    assert float(jnp.max(jnp.abs(l1[:, -1] - l2[:, -1]))) > 1e-4
