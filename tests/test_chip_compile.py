"""Compile-for-chip checks: the Pallas kernels, one full-width prefill and
the serving decode step are compiled for a described TPU v5e (no chip
attached) at real model widths.

Every kernel must reach Mosaic (``tpu_custom_call`` in the compiled text): a
kernel that the chip's compiler refuses, or that silently went through the
interpreter, fails here.  Nothing runs; only the compiler is exercised.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and pytest-xdist workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import model as M


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_qwen2_prefill(one_chip):
    cfg = get_config("qwen2-0.5b")
    B, S = 1, 512
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _spec((B, S, H, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, K, hd), jnp.bfloat16, one_chip)
    text = _compiled_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True), q, kv, kv)
    assert "tpu_custom_call" in text


def test_decode_attention_qwen2_cache_2048(one_chip):
    cfg = get_config("qwen2-0.5b")
    B, C = 8, 2048
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _spec((B, H, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, C, K, hd), jnp.bfloat16, one_chip)
    cpos = _spec((B, C), jnp.int32, one_chip)
    cur = _spec((B,), jnp.int32, one_chip)
    text = _compiled_text(ops.decode_attention, q, kv, kv, cpos, cur)
    assert "tpu_custom_call" in text


def test_rglru_scan_recurrentgemma_width(one_chip):
    W = get_config("recurrentgemma-2b").rnn_width
    x = _spec((2, 1024, W), jnp.float32, one_chip)
    text = _compiled_text(ops.rglru_scan, x, x)
    assert "tpu_custom_call" in text


def test_wkv6_rwkv6_3b_heads(one_chip):
    cfg = get_config("rwkv6-3b")
    B, S, H = 1, 1024, cfg.n_heads
    hd = cfg.d_model // H
    a = _spec((B, S, H, hd), jnp.float32, one_chip)
    u = _spec((H, hd), jnp.float32, one_chip)
    s0 = _spec((B, H, hd, hd), jnp.float32, one_chip)
    text = _compiled_text(ops.wkv6, a, a, a, a, u, s0)
    assert "tpu_custom_call" in text


def test_qwen2_full_width_prefill_with_pallas_attention(one_chip):
    cfg = get_config("qwen2-0.5b")
    S, cache_len = 512, 2048
    params = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0),
                                             jnp.bfloat16)))
    tokens = _spec((1, S), jnp.int32, one_chip)
    ctx = M.Ctx(attn_impl="pallas")
    text = _compiled_text(
        lambda p, t: M.prefill(cfg, p, t, cache_len, ctx), params, tokens)
    assert "tpu_custom_call" in text


def test_qwen2_decode_step_updates_cache_in_place(one_chip):
    """The decode step at the chat cell's shape (128 slots x 1536), state
    donated as the engine donates it: the whole stacked cache aliases the
    outputs, the temporaries are a sliver of it (no relaid-out layer copy,
    no new stack), and the only loop is the layer scan (no ring write
    lowered to a loop of one-row updates)."""
    cfg = get_config("qwen2-0.5b")
    B, C = 128, 1536

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                            tree)

    params = on_chip(jax.eval_shape(lambda: M.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16, max_seq=C)))
    state = on_chip(jax.eval_shape(lambda: M.init_decode_state(
        cfg, B, C, jnp.bfloat16)))
    tokens = _spec((B,), jnp.int32, one_chip)
    step = jax.jit(lambda p, t, s: M.decode_step(cfg, p, t, s),
                   donate_argnums=2)
    compiled = step.lower(params, tokens, state).compile()
    ma = compiled.memory_analysis()
    cache = sum(a.size * a.dtype.itemsize
                for a in jax.tree.leaves(state["caches"]))
    assert ma.alias_size_in_bytes >= cache
    assert ma.temp_size_in_bytes < cache // 100, ma.temp_size_in_bytes
    assert compiled.as_text().count(" while(") == 1


def test_rwkv6_full_width_prefill_loops_over_chunks(one_chip):
    """rwkv6-3b's prefill of a 4096-token prompt at full width: two loops,
    the layer scan and in it the chunk-to-chunk state recurrence (none
    over tokens), and well under a GB of temporaries beside the weights."""
    cfg = get_config("rwkv6-3b")
    S = 4096
    params = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0),
                                             jnp.bfloat16)))
    tokens = _spec((1, S), jnp.int32, one_chip)
    compiled = jax.jit(lambda p, t: M.prefill(cfg, p, t, S)).lower(
        params, tokens).compile()
    assert compiled.as_text().count(" while(") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
