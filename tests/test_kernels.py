"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles.

The kernels run through the Pallas interpreter, asked for explicitly with
``interpret=True``; ``test_chip_compile.py`` compiles them for the chip.
"""
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize(
    "B,S,T,H,K,hd,causal,window,dtype",
    [
        (2, 128, 128, 4, 2, 32, True, 0, jnp.float32),
        (1, 256, 256, 4, 1, 64, True, 48, jnp.float32),
        (2, 64, 64, 6, 6, 16, False, 0, jnp.float32),
        (1, 128, 128, 8, 2, 64, True, 200, jnp.float32),
        (2, 128, 128, 4, 4, 32, True, 0, jnp.bfloat16),
        (1, 64, 64, 2, 1, 128, True, 32, jnp.float32),
    ])
def test_flash_attention_sweep(B, S, T, H, K, hd, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, K, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, K, hd)).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_kv=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert jnp.max(jnp.abs(out.astype(jnp.float32)
                           - want.astype(jnp.float32))) < tol


def test_flash_attention_softcap():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    out = ops.flash_attention(q, k, v, causal=True, softcap=20.0,
                              block_q=16, block_kv=16, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, softcap=20.0)
    assert jnp.max(jnp.abs(out - want)) < 2e-5


@pytest.mark.parametrize("C,window,block", [(64, 0, 16), (64, 8, 16),
                                            (128, 0, 128), (96, 24, 32)])
def test_decode_attention_sweep(C, window, block):
    B, H, K, hd = 3, 8, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, C, K, hd))
    v = jax.random.normal(ks[2], (B, C, K, hd))
    cpos = jnp.tile(jnp.arange(C)[None], (B, 1)).at[:, -5:].set(-1)
    cur = jnp.array([min(40, C - 1), C - 6, 10])
    out = ops.decode_attention(q, k, v, cpos, cur, window=window,
                               block_kv=block, interpret=True)
    want = ref.decode_attention_ref(q, k, v, cpos, cur, window=window)
    assert jnp.max(jnp.abs(out - want)) < 2e-5


def test_decode_attention_ring_wrap():
    """Positions beyond the ring size must mask correctly after wrap."""
    B, H, K, hd, C = 1, 2, 1, 16, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, C, K, hd))
    v = jax.random.normal(ks[2], (B, C, K, hd))
    # ring holds positions 37..68 at slots (p % 32)
    cpos = ((jnp.arange(C) + 64) - ((jnp.arange(C) + 64) % C)
            + jnp.arange(C))[None]
    cpos = jnp.where(cpos > 68, cpos - C, cpos)
    cur = jnp.array([68])
    out = ops.decode_attention(q, k, v, cpos, cur, window=16, block_kv=8,
                               interpret=True)
    want = ref.decode_attention_ref(q, k, v, cpos, cur, window=16)
    assert jnp.max(jnp.abs(out - want)) < 2e-5


@given(st.integers(1, 3), st.sampled_from([32, 64, 96]),
       st.sampled_from([32, 64]))
@settings(max_examples=10, deadline=None)
def test_rglru_property(B, S, W):
    k1, k2 = jax.random.split(jax.random.PRNGKey(B * S + W))
    la = -jnp.abs(jax.random.normal(k1, (B, S, W))) * 0.5 - 0.01
    x = jax.random.normal(k2, (B, S, W))
    h, hl = ops.rglru_scan(la, x, block_t=16, block_w=16,
                           interpret=True)
    h2, hl2 = ref.rglru_scan_ref(la, x)
    assert jnp.max(jnp.abs(h - h2)) < 1e-4
    assert jnp.max(jnp.abs(hl - hl2)) < 1e-4


def test_rglru_decay_bounds():
    """Strong decay forgets: h_t -> input term only."""
    B, S, W = 1, 64, 32
    la = jnp.full((B, S, W), -50.0)                 # a ~ 0
    x = jnp.ones((B, S, W))
    h, _ = ops.rglru_scan(la, x, block_t=16, block_w=16,
                          interpret=True)
    assert jnp.allclose(h, jnp.sqrt(-jnp.expm1(2 * la)) * x, atol=1e-5)


@pytest.mark.parametrize("S,H,hd,bt", [(64, 3, 16, 16), (128, 2, 32, 64),
                                       (96, 1, 64, 32)])
def test_wkv6_sweep(S, H, hd, bt):
    B = 2
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, hd))
    s0 = jax.random.normal(jax.random.PRNGKey(9), (B, H, hd, hd)) * 0.1
    y, s = ops.wkv6(r, k, v, w, u, s0, block_t=bt, interpret=True)
    y2, s2 = ref.wkv6_ref(
        *(a.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
          for a in (r, k, v, w)),
        jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd),
        s0.reshape(B * H, hd, hd))
    y2 = y2.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    assert jnp.max(jnp.abs(y - y2)) < 5e-4
    assert jnp.max(jnp.abs(s.reshape(B * H, hd, hd) - s2)) < 5e-4


def test_wkv6_state_carry_composes():
    """wkv over [0:S] == wkv over [0:S/2] then [S/2:S] with carried state."""
    B, S, H, hd = 1, 64, 2, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))) * 0.3 + 0.6
    u = jax.random.normal(ks[4], (H, hd))
    s0 = jnp.zeros((B, H, hd, hd))
    y_full, s_full = ops.wkv6(r, k, v, w, u, s0, block_t=16,
                             interpret=True)
    h = S // 2
    y1, s1 = ops.wkv6(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u, s0,
                      block_t=16, interpret=True)
    y2, s2 = ops.wkv6(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, s1,
                      block_t=16, interpret=True)
    assert jnp.max(jnp.abs(jnp.concatenate([y1, y2], 1) - y_full)) < 1e-4
    assert jnp.max(jnp.abs(s2 - s_full)) < 1e-4
