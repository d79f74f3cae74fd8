"""RWKV6 ("Finch") block: time-mix with data-dependent decay + channel-mix.

Core recurrence per head (state S in R^{hd x hd}, f32):

    S_t[i,j] = w_t[i] * S_{t-1}[i,j] + k_t[i] * v_t[j]
    y_t[j]   = sum_i r_t[i] * (S_{t-1}[i,j] + u[i] * k_t[i] * v_t[j])

with the *data-dependent* decay w_t = exp(-exp(w0 + tanh(x_w A) B)) — the
defining RWKV6 feature per the assignment table.  Token shift is the learned
lerp between x_t and x_{t-1}; output gating is silu(g) after a per-head
layer norm.

The XLA path runs the exact recurrence with ``lax.scan`` over time (the
projections dominate FLOPs; the scan is the latency-bound part that the
Pallas kernel ``repro.kernels.wkv6`` addresses with time-blocked VMEM tiles
and in-register state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.sharding import constrain

LORA_RANK = 64


def timemix_params(key, cfg, dtype):
    D, H = cfg.d_model, cfg.n_heads
    hd = D // H
    ks = jax.random.split(key, 9)
    return {
        "mu": jnp.full((5, D), 0.5, dtype),            # r,k,v,g,w shifts
        "w0": jnp.asarray(jax.random.uniform(
            ks[0], (D,), jnp.float32, minval=-6.0, maxval=-1.0)),
        "wA": L.dense_init(ks[1], (D, LORA_RANK), jnp.float32),
        "wB": (jax.random.truncated_normal(ks[2], -2, 2,
                                           (LORA_RANK, D), jnp.float32)
               * 0.01),
        "u": L.dense_init(ks[3], (H, hd), jnp.float32, fan_in=hd),
        "wr": L.dense_init(ks[4], (D, D), dtype),
        "wk": L.dense_init(ks[5], (D, D), dtype),
        "wv": L.dense_init(ks[6], (D, D), dtype),
        "wg": L.dense_init(ks[7], (D, D), dtype),
        "wo": L.dense_init(ks[8], (D, D), dtype),
        "ln_scale": jnp.ones((D,), dtype),
        "ln_bias": jnp.zeros((D,), dtype),
    }


def timemix_axes(cfg):
    return {"mu": (None, "embed"), "w0": ("embed",), "wA": ("embed", None),
            "wB": (None, "embed"), "u": ("heads", "head_dim"),
            "wr": ("embed", "heads"), "wk": ("embed", "heads"),
            "wv": ("embed", "heads"), "wg": ("embed", "heads"),
            "wo": ("heads", "embed"),
            "ln_scale": ("embed",), "ln_bias": ("embed",)}


def channelmix_params(key, cfg, dtype):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "mu_k": jnp.full((D,), 0.5, dtype),
        "mu_r": jnp.full((D,), 0.5, dtype),
        "wk": L.dense_init(ks[0], (D, F), dtype),
        "wv": L.dense_init(ks[1], (F, D), dtype, fan_in=F),
        "wr": L.dense_init(ks[2], (D, D), dtype),
    }


def channelmix_axes(cfg):
    return {"mu_k": ("embed",), "mu_r": ("embed",), "wk": ("embed", "ffn"),
            "wv": ("ffn", "embed"), "wr": ("embed", "heads")}


def _shift(x, x_prev=None):
    """x_{t-1} along time; first step uses x_prev (decode) or zeros."""
    if x_prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
    return jnp.concatenate([x_prev[:, None, :], x[:, :-1, :]], axis=1)


def _lerp(x, x_shift, mu):
    return x + (x_shift - x) * mu


def wkv(r, k, v, w, u, s0, rules=None, chunk=128):
    """Exact WKV6 recurrence, time-chunked.

    r,k,v,w: [B, S, H, hd] (w = decay in (0,1)); u: [H, hd];
    s0: [B, H, hd, hd] f32.  Returns (y [B, S, H, hd] f32, s_last).

    The outer scan walks chunks with a rematerialized body, so backward
    saves the state only at chunk boundaries (S/chunk · B·H·hd² instead of
    S·B·H·hd² — the difference between 46 GiB and ~0.2 GiB per device at
    4k·3B scale).  The carry sharding is pinned to the batch axes so GSPMD
    never inserts per-step gathers inside the loop.
    """
    B, S, H, hd = r.shape
    ck = min(chunk, S)
    while S % ck:
        ck //= 2
    nc = S // ck

    def to_chunks(a):
        return a.astype(jnp.float32).reshape(B, nc, ck, H, hd) \
            .transpose(1, 2, 0, 3, 4)              # [nc, ck, B, H, hd]

    xs = tuple(to_chunks(a) for a in (r, k, v, w))

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp                               # [B, H, hd]
        kv = k_t[..., :, None] * v_t[..., None, :]             # [B,H,hd,hd]
        y = jnp.einsum("bhi,bhij->bhj", r_t, s + u[..., None] * kv)
        s = w_t[..., None] * s + kv
        return s, y

    @jax.checkpoint
    def chunk_body(s, inp):
        s = constrain(s, rules, ("batch", "heads", None, None))
        s_out, ys = jax.lax.scan(step, s, inp)
        return s_out, ys

    s_last, ys = jax.lax.scan(chunk_body, s0, xs)   # ys [nc, ck, B, H, hd]
    y = ys.transpose(2, 0, 1, 3, 4).reshape(B, S, H, hd)
    return y, s_last


def wkv_step(r, k, v, w, u, s):
    """One decode step; args [B, H, hd]."""
    kv = k[..., :, None] * v[..., None, :]
    y = jnp.einsum("bhi,bhij->bhj", r, s + u[..., None] * kv)
    s = w[..., None] * s + kv
    return y, s


def _heads(x, H):
    B, S, D = x.shape
    return x.reshape(B, S, H, D // H)


def apply_timemix(params, x, *, cfg, rules, state=None, impl="xla",
                  interpret=False):
    """x: [B, S, D] -> (y, new_state dict(x_tm [B,D], s [B,H,hd,hd]))."""
    B, S, D = x.shape
    H, hd = cfg.n_heads, D // cfg.n_heads
    xs = _shift(x, None if state is None else state["x_tm"])
    mu = params["mu"]
    xr, xk, xv, xg, xw = (_lerp(x, xs, mu[i]) for i in range(5))
    r = _heads(xr @ params["wr"], H)
    k = _heads(xk @ params["wk"], H)
    v = _heads(xv @ params["wv"], H)
    g = xg @ params["wg"]
    # data-dependent decay (f32)
    lora = jnp.tanh(xw.astype(jnp.float32) @ params["wA"]) @ params["wB"]
    w = jnp.exp(-jnp.exp(params["w0"] + lora))                 # (0,1)
    w = _heads(w, H)
    r = constrain(r, rules, ("batch", "seq", "heads", None))
    s0 = state["s"] if state is not None else \
        jnp.zeros((B, H, hd, hd), jnp.float32)
    if state is not None and S == 1:
        y, s_last = wkv_step(r[:, 0].astype(jnp.float32),
                             k[:, 0].astype(jnp.float32),
                             v[:, 0].astype(jnp.float32),
                             w[:, 0], params["u"], s0)
        y = y[:, None]
    elif impl == "pallas":
        from repro.kernels import ops as kops
        y, s_last = kops.wkv6(r, k, v, w, params["u"], s0,
                              interpret=interpret)
    else:
        y, s_last = wkv(r, k, v, w, params["u"], s0, rules=rules)
    # per-head layer norm, silu(g) gate, output proj
    yf = y.reshape(B, S, H, hd)
    mu_y = yf.mean(-1, keepdims=True)
    var = jnp.square(yf - mu_y).mean(-1, keepdims=True)
    yf = (yf - mu_y) * jax.lax.rsqrt(var + 1e-5)
    yf = yf.reshape(B, S, D) * params["ln_scale"].astype(jnp.float32) \
        + params["ln_bias"].astype(jnp.float32)
    out = (yf.astype(x.dtype) * jax.nn.silu(g)) @ params["wo"]
    out = constrain(out, rules, ("batch", "seq", "embed"))
    new_state = {"x_tm": x[:, -1, :], "s": s_last}
    return out, new_state


def apply_channelmix(params, x, *, cfg, rules, state=None):
    """x: [B, S, D] -> (y, x_last for the shift state)."""
    xs = _shift(x, None if state is None else state["x_cm"])
    xk = _lerp(x, xs, params["mu_k"])
    xr = _lerp(x, xs, params["mu_r"])
    kk = jnp.square(jax.nn.relu(xk @ params["wk"]))
    y = jax.nn.sigmoid(xr @ params["wr"]) * (kk @ params["wv"])
    return constrain(y, rules, ("batch", "seq", "embed")), x[:, -1, :]


def init_state(cfg, batch, dtype):
    H, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    return {"x_tm": jnp.zeros((batch, cfg.d_model), dtype),
            "x_cm": jnp.zeros((batch, cfg.d_model), dtype),
            "s": jnp.zeros((batch, H, hd, hd), jnp.float32)}


def state_axes(cfg):
    return {"x_tm": ("batch", "embed"), "x_cm": ("batch", "embed"),
            "s": ("batch", "heads", None, None)}
