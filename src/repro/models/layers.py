"""Shared neural layers: norms, RoPE, MLPs, initializers.

Pure-functional JAX: params are plain pytrees of ``jnp.ndarray``; every init
takes an explicit PRNG key.  Norms and softmaxes compute in f32 regardless of
the activation dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------
def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = (1.0 / max(1, fan_in)) ** 0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def norm_params(d, kind, dtype):
    if kind == "rms":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layer":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    raise ValueError(kind)


def norm_axes(kind):
    if kind == "rms":
        return {"scale": ("embed",)}
    return {"scale": ("embed",), "bias": ("embed",)}


def apply_norm(params, x, kind, eps):
    xf = x.astype(jnp.float32)
    if kind == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + eps)
        y = y * params["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) \
            + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(scale, x):
    """RMSNorm over the trailing (head_dim) axis — gemma3 qk-norm."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + NORM_EPS)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary position embeddings (half-split / NeoX convention)
# --------------------------------------------------------------------------
def rope(x, positions, theta):
    """x: [..., S, n_heads, head_dim]; positions: [..., S] int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq        # [..., S, half]
    ang = ang[..., None, :]                                       # heads dim
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def mlp_params(key, d, f, kind, dtype):
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], (d, f), dtype),
                "w_up": dense_init(ks[1], (d, f), dtype),
                "w_down": dense_init(ks[2], (f, d), dtype)}
    if kind == "gelu":
        return {"w_in": dense_init(ks[0], (d, f), dtype),
                "b_in": jnp.zeros((f,), dtype),
                "w_out": dense_init(ks[1], (f, d), dtype),
                "b_out": jnp.zeros((d,), dtype)}
    raise ValueError(kind)


def mlp_axes(kind):
    if kind in ("swiglu", "geglu"):
        return {"w_gate": ("embed", "ffn"), "w_up": ("embed", "ffn"),
                "w_down": ("ffn", "embed")}
    return {"w_in": ("embed", "ffn"), "b_in": ("ffn",),
            "w_out": ("ffn", "embed"), "b_out": ("embed",)}


def apply_mlp(params, x, kind):
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
        return h @ params["w_down"]
    if kind == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"], approximate=True) \
            * (x @ params["w_up"])
        return h @ params["w_down"]
    if kind == "gelu":
        h = jax.nn.gelu(x @ params["w_in"] + params["b_in"], approximate=True)
        return h @ params["w_out"] + params["b_out"]
    raise ValueError(kind)


# --------------------------------------------------------------------------
# causal depthwise conv1d (RG-LRU branch)
# --------------------------------------------------------------------------
def conv1d_params(key, width, channels, dtype):
    return {"w": dense_init(key, (width, channels), dtype, fan_in=width),
            "b": jnp.zeros((channels,), dtype)}


def conv1d_axes():
    return {"w": (None, "rnn"), "b": ("rnn",)}


def apply_conv1d(params, x):
    """Causal depthwise conv.  x: [B, S, C] -> [B, S, C]."""
    w = params["w"]
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + pad[:, i:i + x.shape[1], :] * w[i]
    return out + params["b"]


def conv1d_step(params, state, x_t):
    """Single decode step.  state: [B, width-1, C]; x_t: [B, C]."""
    w = params["w"]
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)  # [B, width, C]
    y = jnp.einsum("bwc,wc->bc", window, w) + params["b"]
    return window[:, 1:, :], y
