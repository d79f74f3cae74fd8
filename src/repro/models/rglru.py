"""RG-LRU recurrent block (Griffin / RecurrentGemma).

Temporal-mixing module: two D->W projections (a GeLU gate branch and a
recurrence branch), a causal depthwise conv1d, and the Real-Gated Linear
Recurrent Unit:

    r_t = sigmoid(W_a x_t)            (recurrence gate, block-diagonal)
    i_t = sigmoid(W_x x_t)            (input gate, block-diagonal)
    log a_t = -c * softplus(Lambda) * r_t          (c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Training/prefill uses ``jax.lax.associative_scan`` over time (the recurrence
is linear, so it parallelizes); decode carries ``h`` as state.  The Pallas
kernel in ``repro.kernels.rglru_scan`` implements the same recurrence with
time-blocked VMEM tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.sharding import constrain

_C = 8.0
_MAX_SQRT_GRAD = 1e-6


def rglru_params(key, cfg, dtype):
    D, W, H = cfg.d_model, cfg.rnn_width, cfg.n_heads
    bw = W // H                                   # block width per head
    ks = jax.random.split(key, 7)
    return {
        "w_in": L.dense_init(ks[0], (D, W), dtype),
        "w_gate": L.dense_init(ks[1], (D, W), dtype),
        "w_out": L.dense_init(ks[2], (W, D), dtype, fan_in=W),
        "conv": L.conv1d_params(ks[3], cfg.conv_width, W, dtype),
        # block-diagonal gates: [H, bw, bw]
        "w_rgate": L.dense_init(ks[4], (H, bw, bw), dtype, fan_in=bw),
        "b_rgate": jnp.zeros((W,), dtype),
        "w_igate": L.dense_init(ks[5], (H, bw, bw), dtype, fan_in=bw),
        "b_igate": jnp.zeros((W,), dtype),
        # Lambda init so that a = sigmoid(Lambda)^c is in ~(0.9, 0.999)
        "Lambda": jnp.asarray(
            jax.random.uniform(ks[6], (W,), jnp.float32,
                               minval=2.2, maxval=6.9), jnp.float32),
    }


def rglru_axes(cfg):
    return {
        "w_in": ("embed", "rnn"), "w_gate": ("embed", "rnn"),
        "w_out": ("rnn", "embed"), "conv": L.conv1d_axes(),
        "w_rgate": ("heads", None, None), "b_rgate": ("rnn",),
        "w_igate": ("heads", None, None), "b_igate": ("rnn",),
        "Lambda": ("rnn",),
    }


def _gates(params, u, H):
    """u: [..., W] -> (log_a, gated_input) both f32."""
    shp = u.shape
    W = shp[-1]
    bw = W // H
    uf = u.astype(jnp.float32).reshape(*shp[:-1], H, bw)
    r = jnp.einsum("...hb,hbc->...hc", uf,
                   params["w_rgate"].astype(jnp.float32))
    r = jax.nn.sigmoid(r.reshape(shp) + params["b_rgate"].astype(jnp.float32))
    i = jnp.einsum("...hb,hbc->...hc", uf,
                   params["w_igate"].astype(jnp.float32))
    i = jax.nn.sigmoid(i.reshape(shp) + params["b_igate"].astype(jnp.float32))
    log_a = -_C * jax.nn.softplus(params["Lambda"]) * r
    gated = i * u.astype(jnp.float32)
    return log_a, gated


def _scan_linear(a, b, h0=None, chunk=512):
    """h_t = a_t * h_{t-1} + b_t, time-chunked associative scan.

    Chunking + remat bounds the backward saved-state to chunk boundaries
    (the log-depth associative-scan intermediates are recomputed), and the
    chunk carry keeps its batch sharding across iterations."""
    B, S, W = a.shape
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, b1 * a2 + b2

    ck = min(chunk, S)
    while S % ck:
        ck //= 2
    if ck == S:
        _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
        return h
    nc = S // ck
    ac = a.reshape(B, nc, ck, W).transpose(1, 0, 2, 3)
    bc = b.reshape(B, nc, ck, W).transpose(1, 0, 2, 3)

    @jax.checkpoint
    def chunk_body(h, inp):
        a_i, b_i = inp                       # [B, ck, W]
        b_i = b_i.at[:, 0].add(a_i[:, 0] * h)
        _, hh = jax.lax.associative_scan(combine, (a_i, b_i), axis=1)
        return hh[:, -1], hh

    _, hs = jax.lax.scan(chunk_body, jnp.zeros_like(a[:, 0]), (ac, bc))
    return hs.transpose(1, 0, 2, 3).reshape(B, S, W)


def rglru(params, u, h0=None):
    """u: [B, S, W] -> (h [B, S, W], h_last [B, W]).  f32 internally."""
    log_a, gated = _gates(params, u, params["w_rgate"].shape[0])
    a = jnp.exp(log_a)
    # sqrt(1 - a^2) computed stably via expm1 in log space
    mult = jnp.sqrt(jnp.maximum(-jnp.expm1(2.0 * log_a), _MAX_SQRT_GRAD))
    b = mult * gated
    h = _scan_linear(a, b, h0)
    return h.astype(u.dtype), h[:, -1]


def rglru_step(params, u_t, h_prev):
    """Decode step.  u_t: [B, W]; h_prev: [B, W] f32."""
    log_a, gated = _gates(params, u_t, params["w_rgate"].shape[0])
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(-jnp.expm1(2.0 * log_a), _MAX_SQRT_GRAD))
    h = a * h_prev + mult * gated
    return h.astype(u_t.dtype), h


# --------------------------------------------------------------------------
# full temporal block
# --------------------------------------------------------------------------
def block_params(key, cfg, dtype):
    return rglru_params(key, cfg, dtype)


def apply_block(params, x, *, cfg, rules, state=None, impl="xla",
                interpret=False):
    """Griffin recurrent temporal block.

    x: [B, S, D].  state: None (train) or dict(conv [B, cw-1, W], h [B, W]).
    Returns (y [B, S, D], new_state | None).
    """
    gate = jax.nn.gelu(x @ params["w_gate"], approximate=True)
    u_raw = x @ params["w_in"]
    u_raw = constrain(u_raw, rules, ("batch", "seq", "rnn"))
    if state is None:
        u = L.apply_conv1d(params["conv"], u_raw)
        if impl == "pallas":
            from repro.kernels import ops as kops
            log_a, gated = _gates(params, u, params["w_rgate"].shape[0])
            h, h_last = kops.rglru_scan(log_a, gated, interpret=interpret)
            h = h.astype(u.dtype)
            h_last = h_last.astype(jnp.float32)
        else:
            h, h_last = rglru(params, u)
        new_state = {"conv": _conv_tail(u_raw, cfg.conv_width),
                     "h": h_last.astype(jnp.float32)}
        y = (h * gate) @ params["w_out"]
        return constrain(y, rules, ("batch", "seq", "embed")), new_state
    # decode step: x [B, 1, D]
    u_t = u_raw[:, 0]
    conv_state, y_t = L.conv1d_step(params["conv"], state["conv"], u_t)
    h_t, h_f32 = rglru_step(params, y_t, state["h"])
    y = (h_t * gate[:, 0]) @ params["w_out"]
    return y[:, None, :], {"conv": conv_state, "h": h_f32}


def _conv_tail(u_raw, conv_width):
    """Last (conv_width-1) *pre-conv* inputs — the decode conv state."""
    need = conv_width - 1
    S = u_raw.shape[1]
    if S >= need:
        return u_raw[:, S - need:, :]
    pad = jnp.zeros((u_raw.shape[0], need - S, u_raw.shape[2]), u_raw.dtype)
    return jnp.concatenate([pad, u_raw], axis=1)


def init_state(cfg, batch, dtype):
    W = cfg.rnn_width
    return {"conv": jnp.zeros((batch, cfg.conv_width - 1, W), dtype),
            "h": jnp.zeros((batch, W), jnp.float32)}


def state_axes(cfg):
    return {"conv": ("batch", "seq", "rnn"), "h": ("batch", "rnn")}
