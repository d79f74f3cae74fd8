"""Plain float32 RWKV-6 "Finch", written from the published description.

RWKV-6 (arXiv:2404.05892, Section 4; RWKV-LM's ``RWKV_Tmix_x060`` and
``RWKV_CMix_x060``; the model's public ``config.json``): an
attention-free stack of pre-LayerNorm blocks after a LayerNorm on the
embeddings (``ln0``).  Each block is a time-mix and a channel-mix.

Time-mix: the five inputs w, k, v, r, g each take ``x + dx * (maa_* +
m_*)``, dx the previous token's x less this one's and m a rank-32 LoRA
(tanh between) of ``x + dx * maa_x`` ("ddlerp"); the decay is
``exp(-exp(w0 + tanh(x_w A) B))`` (rank 64); per head (64 channels) the
state S [64, 64] and the token's output step as

    y_t = r_t (S + diag(u) k_t v_t^T),   S <- diag(w_t) S + k_t v_t^T,

one token at a time; then a GroupNorm per head with eps 1e-5 x
head_size_divisor², the silu(g) gate and the output projection.
Channel-mix: ``sigmoid(x_r R) * (relu(x_k K)² V)`` over static shifts.
A final LayerNorm and an untied head.  LayerNorm eps
``layer_norm_epsilon``.

Everything runs in float32 at the highest matmul precision, layer by
layer (a scan over the stacked layers), the recurrence a scan over
tokens, and the head in blocks of rows.  It imports nothing of the
program; the weights are the benchmark's (``bench/weights.py``) in the
program's tree layout, tied to it by the names below alone.

``mode`` "fp8" runs the same mathematics one precision lower, for the
control: every weight and every matmul input is rounded through float8
(e4m3, one scale per tensor), one step below the bfloat16 that serving
states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# (leaf path pattern, distribution, arguments): the first that matches.
# Norm gains and biases are random too, so a program that skipped one
# would differ from this reference.  Shift mixes, the decay base w0 and
# the bonus u are drawn from RWKV-LM's initial ranges (a token's weight
# kept 0.69 to 0.9975 a step at w0 alone: memories of 3 to 400 tokens);
# the LoRAs are scaled so that their terms move the mixes by ~0.2 and the
# decay's log-rate by ~0.5, as a trained model's do, not by RWKV-LM's
# near-zero start.
RULES = [
    (r"^(embed|unembed)$", "embedding", {"std": 0.02}),  # vocab from config
    (r"norm\d?/scale$|mixer/ln_scale$", "one_plus_normal", {"std": 0.1}),
    (r"norm\d?/bias$|mixer/ln_bias$", "normal", {"std": 0.1}),
    (r"mixer/maa(_x)?$|ffn/maa_[kr]$", "uniform", {"lo": 0.0, "hi": 1.0}),
    (r"mixer/maa_w2$", "normal", {"std": 0.05}),
    (r"mixer/wB$", "normal", {"std": 0.1}),
    (r"mixer/w0$", "uniform", {"lo": -6.0, "hi": -1.0}),
    (r"mixer/u$", "uniform", {"lo": -0.1, "hi": 1.1}),
    (r"mixer/(maa_w1|wA|w[rkvgo])$|ffn/w[krv]$", "normal_fan_in", {}),
]


def weight_rules(config):
    rules = list(RULES)
    rules[0] = (RULES[0][0], "embedding",
                {"std": 0.02, "vocab": config["vocab_size"]})
    return rules


def _fp8(a):
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _q(a, mode):
    """A weight or matmul input as ``mode`` computes it: "f32" as is,
    "fp8" rounded through float8 e4m3."""
    a = a.astype(jnp.float32)
    return _fp8(a) if mode == "fp8" else a


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _prev(x):
    """The token before each (zeros before the first)."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def _recurrence(r, k, v, w, u):
    """[T, H, hd] each, u [H, hd] -> y [T, H, hd], token by token."""
    H, hd = u.shape

    def step(s, t):
        r_t, k_t, v_t, w_t = t
        kv = k_t[:, :, None] * v_t[:, None, :]
        y = jnp.einsum("hi,hij->hj", r_t, s + u[:, :, None] * kv)
        return w_t[:, :, None] * s + kv, y

    _, y = jax.lax.scan(step, jnp.zeros((H, hd, hd), jnp.float32),
                        (r, k, v, w))
    return y


def _time_mix(config, mode, h, m):
    T, D = h.shape
    hd = config["head_size"]
    H = D // hd
    dx = _prev(h) - h
    lo = jnp.tanh(_q(h + dx * m["maa_x"], mode) @ _q(m["maa_w1"], mode))
    lo = jnp.einsum("tfr,frd->ftd", _q(lo.reshape(T, 5, -1), mode),
                    _q(m["maa_w2"], mode))
    xw, xk, xv, xr, xg = (h + dx * (m["maa"][i] + lo[i]) for i in range(5))
    r = (_q(xr, mode) @ _q(m["wr"], mode)).reshape(T, H, hd)
    k = (_q(xk, mode) @ _q(m["wk"], mode)).reshape(T, H, hd)
    v = (_q(xv, mode) @ _q(m["wv"], mode)).reshape(T, H, hd)
    g = jax.nn.silu(_q(xg, mode) @ _q(m["wg"], mode))
    dec = m["w0"] + jnp.tanh(_q(xw, mode) @ _q(m["wA"], mode)) \
        @ _q(m["wB"], mode)
    w = jnp.exp(-jnp.exp(dec)).reshape(T, H, hd)
    y = _recurrence(r, k, v, w, m["u"])
    eps = config["layer_norm_epsilon"] * config["head_size_divisor"] ** 2
    mu = y.mean(-1, keepdims=True)
    var = jnp.square(y - mu).mean(-1, keepdims=True)
    y = ((y - mu) * jax.lax.rsqrt(var + eps)).reshape(T, D)
    y = y * m["ln_scale"] + m["ln_bias"]
    return _q(y * g, mode) @ _q(m["wo"], mode)


def _channel_mix(mode, h, f):
    dx = _prev(h) - h
    xk = h + dx * f["maa_k"]
    xr = h + dx * f["maa_r"]
    kk = jnp.square(jax.nn.relu(_q(xk, mode) @ _q(f["wk"], mode)))
    return jax.nn.sigmoid(_q(xr, mode) @ _q(f["wr"], mode)) \
        * (_q(kk, mode) @ _q(f["wv"], mode))


def _layer(config, mode, x, lw):
    eps = config["layer_norm_epsilon"]
    x = x + _time_mix(config, mode, _ln(x, lw["norm1"], eps), lw["mixer"])
    return x + _channel_mix(mode, _ln(x, lw["norm2"], eps), lw["ffn"])


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def hidden(config, w, tokens, mode="f32"):
    """tokens [T] -> final normed hidden states [T, D] (float32)."""
    eps = config["layer_norm_epsilon"]
    x = _q(w["embed"], mode)[tokens]
    x = _ln(x, _f32(w["embed_norm"]), eps)

    def body(x, lw):
        return _layer(config, mode, x, _f32(lw)["b0"]), None

    x, _ = jax.lax.scan(body, x, w["units"])
    return _ln(x, _f32(w["final_norm"]), eps)


def _logit_blocks(config, w, h, mode, block):
    """Logits [T // block, block, V] over the published vocabulary."""
    V = config["vocab_size"]
    head = _q(w["unembed"][:V], mode)
    T, D = h.shape
    hb = _q(h, mode).reshape(T // block, block, D)
    return jax.lax.map(lambda r: r @ head.T, hb)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _score(config_items, w, tokens, targets, block):
    config = dict(config_items)
    with jax.default_matmul_precision("highest"):
        h = hidden(config, w, tokens)
        lg = _logit_blocks(config, w, h, "f32", block)
        lg = lg.reshape(-1, lg.shape[-1])
    best = lg.max(-1)
    picked = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[:, None],
                                 -1)[:, 0]
    return jnp.where(targets >= 0, best - picked, 0.0)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _first_fp8(config_items, w, tokens, block):
    config = dict(config_items)
    with jax.default_matmul_precision("highest"):
        hq = hidden(config, w, tokens, "fp8")
        lq = _logit_blocks(config, w, hq, "fp8", block)
    return jnp.argmax(lq.reshape(-1, config["vocab_size"]), -1)


def score(config, w, tokens, targets, block=256):
    """Per position, how far the reference's logit of ``targets[t]`` (the
    token served after tokens[: t + 1]) lies below its best; 0 where
    ``targets[t] < 0``.  ``tokens`` is padded to a multiple of
    ``block``."""
    return _score(_items(config), w, jnp.asarray(tokens),
                  jnp.asarray(targets), block)


def control_targets(config, w, tokens, targets, block=256):
    """The control's tokens: at each position where ``targets[t] >= 0``,
    the token that the float8 reference puts first after tokens[: t + 1]
    (teacher-forced on ``tokens``); -1 elsewhere."""
    first = _first_fp8(_items(config), w, jnp.asarray(tokens), block)
    return jnp.where(jnp.asarray(targets) >= 0, first, -1)


def _items(config):
    """The config's scalars, hashable: the static argument of the jits."""
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))))
