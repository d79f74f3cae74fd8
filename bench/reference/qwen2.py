"""Plain float32 Qwen2, written from the published description.

Qwen2 (arXiv:2407.10671, and the model's public ``config.json``): a
decoder-only transformer of pre-RMSNorm blocks; grouped-query attention
with biases on the query, key and value projections and none on the
output; rotary position embeddings (rotate-half form, base
``rope_theta``); a SwiGLU MLP; a final RMSNorm; the output head tied to
the input embedding.  Everything here runs in float32 at the highest
matmul precision, layer by layer (a scan over the stacked layers) and the
output head in blocks of rows, so that the published widths fit one chip.

It imports nothing of the program.  The weights it reads are the
benchmark's (``bench/weights.py``), in the program's tree layout: only the
names below tie the two together.

``mode`` "fp8" runs the same mathematics one precision lower, for the
control: it rounds every weight and every matmul input through float8
(e4m3, one scale per tensor), one step below the bfloat16 that serving
states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# (leaf path pattern, distribution, arguments): the weights bench/weights.py
# draws for this family.  Norm gains and biases are random too, so a
# program that skipped one would differ from this reference.
RULES = [
    (r"^embed$", "embedding", {"std": 0.02}),   # "vocab" is set from config
    (r"norm\d?/scale$|final_norm/scale$", "one_plus_normal", {"std": 0.1}),
    (r"mixer/b[qkv]$", "normal", {"std": 0.1}),
    (r"mixer/wo$", "normal_fan_in", {"fan_in": "all_but_last"}),
    (r"mixer/w[qkv]$|ffn/w_(gate|up|down)$", "normal_fan_in", {}),
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


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, heads, hd]; rotate-half form."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _layer(config, mode, x, lw):
    """One block over the whole sequence x [T, D]."""
    eps = config["rms_norm_eps"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    T = x.shape[0]
    pos = jnp.arange(T)
    m, f = lw["mixer"], lw["ffn"]
    h = _q(_rms(x, lw["norm1"]["scale"], eps), mode)
    q = jnp.einsum("td,dhk->thk", h, _q(m["wq"], mode)) + m["bq"]
    k = jnp.einsum("td,dhk->thk", h, _q(m["wk"], mode)) + m["bk"]
    v = jnp.einsum("td,dhk->thk", h, _q(m["wv"], mode)) + m["bv"]
    theta = config["rope_theta"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(T, K, H // K, hd)     # query head h reads kv head h // (H/K)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / hd ** 0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, H, hd)
    x = x + jnp.einsum("thk,hkd->td", _q(o, mode), _q(m["wo"], mode))
    h2 = _q(_rms(x, lw["norm2"]["scale"], eps), mode)
    a = jax.nn.silu(h2 @ _q(f["w_gate"], mode)) * (h2 @ _q(f["w_up"], mode))
    return x + _q(a, mode) @ _q(f["w_down"], mode)


def hidden(config, w, tokens, mode="f32"):
    """tokens [T] -> final normed hidden states [T, D] (float32)."""
    emb = _q(w["embed"], mode)
    x = emb[tokens]

    def body(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        return _layer(config, mode, x, lw["b0"]), None

    x, _ = jax.lax.scan(body, x, w["units"])
    return _rms(x, w["final_norm"]["scale"], config["rms_norm_eps"])


def _logit_blocks(config, w, h, mode, block):
    """Logits [T // block, block, V] over the published vocabulary."""
    V = config["vocab_size"]
    emb = _q(w["embed"][:V], mode)
    T, D = h.shape
    hb = _q(h, mode).reshape(T // block, block, D)
    return jax.lax.map(lambda r: r @ emb.T, hb)


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

