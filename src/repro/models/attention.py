"""GQA attention: full/causal/sliding-window, training + prefill + decode.

Three implementations of the score/softmax/value core:

* ``xla_rect``  — q-block-chunked attention in plain jnp (lax.scan over query
  blocks, full kv per block with masking).  Paper-faithful baseline path; for
  causal masks it executes the full rectangle (2x flops waste — visible in
  the roofline, driven down by the banded/pallas paths in §Perf).
* ``xla_flash`` — banded pair-list flash (see ``xla_flash.py``): true causal /
  local block skipping, online softmax, f32 accumulators.
* ``pallas``    — Pallas TPU kernel (``repro.kernels``), same block structure.

KV cache: ring buffer of length ``min(max_len, window)`` for local layers —
this is what makes gemma3/recurrentgemma long-context decode sub-quadratic.
Entries carry their absolute positions; masking is position-based, so the
ring wrap needs no special cases.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import xla_flash
from repro.models.sharding import constrain


def attn_params(key, cfg, dtype):
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (D, H, hd), dtype, fan_in=D),
        "wk": L.dense_init(ks[1], (D, K, hd), dtype, fan_in=D),
        "wv": L.dense_init(ks[2], (D, K, hd), dtype, fan_in=D),
        "wo": L.dense_init(ks[3], (H, hd, D), dtype, fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((K, hd), dtype)
        p["bv"] = jnp.zeros((K, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attn_axes(cfg):
    ax = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        ax.update(bq=("heads", "head_dim"), bk=("kv_heads", "head_dim"),
                  bv=("kv_heads", "head_dim"))
    if cfg.qk_norm:
        ax.update(q_norm=("head_dim",), k_norm=("head_dim",))
    return ax


def _rope_theta(cfg, kind):
    if kind == "local" and cfg.rope_theta_local is not None:
        return cfg.rope_theta_local
    return cfg.rope_theta


def _project_q(params, x, cfg, positions, kind, with_rope=True):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]
    if cfg.qk_norm:
        q = L.rms_head_norm(params["q_norm"], q)
    if with_rope and cfg.use_rope:
        q = L.rope(q, positions, _rope_theta(cfg, kind))
    return q


def _project_kv(params, x, cfg, positions, kind, with_rope=True):
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        k, v = k + params["bk"], v + params["bv"]
    if cfg.qk_norm:
        k = L.rms_head_norm(params["k_norm"], k)
    if with_rope and cfg.use_rope:
        k = L.rope(k, positions, _rope_theta(cfg, kind))
    return k, v


def cross_attn_kv(params, enc_out):
    """Precompute a cross-attention layer's K/V from encoder memory."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    return k, v


def _out_proj(params, ctx, rules):
    # ctx: [B, S, H, hd]
    y = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])
    return constrain(y, rules, ("batch", "seq", "embed"))


# --------------------------------------------------------------------------
# full-sequence attention (train / prefill)
# --------------------------------------------------------------------------
def _rect_attention(q, k, v, q_pos, kv_pos, *, causal, window, softcap,
                    q_block=256):
    """Chunked rectangular attention. q:[B,S,H,hd] k,v:[B,T,K,hd]."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = hd ** -0.5
    qb = min(q_block, S)
    while S % qb:
        qb //= 2
    nq = S // qb
    qg = q.reshape(B, nq, qb, K, G, hd)
    qpos = q_pos.reshape(nq, qb) if q_pos.ndim == 1 else None
    # scan over q blocks; kv stays resident.  The body is rematerialized:
    # recomputing scores in the backward pass keeps the softmax residuals
    # ([B,K,G,qb,T] f32 per block) out of the saved-activation set.
    @jax.checkpoint
    def body(_, inp):
        qi, pq = inp                                   # [B,qb,K,G,hd], [qb]
        s = jnp.einsum("bqkgh,btkh->bkgqt", qi.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
        s = L.softcap(s, softcap)
        m = jnp.ones((qb, T), bool)
        if causal:
            m &= pq[:, None] >= kv_pos[None, :]
        if window:
            m &= (pq[:, None] - kv_pos[None, :]) < window
        s = jnp.where(m, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,btkh->bqkgh", p, v.astype(jnp.float32))
        return None, o.reshape(B, qb, H, hd)

    _, out = jax.lax.scan(body, None, (qg.swapaxes(0, 1), qpos))
    return out.swapaxes(0, 1).reshape(B, S, H, hd).astype(q.dtype)


def full_attention(params, x, *, cfg, kind, rules, impl="xla_rect",
                   positions=None, kv=None, kv_pos=None, causal=True,
                   softcap=None, interpret=False):
    """Self (or cross, via kv=) attention over a full sequence."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    is_cross = kv is not None
    q = _project_q(params, x, cfg, positions, kind, with_rope=not is_cross)
    if is_cross:
        k, v = kv
        kvp = kv_pos if kv_pos is not None \
            else jnp.arange(k.shape[1], dtype=jnp.int32)
        causal = False
    else:
        k, v = _project_kv(params, x, cfg, positions, kind)
        kvp = positions[0]
    window = cfg.local_window if kind == "local" else 0
    sc = cfg.attn_softcap if softcap is None else softcap
    q = constrain(q, rules, ("batch", "seq", "heads", None))
    k = constrain(k, rules, ("batch", "seq", "kv_heads", None))
    v = constrain(v, rules, ("batch", "seq", "kv_heads", None))
    if impl == "xla_flash":
        ctx = xla_flash.flash_attention(q, k, v, positions[0], kvp,
                                        causal=causal, window=window,
                                        softcap=sc)
    elif impl == "pallas":
        from repro.kernels import ops as kops
        ctx = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=sc, interpret=interpret)
    else:
        ctx = _rect_attention(q, k, v, positions[0], kvp, causal=causal,
                              window=window, softcap=sc)
    y = _out_proj(params, ctx, rules)
    return y, (k, v)


# --------------------------------------------------------------------------
# KV cache (decode)
# --------------------------------------------------------------------------
def init_cache(cfg, kind, batch, max_len, dtype):
    C = max_len if (kind != "local" or not cfg.local_window) \
        else min(max_len, cfg.local_window)
    K, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, C, K, hd), dtype),
        "v": jnp.zeros((batch, C, K, hd), dtype),
        "pos": jnp.full((batch, C), -1, jnp.int32),
    }


def cache_axes():
    return {"k": ("batch", "cache_seq", "kv_heads", "head_dim"),
            "v": ("batch", "cache_seq", "kv_heads", "head_dim"),
            "pos": ("batch", "cache_seq")}


def _ring_write(cache, k_new, v_new, positions):
    """Write one token per batch row at slot = pos % C."""
    C = cache["k"].shape[1]
    slots = positions % C

    def upd(buf, new, slot):
        return jax.lax.dynamic_update_slice_in_dim(buf, new[None], slot,
                                                   axis=0)

    k = jax.vmap(upd)(cache["k"], k_new, slots)
    v = jax.vmap(upd)(cache["v"], v_new, slots)
    pos = jax.vmap(
        lambda p, s, val: jax.lax.dynamic_update_slice_in_dim(
            p, val[None], s, axis=0))(cache["pos"], slots, positions)
    return {"k": k, "v": v, "pos": pos}


def fill_cache(cache, k, v, positions):
    """Prefill: write the (last C) tokens of k/v into the cache."""
    C = cache["k"].shape[1]
    S = k.shape[1]
    if S >= C:
        # keep the trailing C tokens; ring slot = pos % C keeps mask logic
        ktail, vtail = k[:, S - C:], v[:, S - C:]
        ptail = positions[:, S - C:]
        # rotate so that entry i sits at slot pos_i % C
        slots = ptail % C
        inv = jnp.argsort(slots, axis=1)
        gather = jax.vmap(lambda a, i: a[i])
        return {"k": gather(ktail, inv), "v": gather(vtail, inv),
                "pos": gather(ptail, inv)}
    k0 = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
    v0 = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
    p0 = jax.lax.dynamic_update_slice_in_dim(cache["pos"], positions, 0,
                                             axis=1)
    return {"k": k0, "v": v0, "pos": p0}


def decode_attention(params, x, cache, positions, *, cfg, kind, rules,
                     impl="xla", cross_kv=None, cross_pos=None):
    """One-token decode. x: [B, 1, D]; positions: [B] absolute positions."""
    B = x.shape[0]
    is_cross = cross_kv is not None
    q = _project_q(params, x, cfg, positions[:, None], kind,
                   with_rope=not is_cross)
    if is_cross:
        k, v = cross_kv                       # [B, T, K, hd] encoder memory
        valid = jnp.ones((B, k.shape[1]), bool)
        new_cache = cache
    else:
        k_new, v_new = _project_kv(params, x, cfg, positions[:, None], kind)
        new_cache = _ring_write(cache, k_new[:, 0], v_new[:, 0], positions)
        k, v = new_cache["k"], new_cache["v"]
        cpos = new_cache["pos"]               # [B, C]
        valid = (cpos >= 0) & (cpos <= positions[:, None])
        if kind == "local" and cfg.local_window:
            valid &= (positions[:, None] - cpos) < cfg.local_window
    K, hd = k.shape[2], k.shape[3]
    G = cfg.n_heads // K
    qf = q[:, 0].reshape(B, K, G, hd).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bkgh,bckh->bkgc", qf, k.astype(jnp.float32))
    s = L.softcap(s, cfg.attn_softcap)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgc,bckh->bkgh", p, v.astype(jnp.float32))
    ctx = ctx.reshape(B, 1, cfg.n_heads, hd).astype(x.dtype)
    y = _out_proj(params, ctx, rules)
    return y, new_cache
