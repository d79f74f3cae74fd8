"""GQA attention: full/causal/sliding-window, training + prefill + decode.

Two implementations of the score/softmax/value core, chosen by
``Ctx.attn_impl``:

* ``xla_rect`` — q-block-chunked attention in plain jnp (lax.scan over query
  blocks, full kv per block with masking).  The served path; for causal
  masks it computes the full rectangle of scores.
* ``pallas``   — the Pallas TPU flash kernel (``repro.kernels``): causal and
  local blocks skipped, online softmax, f32 accumulators.

KV cache: ring buffer of length ``min(max_len, window)`` for local layers —
this is what makes gemma3/recurrentgemma long-context decode sub-quadratic.
Entries carry their absolute positions; masking is position-based, so the
ring wrap needs no special cases.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
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
    if impl == "pallas":
        from repro.kernels import ops as kops
        ctx = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=sc, interpret=interpret)
    elif impl == "xla_rect":
        ctx = _rect_attention(q, k, v, positions[0], kvp, causal=causal,
                              window=window, softcap=sc)
    else:
        raise ValueError(f"unknown attn_impl {impl!r}: "
                         "expected 'xla_rect' or 'pallas'")
    y = _out_proj(params, ctx, rules)
    return y, (k, v)


# --------------------------------------------------------------------------
# KV cache (decode)
# --------------------------------------------------------------------------
def init_cache(cfg, kind, batch, max_len, dtype):
    """One layer's KV cache: ``k``/``v`` [B, C, K*hd] (each position's
    heads side by side in one row) and the position each slot holds."""
    C = max_len if (kind != "local" or not cfg.local_window) \
        else min(max_len, cfg.local_window)
    kv = cfg.n_kv_heads * cfg.head_dim
    return {
        "k": jnp.zeros((batch, C, kv), dtype),
        "v": jnp.zeros((batch, C, kv), dtype),
        "pos": jnp.full((batch, C), -1, jnp.int32),
    }


def cache_axes():
    return {"k": ("batch", "cache_seq", "kv_heads"),
            "v": ("batch", "cache_seq", "kv_heads"),
            "pos": ("batch", "cache_seq")}


def _ring_write(cache, k_new, v_new, positions, layer=None):
    """Write each row's new token at slot ``pos % C``, in place.

    ``cache`` is one layer's ([B, C, ...]) or, with ``layer``, a stack of
    layers ([n_layers, B, C, ...]) of which row ``b`` of layer ``layer`` is
    written.  Only the B new rows are written; a donated cache is updated
    where it lies.
    """
    B, C = positions.shape[0], cache["pos"].shape[-1]
    at = (jnp.arange(B), positions % C)
    if layer is not None:
        at = (layer,) + at

    def put(buf, new):
        return buf.at[at].set(new.astype(buf.dtype), unique_indices=True)

    return {"k": put(cache["k"], k_new), "v": put(cache["v"], v_new),
            "pos": put(cache["pos"], positions)}


def fill_cache(cache, k, v, positions):
    """Prefill: write the (last C) tokens of k/v [B, S, K, hd] into the
    cache."""
    B, S = k.shape[:2]
    k, v = k.reshape(B, S, -1), v.reshape(B, S, -1)
    C = cache["k"].shape[1]
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


def _attend_rows(q, k, v, valid, softcap):
    """One query per row against keys and values stored as [B, C, K*hd].

    q: [B, H, hd] -> [B, H, hd].  Each query head is put in its KV head's
    columns of a block-diagonal [B, H, K*hd] matrix, so that one product
    per row reads the stored rows whole, with no per-head transposed copy.
    The zeros add nothing: the scores are the per-head products, summed in
    float32, and the softmax is float32.  They cost K times the useful
    FLOPs, H per cache byte read, which stays below a TPU's ridge for the
    head counts here; a per-head view of the rows would copy the cache into
    another layout instead.
    """
    B, H, hd = q.shape
    K = k.shape[-1] // hd
    own = (jnp.arange(H)[:, None] // (H // K) == jnp.arange(K)[None, :])
    own = own[None, :, :, None]                              # [1, H, K, 1]
    # q at its own precision times the scale (a power of two for the usual
    # head sizes, so exact)
    qs = (q * hd ** -0.5)[:, :, None, :]
    qb = jnp.where(own, qs, 0).reshape(B, H, K * hd)
    s = jnp.einsum("bnd,bcd->bnc", qb, k, preferred_element_type=jnp.float32)
    s = L.softcap(s, softcap)
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnc,bcd->bnd", p, v, preferred_element_type=jnp.float32)
    o = o.reshape(B, H, K, hd)
    return jnp.where(own, o, 0.0).sum(axis=2)


def decode_attention(params, x, cache, positions, *, cfg, kind, rules,
                     layer=None, cross_kv=None):
    """One-token decode. x: [B, 1, D]; positions: [B] absolute positions.

    Self-attention writes the token's K/V into ``cache`` (a stacked cache
    when ``layer`` is given, see ``_ring_write``) and attends over that
    layer as stored (``_attend_rows``).  Cross-attention (``cross_kv``,
    [B, T, K, hd] encoder memory) writes nothing.
    """
    B = x.shape[0]
    is_cross = cross_kv is not None
    q = _project_q(params, x, cfg, positions[:, None], kind,
                   with_rope=not is_cross)
    if is_cross:
        k, v = (a.reshape(a.shape[0], a.shape[1], -1) for a in cross_kv)
        valid = jnp.ones((B, k.shape[1]), bool)
        new_cache = cache
    else:
        k_new, v_new = _project_kv(params, x, cfg, positions[:, None], kind)
        new_cache = _ring_write(cache, k_new.reshape(B, -1),
                                v_new.reshape(B, -1), positions, layer)
        mine = new_cache if layer is None else \
            jax.tree.map(lambda a: a[layer], new_cache)
        k, v, cpos = mine["k"], mine["v"], mine["pos"]      # cpos: [B, C]
        valid = (cpos >= 0) & (cpos <= positions[:, None])
        if kind == "local" and cfg.local_window:
            valid &= (positions[:, None] - cpos) < cfg.local_window
    ctx = _attend_rows(q[:, 0], k, v, valid, cfg.attn_softcap)
    y = _out_proj(params, ctx[:, None].astype(x.dtype), rules)
    return y, new_cache
