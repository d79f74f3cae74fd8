"""Model assembly: decoder-only LMs (dense/MoE/SSM/hybrid/VLM) + enc-dec.

Layer stacks follow the config's ``pattern_unit × n_units + tail``
factorization: parameters of repeated units are stacked on a leading axis and
traversed with ``jax.lax.scan`` (optionally rematerialized), keeping HLO size
bounded for 61-layer configs.  Caches/recurrent states are stacked the same
way: prefill builds them as the scan's ys, decode carries them through the
scan and updates each layer in place.

Entry points
------------
``init_params``   parameters (+ ``param_axes`` for sharding)
``forward``       tokens -> logits (training / evaluation)
``lm_loss``       next-token CE with optional sequence-chunked logits
``prefill``       tokens -> (last-position logits, decode state)
``decode_step``   one token per sequence against the decode state
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import rwkv6 as RW
from repro.models.sharding import Rules, constrain


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Execution context: sharding rules + implementation selection."""
    rules: Optional[Rules] = None
    mesh: Any = None
    attn_impl: str = "xla_rect"      # xla_rect | pallas
    rnn_impl: str = "xla"            # xla | pallas
    moe_impl: str = "dense"          # dense | ep | ep_a2a
    interpret: bool = False          # Pallas kernels through the interpreter
    remat: bool = True
    ce_chunk: int = 0                # sequence chunking for the CE logits


# --------------------------------------------------------------------------
# per-block params
# --------------------------------------------------------------------------
def _block_init(key, cfg, kind, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"norm1": L.norm_params(cfg.d_model, cfg.norm_type, dtype),
         "norm2": L.norm_params(cfg.d_model, cfg.norm_type, dtype)}
    if kind in ("attn", "local"):
        p["mixer"] = A.attn_params(k1, cfg, dtype)
        p["ffn"] = (MOE.moe_params(k2, cfg, dtype) if cfg.moe is not None
                    else L.mlp_params(k2, cfg.d_model, cfg.d_ff,
                                      cfg.ffn_kind, dtype))
    elif kind == "rglru":
        p["mixer"] = RG.block_params(k1, cfg, dtype)
        p["ffn"] = L.mlp_params(k2, cfg.d_model, cfg.d_ff, cfg.ffn_kind,
                                dtype)
    elif kind == "rwkv":
        p["mixer"] = RW.timemix_params(k1, cfg, dtype)
        p["ffn"] = RW.channelmix_params(k2, cfg, dtype)
    else:
        raise ValueError(kind)
    return p


def _block_axes(cfg, kind):
    ax = {"norm1": L.norm_axes(cfg.norm_type),
          "norm2": L.norm_axes(cfg.norm_type)}
    if kind in ("attn", "local"):
        ax["mixer"] = A.attn_axes(cfg)
        ax["ffn"] = (MOE.moe_axes(cfg) if cfg.moe is not None
                     else L.mlp_axes(cfg.ffn_kind))
    elif kind == "rglru":
        ax["mixer"] = RG.rglru_axes(cfg)
        ax["ffn"] = L.mlp_axes(cfg.ffn_kind)
    else:
        ax["mixer"] = RW.timemix_axes(cfg)
        ax["ffn"] = RW.channelmix_axes(cfg)
    return ax


def _norm(cfg, params, x):
    return L.apply_norm(params, x, cfg.norm_type, cfg.norm_eps)


def zero_aux():
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32)}


def _apply_block(cfg, kind, params, x, ctx: Ctx, mode, cache=None,
                 positions=None, cache_len=0, layer=None):
    """Pre-norm residual block.  Returns (x, new_cache, aux).

    In decode mode ``cache`` is this block's cache, or with ``layer`` the
    stack of its caches over the units, of which the block reads and
    writes layer ``layer`` alone: an attention block the B new rows, a
    recurrent block its whole (small) state.
    """
    aux = zero_aux()
    h = _norm(cfg, params["norm1"], x)
    new_cache = None
    state = None
    if mode == "decode" and kind not in ("attn", "local"):
        state = cache if layer is None else \
            jax.tree.map(lambda a: a[layer], cache)
    if kind in ("attn", "local"):
        if mode == "decode":
            y, new_cache = A.decode_attention(
                params["mixer"], h, cache, positions, cfg=cfg, kind=kind,
                rules=ctx.rules, layer=layer)
        else:
            y, (kc, vc) = A.full_attention(
                params["mixer"], h, cfg=cfg, kind=kind, rules=ctx.rules,
                impl=ctx.attn_impl, positions=positions,
                interpret=ctx.interpret)
            if mode == "prefill":
                c0 = A.init_cache(cfg, kind, x.shape[0], cache_len, x.dtype)
                pos2d = positions if positions is not None else \
                    jnp.arange(x.shape[1], dtype=jnp.int32)[None, :].repeat(
                        x.shape[0], 0)
                new_cache = A.fill_cache(c0, kc, vc, pos2d)
    elif kind == "rglru":
        y, st = RG.apply_block(params["mixer"], h, cfg=cfg, rules=ctx.rules,
                               state=state, impl=ctx.rnn_impl,
                               interpret=ctx.interpret)
        new_cache = st if mode != "train" else None
    else:  # rwkv
        y, st = RW.apply_timemix(params["mixer"], h, cfg=cfg, rules=ctx.rules,
                                 state=state, impl=ctx.rnn_impl,
                                 interpret=ctx.interpret,
                                 remat=ctx.remat and mode == "train")
        new_cache = dict(st) if mode != "train" else None
    x = x + y
    h2 = _norm(cfg, params["norm2"], x)
    if kind == "rwkv":
        f, x_cm = RW.apply_channelmix(
            params["ffn"], h2, cfg=cfg, rules=ctx.rules, state=state)
        if new_cache is not None:
            new_cache["x_cm"] = x_cm
    elif cfg.moe is not None and kind in ("attn", "local"):
        f, aux = MOE.apply(params["ffn"], h2, cfg, ctx.rules, mesh=ctx.mesh,
                           impl=ctx.moe_impl)
    else:
        f = L.apply_mlp(params["ffn"], h2, cfg.ffn_kind)
    x = x + f
    x = constrain(x, ctx.rules, ("batch", "seq", "embed"))
    if state is not None and layer is not None:
        new_cache = jax.tree.map(lambda a, n: a.at[layer].set(n), cache,
                                 new_cache)
    return x, new_cache, aux


def _block_cache_init(cfg, kind, batch, cache_len, dtype):
    if kind in ("attn", "local"):
        return A.init_cache(cfg, kind, batch, cache_len, dtype)
    if kind == "rglru":
        return RG.init_state(cfg, batch, dtype)
    return RW.init_state(cfg, batch, dtype)


def _block_cache_axes(cfg, kind):
    if kind in ("attn", "local"):
        return A.cache_axes()
    if kind == "rglru":
        return RG.state_axes(cfg)
    return RW.state_axes(cfg)


# --------------------------------------------------------------------------
# whole-model params
# --------------------------------------------------------------------------
def init_params(cfg, key, dtype=jnp.float32, max_seq=4096):
    keys = jax.random.split(key, 8)
    Vp = cfg.padded_vocab
    params = {
        "embed": L.embed_init(keys[0], (Vp, cfg.d_model), dtype),
        "final_norm": L.norm_params(cfg.d_model, cfg.norm_type, dtype),
    }
    if not cfg.tied_embeddings:
        params["unembed"] = L.embed_init(keys[1], (Vp, cfg.d_model), dtype)
    if cfg.embed_norm:
        params["embed_norm"] = L.norm_params(cfg.d_model, cfg.norm_type,
                                             dtype)
    if not cfg.use_rope:
        params["pos_embed"] = L.embed_init(keys[2], (max_seq, cfg.d_model),
                                           dtype)

    def unit_init(k):
        ks = jax.random.split(k, len(cfg.pattern_unit))
        return {f"b{i}": _block_init(ks[i], cfg, kind, dtype)
                for i, kind in enumerate(cfg.pattern_unit)}

    unit_keys = jax.random.split(keys[3], cfg.n_units)
    params["units"] = jax.vmap(unit_init)(unit_keys)
    tail_keys = jax.random.split(keys[4], max(1, len(cfg.tail)))
    params["tail"] = [
        _block_init(tail_keys[i], cfg, kind, dtype)
        for i, kind in enumerate(cfg.tail)]
    if cfg.encoder is not None:
        params["encoder"] = _encoder_init(keys[5], cfg, dtype)
        # decoder cross-attention params per layer (stacked with units)
        xkeys = jax.random.split(keys[6], cfg.n_units)
        params["cross"] = jax.vmap(
            lambda k: {"norm": L.norm_params(cfg.d_model, cfg.norm_type,
                                             dtype),
                       "attn": A.attn_params(k, cfg, dtype)})(xkeys)
    return params


def param_axes(cfg):
    """Tree of logical-axis tuples mirroring init_params output."""
    axes = {
        "embed": ("vocab", "embed"),
        "final_norm": L.norm_axes(cfg.norm_type),
    }
    if not cfg.tied_embeddings:
        axes["unembed"] = ("vocab", "embed")
    if cfg.embed_norm:
        axes["embed_norm"] = L.norm_axes(cfg.norm_type)
    if not cfg.use_rope:
        axes["pos_embed"] = (None, "embed")

    def stack(ax_tree):
        return jax.tree.map(lambda ax: ("layers",) + tuple(ax), ax_tree,
                            is_leaf=lambda x: isinstance(x, tuple))

    axes["units"] = stack({f"b{i}": _block_axes(cfg, kind)
                           for i, kind in enumerate(cfg.pattern_unit)})
    axes["tail"] = [_block_axes(cfg, kind) for kind in cfg.tail]
    if cfg.encoder is not None:
        axes["encoder"] = _encoder_axes(cfg)
        axes["cross"] = stack({"norm": L.norm_axes(cfg.norm_type),
                               "attn": A.attn_axes(cfg)})
    return axes


# --------------------------------------------------------------------------
# encoder (whisper; frontend stubbed — inputs are frame embeddings)
# --------------------------------------------------------------------------
def _encoder_init(key, cfg, dtype):
    e = cfg.encoder
    ks = jax.random.split(key, e.n_layers + 1)

    def layer_init(k):
        k1, k2 = jax.random.split(k)
        return {"norm1": L.norm_params(e.d_model, cfg.norm_type, dtype),
                "attn": A.attn_params(k1, cfg, dtype),
                "norm2": L.norm_params(e.d_model, cfg.norm_type, dtype),
                "mlp": L.mlp_params(k2, e.d_model, e.d_ff, "gelu", dtype)}

    return {"layers": jax.vmap(layer_init)(
                jax.random.split(ks[0], e.n_layers)),
            "final_norm": L.norm_params(e.d_model, cfg.norm_type, dtype)}


def _encoder_axes(cfg):
    def stack(t):
        return jax.tree.map(lambda ax: ("layers",) + tuple(ax), t,
                            is_leaf=lambda x: isinstance(x, tuple))
    layer = {"norm1": L.norm_axes(cfg.norm_type), "attn": A.attn_axes(cfg),
             "norm2": L.norm_axes(cfg.norm_type), "mlp": L.mlp_axes("gelu")}
    return {"layers": stack(layer),
            "final_norm": L.norm_axes(cfg.norm_type)}


def _sinusoids(length, channels):
    half = channels // 2
    scale = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / (half - 1)))
    t = jnp.arange(length, dtype=jnp.float32)[:, None] * scale[None, :]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=1)


def encode(cfg, params, frames, ctx: Ctx):
    """frames: [B, n_ctx, d_model] precomputed embeddings (stub frontend)."""
    e = cfg.encoder
    x = frames + _sinusoids(e.n_ctx, e.d_model).astype(frames.dtype)

    def body(h, lp):
        a = _norm(cfg, lp["norm1"], h)
        y, _ = A.full_attention(lp["attn"], a, cfg=cfg, kind="attn",
                                rules=ctx.rules, impl=ctx.attn_impl,
                                causal=False, interpret=ctx.interpret)
        h = h + y
        m = _norm(cfg, lp["norm2"], h)
        h = h + L.apply_mlp(lp["mlp"], m, "gelu")
        return h, None

    if ctx.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    return _norm(cfg, params["encoder"]["final_norm"], x)


# --------------------------------------------------------------------------
# forward (train / eval)
# --------------------------------------------------------------------------
def _embed_tokens(cfg, params, tokens, media=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if media is not None and cfg.n_media_tokens:
        x = jax.lax.dynamic_update_slice(x, media.astype(x.dtype), (0, 0, 0))
    if not cfg.use_rope:
        x = x + params["pos_embed"][None, :x.shape[1], :].astype(x.dtype)
    return _embed_norm(cfg, params, x)


def _embed_norm(cfg, params, x):
    return _norm(cfg, params["embed_norm"], x) if cfg.embed_norm else x


def _run_stack(cfg, params, x, ctx: Ctx, mode, caches=None, positions=None,
               cache_len=0, enc_kv=None):
    """Scan units + unrolled tail.  Returns (x, new_caches, aux_sum).

    Prefill's caches come out of the scan as its ys.  Decode carries the
    stacked caches through the scan, so that each unit writes its layer of
    them in place (a donated state is updated where it lies) instead of
    the scan building a new stack every step.
    """
    has_cross = cfg.encoder is not None
    decoding = mode == "decode"

    def unit_body(carry, xs):
        h, aux_sum, stack = carry
        unit_p = xs["params"]
        cross_p = xs.get("cross")
        cross_kv = xs.get("enc_kv")
        new_c = {}
        stack = dict(stack) if decoding else stack
        for i, kind in enumerate(cfg.pattern_unit):
            name = f"b{i}"
            h, c_out, aux = _apply_block(
                cfg, kind, unit_p[name], h, ctx, mode,
                cache=stack[name] if decoding else None,
                positions=positions, cache_len=cache_len,
                layer=xs["layer"] if decoding else None)
            if decoding:
                stack[name] = c_out
            elif mode == "prefill":
                new_c[name] = c_out
            aux_sum = {k: aux_sum[k] + aux[k] for k in aux_sum}
            if has_cross and cross_p is not None:
                hq = _norm(cfg, cross_p["norm"], h)
                if decoding:
                    y, _ = A.decode_attention(cross_p["attn"], hq, None,
                                              positions, cfg=cfg, kind="attn",
                                              rules=ctx.rules,
                                              cross_kv=cross_kv)
                else:
                    y, _ = A.full_attention(cross_p["attn"], hq, cfg=cfg,
                                            kind="attn", rules=ctx.rules,
                                            impl=ctx.attn_impl, kv=cross_kv,
                                            causal=False,
                                            interpret=ctx.interpret)
                h = h + y
        return (h, aux_sum, stack), (new_c if mode == "prefill" else 0)

    body = jax.checkpoint(unit_body) if (ctx.remat and mode == "train") \
        else unit_body
    xs = {"params": params["units"]}
    stack = None
    if decoding:
        stack = caches["units"]
        xs["layer"] = jnp.arange(cfg.n_units)
    if has_cross:
        xs["cross"] = params["cross"]
        xs["enc_kv"] = enc_kv
    (x, aux_sum, stack), unit_caches = jax.lax.scan(
        body, (x, zero_aux(), stack), xs)
    if decoding:
        unit_caches = stack

    tail_caches = []
    for i, kind in enumerate(cfg.tail):
        c_in = None if caches is None else caches["tail"][i]
        x, c_out, aux = _apply_block(cfg, kind, params["tail"][i], x, ctx,
                                     mode, cache=c_in, positions=positions,
                                     cache_len=cache_len)
        tail_caches.append(c_out)
        aux_sum = {k: aux_sum[k] + aux[k] for k in aux_sum}
    new_caches = None
    if mode != "train":
        new_caches = {"units": unit_caches, "tail": tail_caches}
    return x, new_caches, aux_sum


def _logits(cfg, params, x):
    w = params["embed"] if cfg.tied_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,vd->bsv", x, w)
    return L.softcap(logits, cfg.final_softcap)


def forward(cfg, params, tokens, ctx: Ctx = Ctx(), media=None, frames=None):
    """tokens [B, S] -> logits [B, S, padded_vocab]."""
    x = _embed_tokens(cfg, params, tokens, media)
    x = constrain(x, ctx.rules, ("batch", "seq", "embed"))
    S = tokens.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(
        tokens.shape[0], 0)
    enc_kv = None
    if cfg.encoder is not None:
        enc_out = encode(cfg, params, frames, ctx)
        enc_kv = jax.vmap(lambda cp: A.cross_attn_kv(cp["attn"], enc_out))(
            params["cross"])
    x, _, aux = _run_stack(cfg, params, x, ctx, "train", positions=positions,
                           enc_kv=enc_kv)
    x = _norm(cfg, params["final_norm"], x)
    logits = _logits(cfg, params, x)
    return constrain(logits, ctx.rules, ("batch", "seq", "vocab")), aux


def lm_loss(cfg, params, tokens, labels, ctx: Ctx = Ctx(), media=None,
            frames=None):
    """Next-token CE.  labels < 0 are masked.  Returns (loss, metrics)."""
    x = _embed_tokens(cfg, params, tokens, media)
    x = constrain(x, ctx.rules, ("batch", "seq", "embed"))
    S = tokens.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(
        tokens.shape[0], 0)
    enc_kv = None
    if cfg.encoder is not None:
        enc_out = encode(cfg, params, frames, ctx)
        enc_kv = jax.vmap(lambda cp: A.cross_attn_kv(cp["attn"], enc_out))(
            params["cross"])
    x, _, aux = _run_stack(cfg, params, x, ctx, "train", positions=positions,
                           enc_kv=enc_kv)
    x = _norm(cfg, params["final_norm"], x)
    w = params["embed"] if cfg.tied_embeddings else params["unembed"]

    def ce_chunk(h, y):
        logits = L.softcap(jnp.einsum("bsd,vd->bsv", h, w),
                           cfg.final_softcap).astype(jnp.float32)
        logits = constrain(logits, ctx.rules, ("batch", "seq", "vocab"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
        mask = (y >= 0).astype(jnp.float32)
        return ((lse - picked) * mask).sum(), mask.sum()

    chunk = ctx.ce_chunk
    if chunk and S % chunk == 0 and S > chunk:
        nseg = S // chunk
        hs = x.reshape(x.shape[0], nseg, chunk, -1).swapaxes(0, 1)
        ys = labels.reshape(labels.shape[0], nseg, chunk).swapaxes(0, 1)

        # rematerialized: the [B, chunk, vocab] logits/softmax residuals are
        # recomputed in backward instead of being saved per chunk
        @jax.checkpoint
        def body(acc, inp):
            s, c = ce_chunk(inp[0], inp[1])
            return (acc[0] + s, acc[1] + c), None

        (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                     (hs, ys))
    else:
        tot, cnt = ce_chunk(x, labels)
    loss = tot / jnp.maximum(cnt, 1.0)
    if cfg.moe is not None:
        n_moe = len(cfg.block_kinds())
        loss = loss + cfg.moe.aux_loss * aux["load_balance"] / n_moe \
            + cfg.moe.router_z_loss * aux["router_z"] / n_moe
    return loss, {"ce": tot / jnp.maximum(cnt, 1.0), "tokens": cnt, **aux}


# --------------------------------------------------------------------------
# prefill / decode
# --------------------------------------------------------------------------
def init_decode_state(cfg, batch, cache_len, dtype, enc_kv=None):
    def unit_caches(_):
        return {f"b{i}": _block_cache_init(cfg, kind, batch, cache_len,
                                           dtype)
                for i, kind in enumerate(cfg.pattern_unit)}

    units = jax.vmap(unit_caches)(jnp.arange(cfg.n_units))
    tail = [_block_cache_init(cfg, kind, batch, cache_len, dtype)
            for kind in cfg.tail]
    state = {"caches": {"units": units, "tail": tail},
             "pos": jnp.zeros((batch,), jnp.int32)}
    if enc_kv is not None:
        state["enc_kv"] = enc_kv
    return state


def decode_state_axes(cfg):
    units = {f"b{i}": jax.tree.map(
        lambda ax: ("layers",) + tuple(ax), _block_cache_axes(cfg, kind),
        is_leaf=lambda x: isinstance(x, tuple))
        for i, kind in enumerate(cfg.pattern_unit)}
    tail = [_block_cache_axes(cfg, kind) for kind in cfg.tail]
    state = {"caches": {"units": units, "tail": tail}, "pos": ("batch",)}
    if cfg.encoder is not None:
        state["enc_kv"] = (("layers", "batch", None, "kv_heads", "head_dim"),
                           ("layers", "batch", None, "kv_heads", "head_dim"))
    return state


def prefill(cfg, params, tokens, cache_len, ctx: Ctx = Ctx(), media=None,
            frames=None):
    """Run the prompt, build the decode state.  Returns (last_logits, state)."""
    B, S = tokens.shape
    x = _embed_tokens(cfg, params, tokens, media)
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    enc_kv = None
    if cfg.encoder is not None:
        enc_out = encode(cfg, params, frames, ctx)
        enc_kv = jax.vmap(lambda cp: A.cross_attn_kv(cp["attn"], enc_out))(
            params["cross"])
    x, caches, _ = _run_stack(cfg, params, x, ctx, "prefill",
                              positions=positions, cache_len=cache_len,
                              enc_kv=enc_kv)
    x = _norm(cfg, params["final_norm"], x)
    logits = _logits(cfg, params, x[:, -1:, :])
    state = {"caches": caches, "pos": jnp.full((B,), S, jnp.int32)}
    if enc_kv is not None:
        state["enc_kv"] = enc_kv
    return logits[:, 0], state


def decode_step(cfg, params, tokens, state, ctx: Ctx = Ctx()):
    """tokens: [B] -> (logits [B, Vp], new state)."""
    B = tokens.shape[0]
    positions = state["pos"]
    x = jnp.take(params["embed"], tokens[:, None], axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if not cfg.use_rope:
        pe = jnp.take(params["pos_embed"], positions, axis=0)
        x = x + pe[:, None, :].astype(x.dtype)
    x = constrain(_embed_norm(cfg, params, x), ctx.rules,
                  ("batch", "seq", "embed"))
    x, caches, _ = _run_stack(cfg, params, x, ctx, "decode",
                              caches=state["caches"], positions=positions,
                              enc_kv=state.get("enc_kv"))
    x = _norm(cfg, params["final_norm"], x)
    logits = _logits(cfg, params, x)
    logits = constrain(logits, ctx.rules, ("batch", "seq", "vocab"))
    new_state = dict(state)
    new_state["caches"] = caches
    new_state["pos"] = positions + 1
    return logits[:, 0], new_state
