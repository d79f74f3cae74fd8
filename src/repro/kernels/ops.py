"""Jitted dispatchers for the Pallas kernels.

Each op rearranges model-layout tensors into kernel layout, invokes the
kernel, and returns its result in model layout.

``interpret`` is the caller's explicit choice: ``False`` (the default)
compiles the kernel with Mosaic for a TPU; ``True`` runs it through the
Pallas interpreter, which is how the CPU tests exercise the same code.  It
is never inferred from the backend, so a machine without a TPU fails to
compile a kernel instead of quietly interpreting it.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import wkv6 as _wkv


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_kv=128, interpret=False):
    """q: [B, S, H, hd]; k, v: [B, T, K, hd] (GQA) -> [B, S, H, hd]."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qk = q.reshape(B, S, K, G, hd).transpose(0, 2, 3, 1, 4).reshape(
        B * K, G, S, hd)
    kk = k.transpose(0, 2, 1, 3).reshape(B * K, T, hd)
    vk = v.transpose(0, 2, 1, 3).reshape(B * K, T, hd)
    scale = hd ** -0.5
    out = _fa.flash_attention_bkgs(
        (qk.astype(jnp.float32) * scale).astype(qk.dtype), kk, vk,
        causal=causal, window=window, softcap=softcap, block_q=block_q,
        block_kv=block_kv, interpret=interpret)
    return out.reshape(B, K, G, S, hd).transpose(0, 3, 1, 2, 4).reshape(
        B, S, H, hd)


def decode_attention(q, k, v, cpos, cur, *, window=0, softcap=0.0,
                     block_kv=512, interpret=False):
    """q: [B, H, hd]; k, v: [B, C, K, hd]; cpos: [B, C]; cur: [B]."""
    B, H, hd = q.shape
    C, K = k.shape[1], k.shape[2]
    G = H // K
    qk = q.reshape(B, K, G, hd).reshape(B * K, G, hd)
    scale = hd ** -0.5
    qk = (qk.astype(jnp.float32) * scale).astype(qk.dtype)
    kk = k.transpose(0, 2, 1, 3).reshape(B * K, C, hd)
    vk = v.transpose(0, 2, 1, 3).reshape(B * K, C, hd)
    cp = jnp.repeat(cpos, K, axis=0)[:, None, :]
    cu = jnp.repeat(cur, K, axis=0).astype(jnp.int32)
    out = _dec.decode_attention_bk(qk, kk, vk, cp, cu, window=window,
                                   softcap=softcap, block_kv=block_kv,
                                   interpret=interpret)
    return out.reshape(B, K, G, hd).reshape(B, H, hd)


def rglru_scan(log_a, x, *, block_t=256, block_w=128, interpret=False):
    """log_a, x: [B, S, W] -> (h [B, S, W] f32, h_last [B, W] f32)."""
    log_a = log_a.astype(jnp.float32)
    mult = jnp.sqrt(jnp.maximum(-jnp.expm1(2.0 * log_a), 1e-6))
    h, h_last = _rg.rglru_scan_pallas(
        jnp.exp(log_a), mult * x.astype(jnp.float32), block_t=block_t,
        block_w=block_w, interpret=interpret)
    return h, h_last[:, 0]


def wkv6(r, k, v, w, u, s0, *, block_t=64, interpret=False):
    """Model layout: r,k,v,w [B, S, H, hd]; u [H, hd]; s0 [B, H, hd, hd]."""
    B, S, H, hd = r.shape

    def to_bh(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, S, hd).astype(
            jnp.float32)

    u_b = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd).astype(
        jnp.float32)
    s0_b = s0.reshape(B * H, hd, hd).astype(jnp.float32)
    y, s_last = _wkv.wkv6_pallas(to_bh(r), to_bh(k), to_bh(v), to_bh(w),
                                 u_b, s0_b, block_t=block_t,
                                 interpret=interpret)
    y = y.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    return y, s_last.reshape(B, H, hd, hd)
