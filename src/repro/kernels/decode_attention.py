"""Pallas TPU flash-decode: one-token KV-cache attention with split-K.

Grid: ``(B·K, num_cache_chunks)`` — cache chunks are the sequential axis; the
partial (m, l, acc) reduction lives in VMEM scratch across chunks.  Validity
is position-based (ring-buffered caches store absolute positions; empty slots
hold -1), so ring wrap needs no special casing.

Layouts (pre-arranged by ``ops.decode_attention``):
    q:    [B·K, G, hd]
    k,v:  [B·K, C, hd]
    cpos: [B·K, 1, C] int32   (absolute position per cache slot, -1 = empty)
    cur:  [B·K] int32         (current decode position; scalar-prefetched
                               into SMEM)
    out:  [B·K, G, hd]

Every VMEM block keeps its last two dims either (8, 128)-aligned or equal to
the array's, which is what Mosaic requires: the per-row positions carry a
unit middle axis and the per-row scalar goes through SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(cur_ref, q_ref, k_ref, v_ref, cpos_ref, o_ref, m_sc, l_sc,
            acc_sc, *, window, softcap, nc):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)                  # [G, hd]
    k = k_ref[0].astype(jnp.float32)                  # [ckv, hd]
    v = v_ref[0].astype(jnp.float32)
    cpos = cpos_ref[0]                                # [1, ckv]
    cur = cur_ref[b]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, ckv]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = (cpos >= 0) & (cpos <= cur)
    if window:
        valid &= (cur - cpos) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_sc[...]                                # [G, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_sc[...] = acc_sc[...] * corr + pv
    m_sc[...] = m_new

    @pl.when(j == nc - 1)
    def _write():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def decode_attention_bk(q, k, v, cpos, cur, *, window=0, softcap=0.0,
                        block_kv=512, interpret=False):
    """q: [BK, G, hd]; k,v: [BK, C, hd]; cpos: [BK, 1, C]; cur: [BK]."""
    BK, G, hd = q.shape
    C = k.shape[1]
    ckv = min(block_kv, C)
    while C % ckv:
        ckv //= 2
    nc = C // ckv
    kernel = functools.partial(_kernel, window=window, softcap=softcap,
                               nc=nc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BK, nc),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, j, cur: (b, 0, 0)),
            pl.BlockSpec((1, ckv, hd), lambda b, j, cur: (b, j, 0)),
            pl.BlockSpec((1, ckv, hd), lambda b, j, cur: (b, j, 0)),
            pl.BlockSpec((1, 1, ckv), lambda b, j, cur: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j, cur: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BK, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cur, q, k, v, cpos)
