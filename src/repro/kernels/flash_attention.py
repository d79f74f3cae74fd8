"""Pallas TPU flash attention (causal / sliding-window / GQA).

Grid: ``(B·K, num_q_blocks, num_kv_blocks)`` — the kv dimension is the
minor-most (sequentially iterated) axis, so the online-softmax state for one
(batch·kv-head, q-block) lives in VMEM scratch across kv steps.  Dead blocks
outside the causal/local band are skipped with ``pl.when`` (grid points are
still visited, but no MXU work is issued).

Layouts (pre-arranged by ``ops.flash_attention``):
    q:   [B·K, G, S, hd]    (G = query heads per kv head)
    k,v: [B·K, T, hd]
    out: [B·K, G, S, hd]

Block shapes keep the MXU dims (bq, bkv, hd) at 128-multiples where the
problem allows; VMEM working set per step is
``G·bq·hd + 2·bkv·hd + G·bq·bkv`` f32 words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            bq, bkv, causal, window, softcap, nkv):
    i = pl.program_id(1)
    j = pl.program_id(2)

    q_lo = i * bq
    q_hi = q_lo + bq - 1
    k_lo = j * bkv
    k_hi = k_lo + bkv - 1

    # band-aliveness (static per grid point once i, j are concrete values)
    alive = jnp.bool_(True)
    if causal:
        alive &= k_lo <= q_hi
    if window:
        alive &= k_hi >= q_lo - window + 1

    # last kv block that this q block attends to (for the final write)
    j_last = nkv - 1
    if causal:
        j_last = jnp.minimum(j_last, q_hi // bkv)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(alive)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # [G, bq, hd]
        k = k_ref[0].astype(jnp.float32)              # [bkv, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [G, bq, bkv]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pq = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        pk = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= pq >= pk
        if window:
            mask &= (pq - pk) < window
        s = jnp.where(mask[None], s, NEG_INF)

        m_prev = m_sc[...]
        l_prev = l_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))   # [G, bq]
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_prev * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [G, bq, hd]
        acc_sc[...] = acc_sc[...] * corr[..., None] + pv
        m_sc[...] = m_new

    @pl.when(j == j_last)
    def _write():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention_bkgs(q, k, v, *, causal=True, window=0, softcap=0.0,
                         block_q=128, block_kv=128, interpret=False):
    """q: [BK, G, S, hd]; k, v: [BK, T, hd] -> [BK, G, S, hd]."""
    BK, G, S, hd = q.shape
    T = k.shape[1]
    bq, bkv = min(block_q, S), min(block_kv, T)
    while S % bq:
        bq //= 2
    while T % bkv:
        bkv //= 2
    nq, nkv = S // bq, T // bkv
    grid = (BK, nq, nkv)
    kernel = functools.partial(_kernel, bq=bq, bkv=bkv, causal=causal,
                               window=window, softcap=softcap, nkv=nkv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, bq, hd), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, bq, hd), lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BK, G, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
