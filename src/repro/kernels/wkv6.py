"""Pallas TPU WKV6 recurrence (RWKV6 time-mix core).

Grid: ``(B·H, num_time_blocks)`` — time sequential, per-(batch·head) state
matrix S in VMEM scratch.  The recurrence

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    y_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

is evaluated a time block at a time in its chunked ("parallel") form.  With
the within-block log-decay prefix sums ``L_t = sum_{s<=t} log w_s`` (and the
exclusive ``Lx_t = L_t - log w_t``):

    y_t   = sum_{s<t} [sum_i r_ti k_si e^{Lx_ti - L_si}] v_s
            + (r_t . u . k_t) v_t + (r_t e^{Lx_t}) S_0
    S_end = diag(e^{L_end}) S_0 + sum_s (k_s e^{L_end - L_s}) v_s^T

Every exponent is a sum of log-decays over a time interval, so it is <= 0:
nothing overflows however strong the decay, and the result is exact up to
rounding.  The pairwise decay tensor is [bt, bt, hd], which bounds ``bt``.

Layouts: r, k, v, w: [B·H, S, hd] f32 (w = decay in (0,1));
u: [B·H, 1, hd] (pre-broadcast from [H, hd]); s0: [B·H, hd, hd] f32.
Outputs: y [B·H, S, hd] f32; s_last [B·H, hd, hd] f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (0,)), ((), ()))       # [m, k] @ [k, n]
_TN = (((0,), (0,)), ((), ()))       # [k, m]^T @ [k, n]
# the state is an f32 running sum: keep its matmuls at full f32 precision
_mm = functools.partial(jax.lax.dot_general,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sl_ref,
            state_sc, *, nt):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        state_sc[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)                  # [bt, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = jnp.log(w_ref[0].astype(jnp.float32))
    u = u_ref[0].astype(jnp.float32)                  # [1, hd]
    s0 = state_sc[...]                                # [hd, hd]
    bt = r.shape[0]

    # inclusive prefix sum over time (Hillis-Steele with sublane rolls)
    row = jax.lax.broadcasted_iota(jnp.int32, lw.shape, 0)
    cum = lw
    d = 1
    while d < bt:
        cum = cum + jnp.where(row < d, 0.0, pltpu.roll(cum, d, 0))
        d *= 2
    cum_x = cum - lw                                  # exclusive
    cum_end = cum[bt - 1:bt]                          # [1, hd]

    # intra-block pairs s < t, with the decay between them per channel
    ti = jax.lax.broadcasted_iota(jnp.int32, (bt, bt, 1), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (bt, bt, 1), 1)
    expo = jnp.where(si < ti, cum_x[:, None, :] - cum[None, :, :], -jnp.inf)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(expo), axis=-1)
    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)          # [bt, 1]
    y = _mm(att, v, _NT) + bonus * v + _mm(r * jnp.exp(cum_x), s0, _NT)
    y_ref[0] = y.astype(y_ref.dtype)
    k_end = k * jnp.exp(cum_end - cum)
    state_sc[...] = jnp.exp(cum_end).reshape(-1, 1) * s0 + _mm(k_end, v, _TN)

    @pl.when(t == nt - 1)
    def _write_last():
        sl_ref[0] = state_sc[...].astype(sl_ref.dtype)


def wkv6_pallas(r, k, v, w, u, s0, *, block_t=64, interpret=False):
    """r,k,v,w: [BH, S, hd]; u: [BH, 1, hd]; s0: [BH, hd, hd]."""
    BH, S, hd = r.shape
    bt = min(block_t, S)
    while S % bt:
        bt //= 2
    nt = S // bt
    kernel = functools.partial(_kernel, nt=nt)
    return pl.pallas_call(
        kernel,
        grid=(BH, nt),
        in_specs=[
            pl.BlockSpec((1, bt, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, s0)
