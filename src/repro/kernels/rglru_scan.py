"""Pallas TPU RG-LRU linear-recurrence scan: ``h_t = a_t h_{t-1} + b_t``.

Grid: ``(B, num_channel_blocks, num_time_blocks)`` — time is the sequential
axis; the hidden state (one ``bw``-wide channel block) persists in VMEM
scratch across time blocks.  Within a block the linear recurrence
``h_t = a_t h_{t-1} + b_t`` is evaluated with a log-depth associative scan
over the (bt, bw) tile (Hillis-Steele, with sublane rolls), so the MXU-free
recurrence still vectorizes over the 128-lane dimension.

The gates (``a = exp(log_a)``, ``b = sqrt(1 - a^2) x``) are elementwise and
are formed by ``ops.rglru_scan`` in XLA, which has ``expm1``; the kernel is
the recurrence alone.

Layouts: a, b: [B, S, W] f32;  h: [B, S, W];  h_last: [B, 1, W] (the
unit middle axis keeps its block's last two dims Mosaic-legal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h_ref, hl_ref, state_sc, *, nt):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    a = a_ref[0].astype(jnp.float32)                  # [bt, bw]
    b = b_ref[0].astype(jnp.float32)
    # fold the carried state into step 0
    row = jax.lax.broadcasted_iota(jnp.int32, b.shape, 0)
    b = b + jnp.where(row == 0, a * state_sc[...], 0.0)

    # Hillis-Steele scan over time: after the pass with offset d, row t
    # holds the composition of steps (t-2d, t]; rows < d have nothing
    # before them and keep their value (a := 1, b := 0 for the shifted-in
    # part).  Rolls move data between sublanes without strided slices.
    d = 1
    while d < a.shape[0]:
        head = row < d
        a_prev = jnp.where(head, 1.0, pltpu.roll(a, d, 0))
        b_prev = jnp.where(head, 0.0, pltpu.roll(b, d, 0))
        b = b_prev * a + b
        a = a_prev * a
        d *= 2
    h = b
    h_ref[0] = h.astype(h_ref.dtype)
    state_sc[...] = h[-1:]

    @pl.when(t == nt - 1)
    def _write_last():
        hl_ref[0] = state_sc[...].astype(hl_ref.dtype)


def rglru_scan_pallas(a, b, *, block_t=256, block_w=128, interpret=False):
    """a, b: [B, S, W] -> (h [B, S, W], h_last [B, 1, W])."""
    B, S, W = b.shape
    bt, bw = min(block_t, S), min(block_w, W)
    while S % bt:
        bt //= 2
    while W % bw:
        bw //= 2
    nt, nw = S // bt, W // bw
    kernel = functools.partial(_kernel, nt=nt)
    return pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, bt, bw), lambda b, w, t: (b, t, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bw), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, 1, bw), lambda b, w, t: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
