"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Three implementations:

* ``dense`` — every expert computes every token, masked combine.  Exact
  (dropless) oracle; only viable for tiny smoke/test configs.
* ``ep``    — shard_map expert parallelism: experts sharded over the mesh
  ``model`` axis, activations replicated across it (tokens stay sharded over
  the batch axes).  Each model-peer packs the tokens routed to *its* experts
  into fixed-capacity buffers, computes them, and the outputs combine with a
  single ``psum('model')``.  No all-to-all — the TPU analogue of the paper's
  "keep communication inside the fast domain" rule for network-bound work.
* ``ep_a2a`` — experts sharded over the *batch* axes (pod, data) with the
  expert FFN dim sharded over ``model`` (TP-inside-expert).  Used when the
  expert weights exceed per-chip HBM under pure-EP (kimi-k2 1T): tokens move
  to expert owners with ``all_to_all`` over the batch axes, partial
  down-projections reduce with ``psum('model')``.  DeepSeek-style
  EP-across-nodes + TP-within-node.

Capacity: fixed buffers sized ``ceil(tokens·top_k/E)·capacity_factor`` —
tokens over capacity are dropped (GShard semantics); drop rates are asserted
small in tests.  Packing scatters *indices* first and gathers payloads
directly into buffer layout, so the [T·k, D] expanded tensor never exists.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.sharding import Rules


def moe_params(key, cfg, dtype):
    m = cfg.moe
    D, E, F = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 4)
    return {
        "router": L.dense_init(ks[0], (D, E), jnp.float32),  # router in f32
        "w_gate": L.dense_init(ks[1], (E, D, F), dtype, fan_in=D),
        "w_up": L.dense_init(ks[2], (E, D, F), dtype, fan_in=D),
        "w_down": L.dense_init(ks[3], (E, F, D), dtype, fan_in=F),
    }


def moe_axes(cfg):
    return {
        "router": ("embed", None),
        "w_gate": ("expert", "embed", "expert_ffn"),
        "w_up": ("expert", "embed", "expert_ffn"),
        "w_down": ("expert", "expert_ffn", "embed"),
    }


def _route(router_w, x, top_k):
    """x: [T, D] -> (weights [T,k], ids [T,k], aux dict)."""
    logits = x.astype(jnp.float32) @ router_w                   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    E = router_w.shape[1]
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_ids[:, 0]].add(1.0) / x.shape[0]
    lb = E * jnp.sum(me * ce)                                   # load balance
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))  # z-loss
    return top_w, top_ids, {"load_balance": lb, "router_z": z}


def _capacity(tokens, top_k, n_groups, factor):
    c = int(tokens * top_k / n_groups * factor) + 1
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


def _pack(ids, wts, src, n_groups, first, capacity, payload_ids=None):
    """Assign each (choice) row to a (group, slot) buffer position.

    ids/wts/src: flat [N] (expert-or-destination id, routing weight, source
    token row).  Returns rbuf [G, C] of source rows (-1 empty), wbuf [G, C],
    plus ibuf [G, C] carrying ``payload_ids`` (default: ids) — used by the
    two-stage a2a path to ship true expert ids alongside the tokens.
    """
    payload_ids = ids if payload_ids is None else payload_ids
    local = ids - first
    is_local = (local >= 0) & (local < n_groups)
    key = jnp.where(is_local, local, n_groups)                  # sentinel grp
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    onehot = jax.nn.one_hot(key_s, n_groups + 1, dtype=jnp.int32)
    slot = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                               key_s[:, None], axis=1)[:, 0]
    keep = (key_s < n_groups) & (slot < capacity)
    g_w = jnp.where(keep, key_s, n_groups)
    s_w = jnp.where(keep, slot, 0)
    rbuf = jnp.full((n_groups + 1, capacity), -1, jnp.int32)
    rbuf = rbuf.at[g_w, s_w].set(jnp.where(keep, src[order], -1))
    wbuf = jnp.zeros((n_groups + 1, capacity), jnp.float32)
    wbuf = wbuf.at[g_w, s_w].set(jnp.where(keep, wts[order], 0.0))
    ibuf = jnp.full((n_groups + 1, capacity), -1, jnp.int32)
    ibuf = ibuf.at[g_w, s_w].set(jnp.where(keep, payload_ids[order], -1))
    return rbuf[:n_groups], wbuf[:n_groups], ibuf[:n_groups]


def _gather_rows(x, rbuf):
    """x [T, D]; rbuf [G, C] -> [G, C, D] with zeros at empty slots."""
    safe = jnp.maximum(rbuf, 0)
    out = x[safe]
    return jnp.where((rbuf >= 0)[..., None], out, 0).astype(x.dtype)


def _expert_ffn(buf, wg, wu, wd):
    """buf [El, C, D]; stacked expert weights -> [El, C, D]."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
        * jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _combine(y_buf, rbuf, wbuf, T, dtype):
    """Scatter-add weighted expert outputs back to token rows -> [T, D]."""
    G, C, D = y_buf.shape
    flat_y = y_buf.reshape(G * C, D).astype(jnp.float32)
    flat_r = rbuf.reshape(G * C)
    flat_w = wbuf.reshape(G * C)
    safe_r = jnp.where(flat_r >= 0, flat_r, T)                  # sentinel row
    out = jnp.zeros((T + 1, D), jnp.float32)
    out = out.at[safe_r].add(flat_y * flat_w[:, None])
    return out[:T].astype(dtype)


def _flat_choices(top_w, top_ids):
    T, k = top_ids.shape
    src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    return top_ids.reshape(-1), top_w.reshape(-1), src


# --------------------------------------------------------------------------
# dense oracle
# --------------------------------------------------------------------------
def apply_dense(params, x, cfg):
    """Exact dropless MoE; O(E) compute — tests/smoke only. x: [B,S,D]."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    top_w, top_ids, aux = _route(params["router"], xt, cfg.moe.top_k)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, params["w_gate"])) \
        * jnp.einsum("td,edf->tef", xt, params["w_up"])
    y_all = jnp.einsum("tef,efd->ted", h, params["w_down"])     # [T, E, D]
    comb = jnp.zeros((xt.shape[0], cfg.moe.n_experts), jnp.float32).at[
        jnp.arange(xt.shape[0])[:, None], top_ids].add(top_w)
    y = jnp.einsum("ted,te->td", y_all.astype(jnp.float32), comb)
    return y.reshape(B, S, D).astype(x.dtype), aux


# --------------------------------------------------------------------------
# shard_map expert parallelism
# --------------------------------------------------------------------------
def _hier_gather(w, fsdp_axes, axis):
    """ZeRO-3 just-in-time weight gather, one hop per mesh axis so the
    fast-domain (ICI) part never pays DCN rates — the paper's 'keep traffic
    in the smallest domain' rule applied to parameter gathers."""
    for a in reversed(fsdp_axes):
        w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
    return w


def _ep_local(x_loc, router, wg, wu, wd, *, cfg, expert_axis, batch_axes,
              fsdp_axes=None):
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    n_shards = jax.lax.axis_size(expert_axis)
    n_local = E // n_shards
    me = jax.lax.axis_index(expert_axis)
    if fsdp_axes:
        wg = _hier_gather(wg, fsdp_axes, 1)
        wu = _hier_gather(wu, fsdp_axes, 1)
        wd = _hier_gather(wd, fsdp_axes, 2)
    top_w, top_ids, aux = _route(router, x_loc, k)
    ids, wts, src = _flat_choices(top_w, top_ids)
    cap = _capacity(x_loc.shape[0], k, E, cfg.moe.capacity_factor)
    rbuf, wbuf, _ = _pack(ids, wts, src, n_local, me * n_local, cap)
    buf = _gather_rows(x_loc, rbuf)
    y_buf = _expert_ffn(buf, wg, wu, wd)
    y = _combine(y_buf, rbuf, wbuf, x_loc.shape[0], x_loc.dtype)
    y = jax.lax.psum(y, expert_axis)
    # aux scalars vary over the batch axes only (x is replicated over the
    # expert axis), so the mean is taken there
    aux = {n: jax.lax.pmean(v, batch_axes) for n, v in aux.items()}
    return y, aux


def _ep_a2a_local(x_loc, router, wg, wu, wd, *, cfg, expert_axis,
                  batch_axes, fsdp_axes=None):
    """Tokens sharded over (…, expert_axis); experts owned by expert_axis
    peers.  Dispatch/return via all_to_all over the expert axis only — the
    DeepSeek-style EP used when activations are sharded too finely for the
    replicated-activation psum path."""
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    n_owner = jax.lax.axis_size(expert_axis)
    n_local = E // n_owner
    me = jax.lax.axis_index(expert_axis)
    Tl, D = x_loc.shape
    if fsdp_axes:
        wg = _hier_gather(wg, fsdp_axes, 1)
        wu = _hier_gather(wu, fsdp_axes, 1)
        wd = _hier_gather(wd, fsdp_axes, 2)
    top_w, top_ids, aux = _route(router, x_loc, k)
    ids, wts, src = _flat_choices(top_w, top_ids)
    # stage 1: pack per destination owner (dest = expert // n_local),
    # shipping the true expert id in ibuf for stage-2 routing
    cap = _capacity(Tl, k, n_owner, cfg.moe.capacity_factor)
    rbuf, wbuf, ebuf = _pack(ids // n_local, wts, src, n_owner, 0, cap,
                             payload_ids=ids)
    sbuf = _gather_rows(x_loc, rbuf)                            # [O, cap, D]
    a2a = functools.partial(jax.lax.all_to_all, axis_name=expert_axis,
                            split_axis=0, concat_axis=0, tiled=True)
    rx = a2a(sbuf).reshape(-1, D)                               # [O*cap, D]
    re = a2a(ebuf.astype(jnp.float32)).astype(jnp.int32).reshape(-1)
    # stage 2: pack received rows per local expert
    R = rx.shape[0]
    cap2 = _capacity(R, 1, n_local, cfg.moe.capacity_factor)
    lr, lw, _ = _pack(re, jnp.ones((R,), jnp.float32),
                      jnp.arange(R, dtype=jnp.int32), n_local,
                      me * n_local, cap2)
    lbuf = _gather_rows(rx, lr)
    y_buf = _expert_ffn(lbuf, wg, wu, wd)
    y_rows = _combine(y_buf, lr, lw, R, x_loc.dtype)
    back = a2a(y_rows.reshape(n_owner, cap, D))                 # return trip
    y = _combine(back, rbuf, wbuf, Tl, x_loc.dtype)
    axes = tuple(batch_axes)
    if expert_axis not in axes:
        axes = axes + (expert_axis,)
    aux = {n: jax.lax.pmean(v, axes) for n, v in aux.items()}
    return y, aux


def apply_ep(params, x, cfg, rules: Rules, mesh, impl="ep"):
    """Expert-parallel MoE under shard_map.  x: [B,S,D] (sharded on batch)."""
    B, S, D = x.shape
    batch_ax = rules.batch if isinstance(rules.batch, tuple) \
        else ((rules.batch,) if rules.batch else ())
    seq_ax = rules.seq if isinstance(rules.seq, tuple) \
        else ((rules.seq,) if rules.seq else ())
    batch_ax = tuple(batch_ax) + tuple(seq_ax)   # token sharding axes
    xt = x.reshape(B * S, D)

    if impl == "ep":
        expert_axis = rules.expert
        assert isinstance(expert_axis, str), "ep needs a single expert axis"
        fsdp = rules.fsdp
        fsdp = (fsdp,) if isinstance(fsdp, str) else fsdp
        fn = functools.partial(_ep_local, cfg=cfg, expert_axis=expert_axis,
                               batch_axes=tuple(batch_ax),
                               fsdp_axes=tuple(fsdp) if fsdp else None)
        wspec = (P(expert_axis, fsdp, None) if fsdp
                 else P(expert_axis, None, None))
        wdspec = (P(expert_axis, None, fsdp) if fsdp
                  else P(expert_axis, None, None))
        in_specs = (P(batch_ax, None), P(None, None), wspec, wspec, wdspec)
    else:  # ep_a2a: tokens sharded over batch axes incl. the expert axis
        expert_axis = rules.expert
        assert isinstance(expert_axis, str), "ep_a2a needs one expert axis"
        fsdp = rules.fsdp
        fsdp = (fsdp,) if isinstance(fsdp, str) else fsdp
        fn = functools.partial(_ep_a2a_local, cfg=cfg,
                               expert_axis=expert_axis,
                               batch_axes=tuple(batch_ax),
                               fsdp_axes=tuple(fsdp) if fsdp else None)
        wspec = (P(expert_axis, fsdp, None) if fsdp
                 else P(expert_axis, None, None))
        wdspec = (P(expert_axis, None, fsdp) if fsdp
                  else P(expert_axis, None, None))
        in_specs = (P(batch_ax, None), P(None, None), wspec, wspec, wdspec)

    y, aux = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs,
        out_specs=(P(batch_ax, None), P()), check_vma=False)(
        xt, params["router"], params["w_gate"], params["w_up"],
        params["w_down"])
    return y.reshape(B, S, D), aux


def apply(params, x, cfg, rules: Optional[Rules], mesh=None, impl="dense"):
    if impl == "dense" or mesh is None or rules is None \
            or rules.expert is None:
        return apply_dense(params, x, cfg)
    return apply_ep(params, x, cfg, rules, mesh, impl=impl)
