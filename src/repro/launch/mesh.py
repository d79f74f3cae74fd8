"""Host mesh + sharding assembly for the launchers."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig, ShapeSpec
from repro.models import model as M
from repro.models.sharding import Rules


def make_host_mesh(devices):
    """("data", "model") mesh over one host's chips.  A host is inside one
    ICI domain, so tensor parallelism takes up to 16 of its chips and data
    parallelism the rest."""
    n = len(devices)
    model = min(n, 16)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def effective_rules(rules: Rules, mesh) -> Rules:
    """Drop mesh axes that don't exist (single-pod mesh has no 'pod')."""
    have = set(mesh.axis_names)

    def fix(ax):
        if ax is None:
            return None
        tup = (ax,) if isinstance(ax, str) else tuple(ax)
        kept = tuple(a for a in tup if a in have)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    return Rules(**{f.name: fix(getattr(rules, f.name))
                    for f in dataclasses.fields(rules)})


def _axis_size(mesh, ax) -> int:
    if ax is None:
        return 1
    tup = (ax,) if isinstance(ax, str) else ax
    n = 1
    for a in tup:
        n *= dict(mesh.shape)[a]
    return n


def _shard_leaf(mesh, rules: Rules, names, shape):
    """NamedSharding with divisibility demotion + optional FSDP overlay."""
    used = set()
    axes = []
    for n, d in zip(names, shape):
        ax = getattr(rules, n) if n is not None else None
        if ax is not None:
            tup = (ax,) if isinstance(ax, str) else tuple(ax)
            tup = tuple(a for a in tup if a not in used)
            size = 1
            for a in tup:
                size *= dict(mesh.shape)[a]
            if not tup or size == 0 or d % size != 0:
                ax = None
            else:
                used.update(tup)
                ax = tup if len(tup) > 1 else tup[0]
        axes.append(ax)
    # FSDP overlay: shard the largest still-unsharded dim over rules.fsdp
    if rules.fsdp is not None:
        ftup = (rules.fsdp,) if isinstance(rules.fsdp, str) \
            else tuple(rules.fsdp)
        ftup = tuple(a for a in ftup if a not in used)
        fsize = 1
        for a in ftup:
            fsize *= dict(mesh.shape)[a]
        if ftup and fsize > 1:
            cands = [i for i, ax in enumerate(axes)
                     if ax is None and shape[i] % fsize == 0
                     and shape[i] >= fsize]
            if cands:
                i = max(cands, key=lambda i: shape[i])
                axes[i] = ftup if len(ftup) > 1 else ftup[0]
    return NamedSharding(mesh, P(*axes))


def tree_shardings(mesh, rules: Rules, tree_struct, axes_tree):
    """Map a tree of ShapeDtypeStructs + logical-axes tree -> shardings."""
    rules = effective_rules(rules, mesh)
    flat_s, treedef = jax.tree.flatten(tree_struct)
    flat_a = treedef.flatten_up_to(axes_tree)
    out = []
    for s, a in zip(flat_s, flat_a):
        if a is None or len(a) != len(s.shape):
            out.append(NamedSharding(mesh, P()))
        else:
            out.append(_shard_leaf(mesh, rules, a, s.shape))
    return treedef.unflatten(out)


def scalar_sharding(mesh):
    return NamedSharding(mesh, P())


# --------------------------------------------------------------------------
# optimizer-state logical axes (mirrors optimizers.py structures)
# --------------------------------------------------------------------------
def opt_state_axes(optimizer_name: str, params_struct, param_axes):
    if optimizer_name == "adamw":
        return {"m": param_axes, "v": param_axes}
    # adafactor: factored leaves for >=2D params
    def st_axes(s, a):
        if a is not None and len(s.shape) >= 2 and len(a) == len(s.shape):
            return {"row": tuple(a[:-1]), "col": tuple(a[:-2]) + (a[-1],)}
        return {"v": a}

    flat_s, treedef = jax.tree.flatten(params_struct)
    flat_a = treedef.flatten_up_to(param_axes)
    return treedef.unflatten([st_axes(s, a)
                              for s, a in zip(flat_s, flat_a)])


def train_state_shardings(mesh, rules: Rules, cfg: ArchConfig,
                          optimizer_name: str, state_struct):
    """Shardings for a train-state tree (``TrainState.tree()`` layout):
    params by their logical axes, optimizer state likewise (over
    ``rules.opt_fsdp`` for ZeRO-1), the step counter replicated."""
    rules = effective_rules(rules, mesh)
    axes = M.param_axes(cfg)
    params = state_struct["params"]
    pshard = tree_shardings(mesh, rules, params, axes)
    orules = rules if rules.opt_fsdp is None else \
        dataclasses.replace(rules, fsdp=rules.opt_fsdp)
    out = {"params": pshard,
           "opt_state": tree_shardings(
               mesh, orules, state_struct["opt_state"],
               opt_state_axes(optimizer_name, params, axes)),
           "step": scalar_sharding(mesh)}
    if "err_state" in state_struct:
        out["err_state"] = pshard
    return out


def input_shardings(cfg: ArchConfig, shape: ShapeSpec, mesh,
                    rules: Rules) -> Dict[str, Any]:
    """Shardings of a train step's inputs: tokens and labels over batch
    (and seq, where the rules split it), media and frames over batch."""
    rules = effective_rules(rules, mesh)
    batch_ax = rules.batch
    bsize = _axis_size(mesh, batch_ax)
    if shape.global_batch % max(bsize, 1) != 0 or bsize <= 1:
        batch_ax = None
    seq_ax = rules.seq
    if seq_ax is not None and shape.seq_len % max(_axis_size(mesh, seq_ax),
                                                  1) != 0:
        seq_ax = None
    out: Dict[str, Any] = {"tokens": NamedSharding(mesh, P(batch_ax, seq_ax)),
                           "labels": NamedSharding(mesh, P(batch_ax, seq_ax))}
    if cfg.n_media_tokens:
        out["media"] = NamedSharding(mesh, P(batch_ax, None, None))
    if cfg.encoder is not None:
        out["frames"] = NamedSharding(mesh, P(batch_ax, None, None))
    return out
