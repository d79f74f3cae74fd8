"""Training launcher: plan -> mesh -> data -> train loop -> checkpoints.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \\
        --steps 100 --batch 8 --seq 512 --ckpt-dir ckpt

The planner (core.meshplan) supplies the layout rules, optimizer, remat and
loss chunking; the state is built directly in its sharded layout on a mesh
over the given chips (all local chips by default), donated to every step,
and checkpointed for restart/elastic resume.  ``--reduced`` swaps in the
scaled-down config of the same family (CPU-sized; the tests use those).
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as CK
from repro.configs import ShapeSpec, get_config, scaled_down
from repro.core.meshplan import plan_job
from repro.data import DataConfig, SyntheticLM
from repro.launch import compile_cache
from repro.launch import mesh as MX
from repro.models import model as M
from repro.optim import get_optimizer
from repro.optim.schedule import warmup_cosine
from repro.train.trainer import (TrainState, init_state, make_train_step,
                                 train_loop)


@dataclasses.dataclass
class TrainJob:
    """A planned train job laid out on a mesh: what ``train`` runs."""
    plan: Any
    mesh: Any
    state_shardings: Any
    input_shardings: Dict[str, Any]
    init: Callable          # key -> state tree, already sharded
    step: Callable          # (tree, tokens, labels, extras) -> (tree, mets)


def build(cfg, *, steps, batch, seq, lr=3e-4, devices=None, optimizer=None,
          compress=None) -> TrainJob:
    """Plan the job for ``devices`` (default: all local chips), lay it out
    on a host mesh, and build its sharded init and step functions."""
    devices = list(devices or jax.local_devices())
    shape = ShapeSpec("cli", "train", seq, batch)
    plan = plan_job(cfg, shape, n_chips=len(devices))
    mesh = MX.make_host_mesh(devices)
    rules = MX.effective_rules(plan.rules, mesh)
    opt_name = optimizer or plan.optimizer
    opt = get_optimizer(opt_name, warmup_cosine(lr, min(20, steps // 4),
                                                steps))
    ctx = M.Ctx(rules=rules, mesh=mesh, moe_impl=plan.moe_impl,
                remat=plan.remat, ce_chunk=plan.ce_chunk)

    def init_tree(key):
        return init_state(cfg, key, opt, max_seq=seq,
                          compress=compress).tree()

    state_sh = MX.train_state_shardings(
        mesh, rules, cfg, opt_name,
        jax.eval_shape(init_tree, jax.random.PRNGKey(0)))
    step_fn = make_train_step(cfg, ctx, opt, compress=compress)

    def sharded_step(tree, tokens, labels, extras):
        # pin the new state to the state's layout, so the donated input
        # buffers are reused and the next step sees the same shardings
        new, metrics = step_fn(tree, tokens, labels, extras)
        return jax.lax.with_sharding_constraint(new, state_sh), metrics

    return TrainJob(plan=plan, mesh=mesh,
                    state_shardings=state_sh,
                    input_shardings=MX.input_shardings(cfg, shape, mesh,
                                                       rules),
                    init=jax.jit(init_tree, out_shardings=state_sh),
                    step=sharded_step)


def train(cfg, *, steps, batch, seq, seed=0, lr=3e-4, devices=None,
          optimizer=None, compress=None, ckpt_dir=None, ckpt_every=50,
          resume=False, log_every=10, log_fn=print):
    """Build the job and run ``steps`` steps of it on synthetic data made
    from ``seed``.  Returns (job, final state tree, last metrics)."""
    job = build(cfg, steps=steps, batch=batch, seq=seq, lr=lr,
                devices=devices, optimizer=optimizer, compress=compress)
    # built in place, already sharded: no single-device copy of the state
    tree = job.init(jax.random.PRNGKey(seed))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=batch, seed=seed))
    start = 0
    if resume and ckpt_dir and CK.latest_step(ckpt_dir):
        tree = CK.restore(ckpt_dir, tree)
        start = int(tree["step"])
        data.state.step = start
        log_fn(f"resumed from step {start}")

    in_sh = job.input_shardings
    extras = {}
    if cfg.n_media_tokens:
        extras["media"] = jax.device_put(
            jnp.zeros((batch, cfg.n_media_tokens, cfg.d_model)),
            in_sh["media"])
    if cfg.encoder is not None:
        extras["frames"] = jax.device_put(
            jnp.zeros((batch, cfg.encoder.n_ctx, cfg.encoder.d_model)),
            in_sh["frames"])

    def batches():
        for tokens, labels in data:
            yield (jax.device_put(tokens, in_sh["tokens"]),
                   jax.device_put(labels, in_sh["labels"]))

    state = TrainState(params=tree["params"], opt_state=tree["opt_state"],
                       step=tree["step"], err_state=tree.get("err_state"))
    with jax.set_mesh(job.mesh):
        tree, metrics = train_loop(
            cfg, state, job.step, batches(), steps - start,
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, extras=extras,
            log_every=log_every, log_fn=log_fn)
    return job, tree, metrics


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="scaled-down config of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default=None)
    ap.add_argument("--compress", default=None, choices=[None, "int8",
                                                         "topk"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = scaled_down(cfg)
    job, tree, metrics = train(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq,
        seed=args.seed, lr=args.lr, optimizer=args.optimizer,
        compress=args.compress, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, resume=args.resume)
    print(f"done: step={int(tree['step'])} "
          f"loss={float(metrics['loss']):.4f} "
          f"(plan: {job.plan.notes or 'tp'})")
    return tree


if __name__ == "__main__":
    main()
