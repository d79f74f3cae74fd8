import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first backend initialization).  Placeholder host devices exist so
# jax.make_mesh can build the production meshes; nothing is allocated — the
# dry-run lowers and compiles against ShapeDtypeStructs only.

"""Multi-pod dry-run: lower + compile every (arch × shape) cell.

For each cell and mesh ((16,16) single-pod / (2,16,16) multi-pod) this:
  1. asks the planner (core.meshplan) for the job's layout plan,
  2. builds the step function (train_step / prefill_step / serve_step),
  3. ``jit(...).lower(**ShapeDtypeStructs).compile()``,
  4. prints memory_analysis() (proves it fits) and cost_analysis(),
  5. parses the partitioned HLO into roofline terms (repro.roofline),
  6. appends the record to results/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_configs, \
    shape_skip_reason
from repro.core.meshplan import plan_job
from repro.launch import mesh as MX
from repro.models import model as M
from repro.optim import get_optimizer
from repro.optim.schedule import warmup_cosine
from repro.roofline import analysis as RA
from repro.roofline import hlo_cost


def _mesh_dict(mesh):
    return {k: int(v) for k, v in mesh.shape.items()}


def build_cell(cfg, shape, mesh, plan, ctx_overrides=None):
    """Returns (fn, arg_structs tuple, in_shardings tuple)."""
    rules = plan.rules
    over = dict(ctx_overrides or {})
    rules = over.pop("rules", rules)
    rules = MX.effective_rules(rules, mesh)
    accum_override = over.pop("accum", None)
    from repro.models import moe as _moe
    _moe.GATHER_QUANT = over.pop("moe_gather_quant", False)
    ctx = M.Ctx(rules=rules, mesh=mesh,
                attn_impl=over.pop("attn_impl", "xla_rect"),
                rnn_impl=over.pop("rnn_impl", "xla"),
                moe_impl=over.pop("moe_impl", plan.moe_impl),
                remat=over.pop("remat", plan.remat),
                ce_chunk=over.pop("ce_chunk", plan.ce_chunk))
    assert not over, f"unknown overrides {over}"
    dtype = jnp.bfloat16
    params_struct = jax.eval_shape(
        lambda k: M.init_params(cfg, k, dtype, max_seq=shape.seq_len),
        jax.random.PRNGKey(0))
    axes = M.param_axes(cfg)
    pshard = MX.tree_shardings(mesh, rules, params_struct, axes)
    specs = MX.input_specs(cfg, shape)
    ishard = MX.input_shardings(cfg, shape, mesh, rules)

    if shape.kind == "train":
        opt = get_optimizer(plan.optimizer, warmup_cosine(3e-4, 100, 10000))
        state_struct = {"params": params_struct,
                        "opt_state": jax.eval_shape(opt.init, params_struct),
                        "step": jax.ShapeDtypeStruct((), jnp.int32)}
        state_shard = MX.train_state_shardings(mesh, rules, cfg,
                                               plan.optimizer, state_struct)
        extras_keys = [k for k in ("media", "frames") if k in specs]

        A = accum_override if accum_override is not None \
            else plan.accum_steps
        batch_sh = ishard["tokens"].spec[0]

        def train_step(state, tokens, labels, *extras):
            kw = dict(zip(extras_keys, extras))
            params = state["params"]

            def loss_fn(p, tok, lab):
                return M.lm_loss(cfg, p, tok, lab, ctx, **kw)

            if A > 1:
                B, S = tokens.shape

                def micro_split(a):
                    r = a.reshape((A, B // A) + a.shape[1:])
                    spec = jax.sharding.PartitionSpec(
                        None, batch_sh, *([None] * (a.ndim - 1)))
                    return jax.lax.with_sharding_constraint(r, spec)

                def micro(acc, inp):
                    tok, lab = inp[0], inp[1]
                    mkw = dict(zip(extras_keys, inp[2:]))

                    def lf(p):
                        return M.lm_loss(cfg, p, tok, lab, ctx, **mkw)

                    (l, _), g = jax.value_and_grad(lf, has_aux=True)(params)
                    return (jax.tree.map(jnp.add, acc[0], g), acc[1] + l), 0

                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                                     params)
                (grads, lsum), _ = jax.lax.scan(
                    micro, (zeros, jnp.zeros(())),
                    (micro_split(tokens), micro_split(labels),
                     *[micro_split(kw[k]) for k in extras_keys]))
                grads = jax.tree.map(lambda g: g / A, grads)
                loss = lsum / A
            else:
                (loss, _), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, tokens, labels)
            new_p, new_o, om = opt.update(grads, state["opt_state"],
                                          params, state["step"])
            return ({"params": new_p, "opt_state": new_o,
                     "step": state["step"] + 1},
                    {"loss": loss, **om})

        args = (state_struct, specs["tokens"], specs["labels"],
                *[specs[k] for k in extras_keys])
        shards = (state_shard, ishard["tokens"], ishard["labels"],
                  *[ishard[k] for k in extras_keys])
        return train_step, args, shards

    if shape.kind == "prefill":
        extras_keys = [k for k in ("media", "frames") if k in specs]

        def prefill_step(params, tokens, *extras):
            kw = dict(zip(extras_keys, extras))
            return M.prefill(cfg, params, tokens, shape.seq_len, ctx, **kw)

        args = (params_struct, specs["tokens"],
                *[specs[k] for k in extras_keys])
        shards = (pshard, ishard["tokens"],
                  *[ishard[k] for k in extras_keys])
        return prefill_step, args, shards

    # decode: serve_step = one token against a seq_len cache
    def serve_step(params, tokens, state):
        return M.decode_step(cfg, params, tokens, state, ctx)

    args = (params_struct, specs["tokens"], specs["state"])
    shards = (pshard, ishard["tokens"], ishard["state"])
    return serve_step, args, shards


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             ctx_overrides=None, variant: str = "baseline",
             verbose: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant, "ok": False}
    skip = shape_skip_reason(cfg, shape)
    if skip:
        rec.update(skipped=True, reason=skip, ok=True)
        return rec
    t0 = time.time()
    try:
        mesh = MX.make_production_mesh(multi_pod=multi_pod)
        plan = plan_job(cfg, shape, n_chips=512 if multi_pod else 256,
                        optimized=(variant == "planner_opt"))
        fn, args, shards = build_cell(cfg, shape, mesh, plan, ctx_overrides)
        # donate the mutable state (train state / decode caches) so outputs
        # alias inputs — the steady-state HBM picture, not double-buffered
        donate = (0,) if shape.kind == "train" else \
            ((2,) if shape.kind == "decode" else ())
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=shards,
                              donate_argnums=donate).lower(*args)
            t_low = time.time() - t0
            compiled = lowered.compile()
            t_comp = time.time() - t0 - t_low
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            hlo = compiled.as_text()
        costs = hlo_cost.analyze(hlo, _mesh_dict(mesh))
        n_chips = 512 if multi_pod else 256
        arg_bytes = int(getattr(ma, "argument_size_in_bytes", 0))
        temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
        out_bytes = int(getattr(ma, "output_size_in_bytes", 0))
        rl = RA.build(cfg, shape, mesh_name, n_chips, costs, arg_bytes,
                      notes=plan.notes)
        rec.update(
            ok=True, plan=dataclasses.asdict(plan) | {
                "rules": {f.name: getattr(plan.rules, f.name)
                          for f in dataclasses.fields(plan.rules)}},
            lower_s=round(t_low, 1), compile_s=round(t_comp, 1),
            memory_analysis={
                "argument_bytes": arg_bytes, "temp_bytes": temp_bytes,
                "output_bytes": out_bytes,
                "total_per_device": arg_bytes + temp_bytes,
                # CPU backend does not implement buffer donation (alias=0);
                # on TPU the donated state aliases outputs, so steady-state
                # peak = args + (temp - outputs)+.  Report both.
                "fits_16GiB_undonated":
                    (arg_bytes + temp_bytes) < 16 * 2 ** 30,
                "fits_16GiB": (arg_bytes
                               + max(temp_bytes - out_bytes, 0))
                    < 16 * 2 ** 30},
            cost_analysis={k: ca.get(k) for k in ("flops", "bytes accessed")
                           if ca and k in ca},
            roofline=rl.to_dict())
        if verbose:
            print(f"[{arch} x {shape_name} @ {mesh_name}] OK "
                  f"lower {t_low:.1f}s compile {t_comp:.1f}s | "
                  f"args/dev {arg_bytes/2**30:.2f}GiB "
                  f"temp/dev {temp_bytes/2**30:.2f}GiB | "
                  f"terms c/m/n = {rl.compute_s*1e3:.2f}/"
                  f"{rl.memory_s*1e3:.2f}/{rl.collective_s*1e3:.2f} ms "
                  f"-> {rl.dominant} | useful {rl.useful_ratio:.2f} "
                  f"| roofline frac {rl.roofline_fraction:.3f}")
            print("  memory_analysis:", ma)
            if ca:
                print("  cost_analysis flops=%.3e bytes=%.3e" %
                      (ca.get("flops", 0), ca.get("bytes accessed", 0)))
    except Exception as e:  # noqa: BLE001 — record and continue
        rec.update(error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{arch} x {shape_name} @ {mesh_name}] FAIL {e}")
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for name in list_configs():
            for sh in SHAPES:
                cells.append((name, sh))
    else:
        cells.append((args.arch, args.shape))
    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]
    overrides = {}
    if args.attn_impl:
        overrides["attn_impl"] = args.attn_impl
    if args.variant not in ("baseline", "planner_opt"):
        from repro.launch.perf_variants import VARIANTS
        overrides.update(VARIANTS[args.variant])

    for arch, sh in cells:
        for mp in meshes:
            tag = f"{arch}__{sh}__{'mp' if mp else 'sp'}__{args.variant}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_done and os.path.exists(path):
                print("skip (done):", tag)
                continue
            rec = run_cell(arch, sh, mp, overrides or None, args.variant)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
