"""Bring-up run of the planner-placed device path on TPU.

    python chip_smoke.py              # one chip: plan, serve, kernels, train
    python chip_smoke.py --chips 4    # four chips: sharded train vs one chip

Everything runs in this one process through the repo's entry points, at the
full published width of qwen2-0.5b with random weights and data made from
``--seed`` (nothing is downloaded):

* plan     ``core.meshplan.plan_job`` for the train job on the local chips;
* serve    ``launch.serve``'s engine (bf16 weights and cache, 8 slots, 2048
           cache) answers 16 requests; two requests' prefill and first
           decode-step logits are checked against a no-cache
           ``models.model.forward`` of the same tokens;
* kernels  the four Pallas kernels, compiled by Mosaic (never interpreted),
           at real widths against ``kernels/ref.py``, and a full-width
           prefill with the Pallas attention against the XLA one;
* train    ``launch.train`` takes a few donated, planner-laid-out steps;
           the loss must be finite and fall.

Each phase prints one JSON line; times in them are cold bring-up timings of
one run (compiles reported apart), not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``; any failed phase exits non-zero before
it.  Without a TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-0.5b"
SLOTS, CACHE_LEN, MAX_NEW, N_REQUESTS = 8, 2048, 32, 16
PROMPT_LENS = (128, 256, 384, 512)
REF_LEN = 256                 # prompt length of the two reference requests
TRAIN_BATCH, TRAIN_SEQ, TRAIN_LR = 8, 512, 1e-3

# Two bf16 programs computing the same logits round differently (8-bit
# mantissa, ~4e-3 per op), and one early rounding flip carries through 24
# layers: 0 to 1.4e-2 relative L2 measured on a v5e.  A request spliced into
# the wrong cache slot or axis is off by O(1) (the serve line prints the
# distance between two requests' logits as that scale), so 5e-2 separates
# rounding from a fault.
BF16_LOGITS_REL = 5e-2
# bf16 kernel outputs (attention over unit-normal inputs): max abs error.
BF16_KERNEL_ABS = 2e-2
# f32 kernels against f32 references at highest matmul precision: max abs
# error relative to the reference's largest magnitude.
F32_KERNEL_REL = 1e-3
# Sharded vs one-chip train loss, same seed and data: the partial sums
# reduce in another order; relative difference per step.
SHARDED_LOSS_REL = 5e-3


class PhaseFailed(RuntimeError):
    pass


def _report(phase, ok, **fields):
    print(json.dumps({"phase": phase, "ok": bool(ok), **fields},
                     default=str), flush=True)
    if not ok:
        raise PhaseFailed(phase)


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _rel_l2(got, want):
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def _max_abs(got, want):
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def _compile(fn, *args):
    """AOT-compile ``fn`` for ``args``: (compiled, seconds, Mosaic seen).
    The Mosaic call is looked for in the lowered module, which exists even
    when the executable comes from the persistent compile cache."""
    import jax
    t = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    return (compiled, time.perf_counter() - t,
            "tpu_custom_call" in lowered.as_text())


def _timed(fn, *args):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


# ---------------------------------------------------------------------------
def phase_plan(cfg, n_chips):
    from repro.configs import ShapeSpec
    from repro.core.meshplan import plan_job
    plan = plan_job(cfg, ShapeSpec("cli", "train", TRAIN_SEQ, TRAIN_BATCH),
                    n_chips=n_chips)
    _report("plan", True, arch=cfg.name, n_chips=n_chips,
            profile=plan.profile.value, optimizer=plan.optimizer,
            remat=plan.remat, ce_chunk=plan.ce_chunk,
            accum_steps=plan.accum_steps,
            rules={k: v for k, v in vars(plan.rules).items()
                   if v is not None},
            notes=plan.notes)
    return plan


def phase_serve(cfg, seed):
    import jax
    import jax.numpy as jnp
    from repro.launch import serve as SV
    from repro.models import model as M

    t0 = time.perf_counter()
    eng = SV.build_engine(cfg, slots=SLOTS, cache_len=CACHE_LEN, seed=seed)
    jax.block_until_ready(eng.params)

    # reference: two requests in flight together; their prefill logits and
    # first decode-step logits against one no-cache forward each (causal,
    # so tokens after position REF_LEN do not change the compared rows)
    refs = SV.make_requests(cfg, 2, (REF_LEN,), MAX_NEW, seed, uid0=10_000)
    for r in refs:
        eng.submit(r)
    eng.tick()                          # admits both, first decode step
    fwd = jax.jit(lambda p, t: M.forward(cfg, p, t)[0])
    fwd_len = max(PROMPT_LENS)
    checks = []
    for slot, r in enumerate(refs):
        first = eng.slot_out[slot][0]
        pre = eng.prefill(r.prompt)[0][0]
        dec = eng.last_logits[slot]
        seq = jnp.zeros((1, fwd_len), jnp.int32)
        seq = seq.at[0, :REF_LEN].set(r.prompt).at[0, REF_LEN].set(first)
        want = fwd(eng.params, seq)[0]
        checks.append({
            "prefill_rel_l2": _rel_l2(pre, want[REF_LEN - 1]),
            "decode_rel_l2": _rel_l2(dec, want[REF_LEN]),
            "prefill_token_agrees":
                int(jnp.argmax(pre)) == int(jnp.argmax(want[REF_LEN - 1])),
            "decode_token_agrees":
                int(jnp.argmax(dec)) == int(jnp.argmax(want[REF_LEN]))})
    cross = _rel_l2(eng.last_logits[0], eng.last_logits[1])
    eng.run_to_completion()

    # warm-up: one short request per prompt length compiles every prefill
    for r in SV.make_requests(cfg, len(PROMPT_LENS), PROMPT_LENS, 2, seed,
                              uid0=20_000):
        eng.submit(r)
    eng.run_to_completion()
    setup_s = time.perf_counter() - t0

    reqs = SV.make_requests(cfg, N_REQUESTS, PROMPT_LENS, MAX_NEW, seed)
    for r in reqs:
        eng.submit(r)
    t1 = time.perf_counter()
    fins = [f for f in eng.run_to_completion() if f.uid < N_REQUESTS]
    run_s = time.perf_counter() - t1
    n_tok = sum(len(f.tokens) for f in fins)
    answered = (sorted(f.uid for f in fins) == list(range(N_REQUESTS))
                and all(len(f.tokens) == MAX_NEW
                        and all(0 <= t < cfg.padded_vocab for t in f.tokens)
                        for f in fins))
    worst = max(max(c["prefill_rel_l2"], c["decode_rel_l2"]) for c in checks)
    _report("serve", answered and worst <= BF16_LOGITS_REL,
            what="launch.serve engine, run_to_completion",
            shapes={"slots": SLOTS, "cache_len": CACHE_LEN,
                    "prompt_lens": list(PROMPT_LENS),
                    "max_new_tokens": MAX_NEW, "dtype": "bfloat16"},
            requests_answered=len(fins), tokens=n_tok,
            setup_s_compiles_included=setup_s, bringup_run_s=run_s,
            bringup_tokens_per_s=n_tok / run_s,
            peak_bytes_in_use=_peak_bytes(),
            reference={"tol_rel_l2": BF16_LOGITS_REL, "worst_rel_l2": worst,
                       "requests": checks,
                       "between_requests_rel_l2": cross})
    return eng


def _kernel_cases(cfg):
    """(name, kernel fn, reference fn, args, tolerance kind) at real
    widths: qwen2-0.5b attention, recurrentgemma-2b RG-LRU, rwkv6-3b WKV."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape).astype(dtype)

    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    S, B, C = max(PROMPT_LENS), SLOTS, CACHE_LEN
    bf = jnp.bfloat16
    cases = [("flash_attention",
              functools.partial(ops.flash_attention, causal=True),
              functools.partial(ref.attention_ref, causal=True),
              (normal((1, S, H, hd), bf), normal((1, S, K, hd), bf),
               normal((1, S, K, hd), bf)), "bf16")]
    cur = jnp.arange(B, dtype=jnp.int32) * (C // B) + C // (2 * B)
    cpos = jnp.where(jnp.arange(C)[None, :] <= cur[:, None],
                     jnp.arange(C)[None, :], -1).astype(jnp.int32)
    cases.append(("decode_attention",
                  ops.decode_attention,
                  ref.decode_attention_ref,
                  (normal((B, H, hd), bf), normal((B, C, K, hd), bf),
                   normal((B, C, K, hd), bf), cpos, cur), "bf16"))
    W = get_config("recurrentgemma-2b").rnn_width
    log_a = -jnp.abs(normal((2, 1024, W))) * 0.5 - 0.01
    cases.append(("rglru_scan",
                  ops.rglru_scan,
                  ref.rglru_scan_ref, (log_a, normal((2, 1024, W))), "f32"))
    rw = get_config("rwkv6-3b")
    Hr, hdr, Sr = rw.n_heads, rw.d_model // rw.n_heads, 1024
    w = jax.nn.sigmoid(normal((1, Sr, Hr, hdr))) * 0.5 + 0.4

    def wkv_ref(r, k, v, w, u, s0):
        def bh(a):
            return a.transpose(0, 2, 1, 3).reshape(Hr, Sr, hdr)
        y, s = ref.wkv6_ref(bh(r), bh(k), bh(v), bh(w), u, s0[0])
        return y.reshape(1, Hr, Sr, hdr).transpose(0, 2, 1, 3), s[None]

    cases.append(("wkv6", ops.wkv6, wkv_ref,
                  (normal((1, Sr, Hr, hdr)), normal((1, Sr, Hr, hdr)),
                   normal((1, Sr, Hr, hdr)), w, normal((Hr, hdr)),
                   normal((1, Hr, hdr, hdr)) * 0.1), "f32"))
    return cases


def phase_kernels(cfg, eng):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    results, ok = {}, True
    for name, fn, ref_fn, args, kind in _kernel_cases(cfg):
        compiled, compile_s, mosaic = _compile(fn, *args)
        out, run_s = _timed(compiled, *args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        outs = jax.tree.leaves(out)
        wants = jax.tree.leaves(want)
        err = max(_max_abs(o, w) for o, w in zip(outs, wants))
        if kind == "bf16":
            tol = BF16_KERNEL_ABS
        else:
            tol = F32_KERNEL_REL * max(1.0, max(
                float(jnp.max(jnp.abs(w))) for w in wants))
        passed = mosaic and err <= tol
        ok &= passed
        results[name] = {"shapes": [list(a.shape) for a in args],
                         "mosaic_compiled": mosaic, "compile_s": compile_s,
                         "bringup_run_s": run_s, "max_abs_err": err,
                         "tol": tol, "ok": passed}

    # one full-width prefill with the Pallas attention vs the XLA one
    S = max(PROMPT_LENS)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, S), 0, cfg.vocab,
                                jnp.int32)
    ctx = M.Ctx(attn_impl="pallas")
    compiled, compile_s, mosaic = _compile(
        lambda p, t: M.prefill(cfg, p, t, eng.cache_len, ctx)[0],
        eng.params, tokens)
    got, run_s = _timed(compiled, eng.params, tokens)
    rel = _rel_l2(got, eng.prefill(tokens[0])[0])
    passed = mosaic and rel <= BF16_LOGITS_REL
    ok &= passed
    results["prefill_pallas_vs_xla_rect"] = {
        "shapes": {"tokens": [1, S], "cache_len": eng.cache_len},
        "mosaic_compiled": mosaic, "compile_s": compile_s,
        "bringup_run_s": run_s, "rel_l2": rel, "tol": BF16_LOGITS_REL,
        "ok": passed}
    _report("kernels", ok, what="Pallas kernels vs kernels/ref.py",
            peak_bytes_in_use=_peak_bytes(), results=results)


def _train_run(cfg, seed, steps, devices):
    """launch.train on ``devices``; per-step losses and host timestamps."""
    import numpy as np
    from repro.launch import train as TR
    stamps = []
    t0 = time.perf_counter()
    job, tree, metrics = TR.train(
        cfg, steps=steps, batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=seed,
        lr=TRAIN_LR, devices=devices, log_every=1,
        log_fn=lambda _msg: stamps.append(time.perf_counter()))
    losses = np.asarray(metrics["loss_history"], dtype=np.float64)
    times = {"first_step_s_compiles_included": stamps[0] - t0,
             "bringup_step_s": ((stamps[-1] - stamps[0]) / (steps - 1)
                                if steps > 1 else None)}
    return job, tree, losses, times


def phase_train(cfg, seed, *, steps=6):
    import numpy as np
    job, tree, losses, times = _train_run(cfg, seed, steps, None)
    ok = bool(np.all(np.isfinite(losses))) and losses[-1] < losses[0]
    _report("train", ok, what="launch.train, donated state",
            shapes={"batch": TRAIN_BATCH, "seq": TRAIN_SEQ,
                    "mesh": dict(job.mesh.shape), "params_dtype": "float32"},
            plan={"remat": job.plan.remat, "ce_chunk": job.plan.ce_chunk,
                  "optimizer": job.plan.optimizer},
            losses=losses.tolist(), **times,
            peak_bytes_in_use=_peak_bytes())


def _param_bytes_by_device(tree):
    import jax
    out = {}
    for leaf in jax.tree.leaves(tree["params"]):
        for shard in leaf.addressable_shards:
            d = shard.device.id
            out[d] = out.get(d, 0) + shard.data.nbytes
    return out


def phase_sharded_train(cfg, seed, devices, *, steps=4):
    import numpy as np
    _, tree, one, t_one = _train_run(cfg, seed, steps, devices[:1])
    one_bytes = _param_bytes_by_device(tree)
    del tree
    job, tree, many, t_many = _train_run(cfg, seed, steps, devices)
    by_dev = _param_bytes_by_device(tree)
    total = sum(one_bytes.values())
    rel = np.abs(many - one) / np.abs(one)
    spread = (len(by_dev) == len(devices)
              and max(by_dev.values()) < total)
    ok = (bool(np.all(np.isfinite(many))) and spread
          and float(rel.max()) <= SHARDED_LOSS_REL)
    _report("train_sharded", ok,
            what=f"launch.train on {len(devices)} chips vs 1 chip",
            shapes={"batch": TRAIN_BATCH, "seq": TRAIN_SEQ,
                    "mesh": dict(job.mesh.shape)},
            rules={k: v for k, v in vars(job.plan.rules).items()
                   if v is not None},
            losses_one_chip=one.tolist(), losses_sharded=many.tolist(),
            max_rel_diff=float(rel.max()), tol=SHARDED_LOSS_REL,
            param_bytes_one_chip=total, param_bytes_by_device=by_dev,
            one_chip_times=t_one, sharded_times=t_many,
            peak_bytes_in_use=_peak_bytes())


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded train path and its one-chip "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch import compile_cache
    compile_cache.enable()
    cfg = get_config(ARCH)
    try:
        if args.chips == 4:
            phase_sharded_train(cfg, args.seed, devices[:4])
        else:
            phase_plan(cfg, len(devices))
            eng = phase_serve(cfg, args.seed)
            phase_kernels(cfg, eng)
            del eng
            phase_train(cfg, args.seed)
    except PhaseFailed as e:
        print(f"chip_smoke: phase {e} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
