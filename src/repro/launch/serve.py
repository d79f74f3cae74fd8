"""Serving launcher: continuous-batching engine over a model with random
weights made from ``--seed`` (nothing is downloaded).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \\
        --requests 16 --slots 8 --cache-len 2048 --prompt-lens 128,256,512

Parameters and the KV cache are bfloat16 at the config's full width;
``--reduced`` swaps in the scaled-down config of the same family.  After
the run it prints the engine's own record (``serve/trace.py``): ticks, host
syncs per tick, the queue wait's 95th percentile and each span's self time.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, scaled_down
from repro.launch import compile_cache
from repro.models import model as M
from repro.serve.engine import Engine, Request
from repro.serve.trace import Trace


def build_engine(cfg, *, slots, cache_len, seed=0, trace=None):
    """Random bf16 parameters from ``seed`` (made on the device) + an
    engine with a bf16 cache, recording into ``trace`` if one is given."""
    params = jax.jit(lambda k: M.init_params(cfg, k, jnp.bfloat16,
                                             max_seq=cache_len))(
        jax.random.PRNGKey(seed))
    return Engine(cfg, params, batch_slots=slots, cache_len=cache_len,
                  dtype=jnp.bfloat16, trace=trace)


def make_requests(cfg, n, prompt_lens, max_new, seed=0, uid0=0):
    """``n`` requests cycling through ``prompt_lens``, tokens from ``seed``."""
    key = jax.random.PRNGKey(seed + 1)
    reqs = []
    for i in range(n):
        plen = prompt_lens[i % len(prompt_lens)]
        prompt = jax.random.randint(jax.random.fold_in(key, uid0 + i),
                                    (plen,), 0, cfg.vocab, jnp.int32)
        reqs.append(Request(uid=uid0 + i, prompt=prompt,
                            max_new_tokens=max_new))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="scaled-down config of the same family")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=2048)
    ap.add_argument("--prompt-lens", default="128,256,384,512")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = scaled_down(cfg)
    lens = [int(x) for x in args.prompt_lens.split(",")]
    trace = Trace()
    eng = build_engine(cfg, slots=args.slots, cache_len=args.cache_len,
                       seed=args.seed, trace=trace)
    for req in make_requests(cfg, args.requests, lens, args.max_new,
                             args.seed):
        eng.submit(req)
    t0 = time.time()
    fins = eng.run_to_completion()
    dt = time.time() - t0
    toks = sum(len(f.tokens) for f in fins)
    print(f"served {len(fins)} requests, {toks} tokens in {dt:.2f}s "
          f"(compiles included; {args.slots} slots, "
          f"{jax.devices()[0].device_kind})")
    print(trace.summary_line())
    for f in sorted(fins, key=lambda f: f.uid)[:4]:
        print(f"  req {f.uid}: {f.tokens}")
    return fins


if __name__ == "__main__":
    main()
