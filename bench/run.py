"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload qwen2-0.5b.serve-chat --seed 7 \\
        --seconds 30 --trace 0

From the root of a checkout.  ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` its per-layer metrics, read from host spans and a
profiler trace of the window's last seconds.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
JAX's compilation cache is kept at ``.bench_cache/jax`` in the checkout.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _process_start() -> float:
    """``time.perf_counter()``'s reading at this process's start."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        age = 0.0
    return time.perf_counter() - max(0.0, age)


T_PROCESS = _process_start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    # before JAX is imported: the compile cache at a fixed path inside the
    # checkout, holding every program (the small ones too)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_cache"
                                                  / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        line, _ = harness.run_cell(args.workload, args.seed, args.seconds,
                                   bool(args.trace), t_process=T_PROCESS)
    except (harness.NoChip, harness.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
