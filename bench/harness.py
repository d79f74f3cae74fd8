"""Finds a cell's files by name, runs its runner, and prints the result line.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name that ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes, dtypes, source, reference module
    bench/traffic/<traffic>.json    the mix: kind, lengths, arrivals, rate
    bench/metrics/<metric>.py       ``read(rec) -> float | None``
    bench/runners/<kind>.py         one runner per traffic kind
    bench/reference/<family>.py     plain float32 reference

A new cell, mix or metric is a new file and a new entry; no file here
changes.  Importing this module touches no accelerator.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(RuntimeError):
    """A name in BENCHMARK.json with no file, or a file that does not fit."""


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _file(kind: str, name: str, suffix: str,
          bench_dir: pathlib.Path = BENCH_DIR) -> pathlib.Path:
    path = bench_dir / kind / f"{name}{suffix}"
    if not path.is_file():
        raise SpecError(f"{kind} {name!r}: no file {path.name} under "
                        f"{bench_dir.name}/{kind}/")
    return path


def load_json(kind: str, name: str,
              bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, Any]:
    return json.loads(_file(kind, name, ".json", bench_dir).read_text())


def load_module(kind: str, name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = _file(kind, name, ".py", bench_dir)
    mod_name = f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(kind: str):
    return importlib.import_module(f"bench.runners.{kind}")


def reference(family: str):
    return importlib.import_module(f"bench.reference.{family}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _reports(metric: Dict[str, Any], cell: str,
             e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a per-layer metric with no list goes wherever its end-to-end metric is
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((bench_dir.parent / cfg_entry["file"]).read_text())
    traffic = load_json("traffic", w["traffic"], bench_dir)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------
class NoChip(RuntimeError):
    pass


def peaks(kind: str, bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, Any]:
    """Published peaks of one chip of ``kind`` (``device_kind``)."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table["chips"]:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table["chips"][kind]


def chips_for(cell: Cell):
    """The cell's chips, or NoChip when JAX finds no TPU or too few."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"cell asks for {cell.chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:cell.chips]


def memory_stats(devices) -> Dict[str, int]:
    """Peak bytes in use on the fullest chip, and that chip's limit."""
    best = {"peak_bytes_in_use": 0, "bytes_limit": 0}
    for d in devices:
        st = d.memory_stats() or {}
        if st.get("peak_bytes_in_use", 0) >= best["peak_bytes_in_use"]:
            best = {"peak_bytes_in_use": int(st.get("peak_bytes_in_use", 0)),
                    "bytes_limit": int(st.get("bytes_limit", 0))}
    return best


# --------------------------------------------------------------------------
# the record a runner fills and the metric readers read
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Record:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    peak: Dict[str, Any]
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    # serve: one dict per request due in the window (see runners/serve.py)
    requests: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    # how full the system under test was: slots, cache positions, queue
    occupancy: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # host spans [(start_s, end_s)] by name, and counters, over the window
    spans: Dict[str, List[tuple]] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    # required FLOPs of the work done in the window, by phase
    flops: Dict[str, float] = dataclasses.field(default_factory=dict)
    memory: Dict[str, int] = dataclasses.field(default_factory=dict)
    trace_summary: Any = None          # tracing.TraceSummary or None
    # numbers compared for ``correct``: name -> {"value", "limit"}
    checks: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def correct(self) -> bool:
        if not self.checks:
            return False
        return all(_within(c) for c in self.checks.values())


def _within(check: Dict[str, float]) -> bool:
    v = check["value"]
    return v is not None and math.isfinite(v) and v <= check["limit"]


def read_metrics(rec: Record, metrics: List[Dict[str, Any]],
                 bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, Any]:
    """Each metric's reader by name; a reader that returns None is left
    out of the line."""
    out = {}
    for m in metrics:
        value = load_module("metrics", m["name"], bench_dir).read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(rec: Record, devices) -> Dict[str, Any]:
    cell = rec.cell
    metrics = read_metrics(rec, cell.per_layer if rec.trace
                           else cell.end_to_end)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": rec.memory.get("peak_bytes_in_use", 0)}
    line = {"correct": rec.correct(), "attempted": rec.attempted,
            "failed": rec.failed, "metrics": metrics, "device": device}
    if rec.trace and rec.trace_summary is not None:
        device["busy_s"] = rec.trace_summary.busy_s
        device["window_s"] = rec.trace_summary.window_s
        line["breakdown"] = rec.trace_summary.breakdown()
    if rec.occupancy:
        line["occupancy"] = rec.occupancy
    line["checks"] = rec.checks
    return line


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             devices=None, shrink: Optional[Callable] = None,
             fault: Optional[Callable] = None, bench=None,
             t_process: Optional[float] = None,
             log=lambda s: print(s, file=sys.stderr, flush=True)):
    """Run one cell and return (result line dict, record).

    ``devices`` defaults to the cell's chips (NoChip without a TPU).  The
    tests pass CPU devices, a ``shrink(config, traffic)`` that makes the
    cell small, and a ``fault(target)`` that breaks the timed path."""
    cell = find_cell(name, bench)
    t0 = t_process if t_process is not None else time.perf_counter()
    t_import = time.perf_counter()
    import jax                                          # noqa: F401
    t_import = time.perf_counter() - t_import
    if devices is None:
        devices = chips_for(cell)
    peak = peaks(devices[0].device_kind) if devices[0].platform == "tpu" \
        else {"bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}
    rec = Record(cell=cell, seed=seed, seconds=seconds, trace=trace,
                 peak=peak)
    rec.info["setup_parts"] = {"import_jax": t_import,
                               "to_devices": time.perf_counter() - t0}
    runner(cell.traffic["kind"]).run(rec, devices, t0=t0, shrink=shrink,
                                     fault=fault, log=log)
    return result_line(rec, devices), rec


def print_result(line: Dict[str, Any]):
    """Checks as the last lines on stderr, then the line on stdout."""
    for name, c in line["checks"].items():
        ok = "ok" if _within(c) else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
