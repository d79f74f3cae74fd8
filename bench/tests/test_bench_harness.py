"""The harness finds every piece by name, so a cell, a mix or a metric is
added as files and entries alone; without a TPU a run prints nothing."""
import json
import shutil
import sys

import pytest

from bench import harness, run


def _bench_copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return root


def test_added_files_are_found_by_name(tmp_path):
    root = _bench_copy(tmp_path)
    bench_dir = root / "bench"
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    # a new configuration, traffic mix and per-layer metric: files only
    cfg = json.loads((bench_dir / "configs" / "qwen2-0.5b.json").read_text())
    (bench_dir / "configs" / "added-model.json").write_text(
        json.dumps(dict(cfg, name="added-model")))
    (bench_dir / "traffic" / "added-mix.json").write_text(json.dumps(
        {"kind": "serve", "slots": 2, "cache_len": 64}))
    (bench_dir / "metrics" / "added_metric.serve.py").write_text(
        "def read(rec):\n    return rec.counters['admitted'] * 2.0\n")
    bench["configs"].append({"name": "added-model", "source": "x",
                             "file": "bench/configs/added-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "added-model.added-mix",
                               "config": "added-model",
                               "traffic": "added-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "added_metric.serve", "unit": "n",
                               "better": "higher", "source": "program_counter",
                               "layer": "engine (serve/engine.py)",
                               "moves": "ttft_p95_ms",
                               "workloads": ["added-model.added-mix"]})
    for m in bench["end_to_end"]:
        if m["name"] == "ttft_p95_ms":
            m["workloads"].append("added-model.added-mix")
    cell = harness.find_cell("added-model.added-mix", bench, bench_dir)
    assert cell.config["name"] == "added-model"
    assert cell.traffic == {"kind": "serve", "slots": 2, "cache_len": 64}
    assert [m["name"] for m in cell.per_layer] == ["added_metric.serve"]
    assert "ttft_p95_ms" in [m["name"] for m in cell.end_to_end]
    rec = harness.Record(cell=cell, seed=1, seconds=1.0, trace=True,
                         peak={}, counters={"admitted": 21})
    assert harness.read_metrics(rec, cell.per_layer, bench_dir) == {
        "added_metric.serve": {"value": 42.0, "unit": "n"}}


def test_missing_file_is_an_error(tmp_path):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "m.nomix", "config": "qwen2-0.5b",
                               "traffic": "no-such-mix", "chips": 1,
                               "why": "x"})
    with pytest.raises(harness.SpecError):
        harness.find_cell("m.nomix", bench)


def test_every_declared_metric_has_a_reader():
    bench = harness.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench)
        harness.runner(cell.traffic["kind"])


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys, monkeypatch):
    # run.main sets these for its own process; give them back afterwards
    for var in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(var, "")
    monkeypatch.setattr(sys, "path", list(sys.path))
    name = harness.load_benchmark()["workloads"][0]["name"]
    assert run.main(["--workload", name, "--seed", "1", "--seconds", "1"]) \
        == 2
    assert capsys.readouterr().out == ""
