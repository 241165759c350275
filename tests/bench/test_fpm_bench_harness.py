"""The harness: cells found by name, the last line's schema, and the
command's refusal to run off a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
import run

ROOT = harness.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_every_cell_of_the_spec_resolves():
    for w in SPEC["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.params["kind"] in ("mine", "serve")
        assert cell.driver().run and cell.driver().rehearse
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)


def test_new_files_are_found_by_name_without_edits(tmp_path):
    """A config, a traffic mix, a workload and a per-layer metric added
    as files (and entries) are picked up; no existing file changes."""
    bench = tmp_path / "benchmarks" / "fpm_bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    cfg = json.load(open(bench / "configs" / "quest-t10i4d100k.json"))
    cfg["name"] = "quest-new"
    (bench / "configs" / "quest-new.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "mine", "max_warmup": 1}))
    (bench / "workloads" / "new-cell.json").write_text(json.dumps(
        {"config": "quest-new", "traffic": "new-mix", "chips": 1,
         "why": "test", "who": "test"}))
    (bench / "layer_metrics" / "ops.new.py").write_text(
        "def read(record):\n    return float(len(record['ops']))\n")
    spec["configs"].append({"name": "quest-new", "source": "test",
                            "file": "benchmarks/fpm_bench/configs/"
                                    "quest-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new-cell", "config": "quest-new",
                              "traffic": "new-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "ops.new", "unit": "ops",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "scheduler", "moves": "setup_s",
                              "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.Cell("new-cell", root=str(tmp_path), bench=str(bench))
    assert cell.config["name"] == "quest-new"
    assert cell.params == {"kind": "mine", "max_warmup": 1}
    assert [m["name"] for m in cell.per_layer] == ["ops.new"]
    got = run.per_layer(cell, {"ops": [1, 2, 3]})
    assert got == {"ops.new": {"value": 3.0, "unit": "ops"}}


def test_an_unlisted_cell_is_built_from_its_files_alone(tmp_path):
    bench = tmp_path / "benchmarks" / "fpm_bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench / "workloads" / "t10i4-serve.json",
                bench / "workloads" / "unlisted-cell.json")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    with pytest.raises(harness.SpecError):
        harness.Cell("unlisted-cell", root=str(tmp_path), bench=str(bench))
    cell = harness.Cell.unlisted("unlisted-cell", bench=str(bench))
    assert cell.config["name"] == "quest-t10i4d100k"
    assert cell.params["kind"] == "serve" and cell.chips == 1
    assert cell.end_to_end == [] and cell.per_layer == []


def test_a_cell_file_that_disagrees_is_refused(tmp_path):
    bench = tmp_path / "benchmarks" / "fpm_bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    name = SPEC["workloads"][0]["name"]
    path = bench / "workloads" / (name + ".json")
    cell = json.loads(path.read_text())
    cell["chips"] = 4
    path.write_text(json.dumps(cell))
    with pytest.raises(harness.SpecError):
        harness.Cell(name, root=str(tmp_path), bench=str(bench))


def test_last_line_schema():
    cell = harness.Cell("t40i10-mine")
    out = {"correct": True, "attempted": 3, "failed": 0,
           "metrics": {"mine_s": 2.5, "setup_s": 30.0},
           "record": {"ops": [{"t0": 0.0, "t1": 1.0, "flushes": 4,
                               "requests": 10}]},
           "checks": {"mines_wrong": {"value": 0, "limit": 0}}}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123}
    line = run.result_line(cell, out, device)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["metrics"] == {"mine_s": {"value": 2.5, "unit": "s"},
                               "setup_s": {"value": 30.0, "unit": "s"}}
    json.dumps(line)
    reduced = {"busy_s": 0.25, "window_s": 1.0, "top_ops": [["x", 0.1]],
               "idle_gaps": [["mine", 0.5]],
               "kernel_s": {"bitmap_join_many": 0.2},
               "kernel_seen": {"bitmap_join_many": True}}
    traced = run.result_line(cell, out, device, reduced)
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "checks"]
    assert traced["device"]["busy_s"] == 0.25
    assert traced["metrics"]["idle_share.mine"]["value"] == 75.0
    assert traced["metrics"]["bitmap_join_ms.mine"]["value"] == 200.0
    assert traced["metrics"]["batch_occupancy.mine"]["value"] == 2.5
    # a kernel that never ran is left out, not read as 0
    assert "gather_intersect_ms.mine" not in traced["metrics"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/fpm_bench/run.py", "--workload",
         "t40i10-mine", "--seed", str(2 ** 33 + 5), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _has_result(stdout):
    return any(line.startswith("{") for line in stdout.splitlines())


def test_off_a_tpu_the_command_fails_without_a_result():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_with_only_the_benchmark_files_the_command_fails(tmp_path):
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _command(str(tmp_path))
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    # it stops at the missing program, before it ever looks for a chip
    assert "No module named 'repro'" in proc.stderr


def test_peaks_are_keyed_by_device_kind():
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(harness.SpecError):
        harness.peaks("cpu")
