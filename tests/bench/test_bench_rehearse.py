"""Each cell's whole run, rehearsed on the CPU at the smoke sizes."""

import json
import os
import shutil

import pytest

import benchproc

CELLS = ["qwen2-1.5b.offline", "hymba-1.5b.offline"]


def _well_formed(line, trace):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and "TPU" not in json.dumps(line)
    assert dev["count"] == 1 and "memory_peak_bytes" in dev
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_last_line(cell, tmp_path):
    proc = benchproc.run(
        ["bench/run.py", "--workload", cell, "--seed", str(2**31 + 11),
         "--seconds", "3", "--trace", "0", "--rehearse"], tmp_path)
    line = benchproc.last_line(proc)
    _well_formed(line, trace=False)
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2


def test_traced_rehearsal_last_line(tmp_path):
    proc = benchproc.run(
        ["bench/run.py", "--workload", "qwen2-1.5b.offline", "--seed", "5",
         "--seconds", "1", "--trace", "1", "--rehearse"], tmp_path)
    line = benchproc.last_line(proc)
    _well_formed(line, trace=True)
    assert "nfe_ms.offline" in line["metrics"]
    assert "samples_per_s" not in line["metrics"]


def test_no_chip_no_result(tmp_path):
    proc = benchproc.run(
        ["bench/run.py", "--workload", "qwen2-1.5b.offline", "--seed", "1",
         "--seconds", "1", "--trace", "0"], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    root = benchproc.ROOT
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    for path in json.load(open(os.path.join(root, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(root, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = benchproc.run(
        ["bench/run.py", "--workload", "qwen2-1.5b.offline", "--seed", "1",
         "--seconds", "1", "--trace", "0"], tmp_path / "cache", cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
