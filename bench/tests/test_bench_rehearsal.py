"""Every cell of ``BENCHMARK.json`` loaded from its files by name and
run once on the CPU at a tiny size, and the run refused off a TPU and
where the checkout holds no program."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.rehearse import run_cell, steer

BENCH = harness.load_benchmark()
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_runs_and_prints_the_contract_line(workload, monkeypatch,
                                                capsys):
    steer(monkeypatch)
    rc, line, err = run_cell(capsys, workload)
    assert rc == 0, err[-2000:]
    assert list(line) == KEYS
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    cell = harness.Cell(BENCH, workload)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in line["metrics"]
    for name, m in line["metrics"].items():
        assert m["value"] > 0, name
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    limits = harness.load_json("limits", workload)
    assert set(line["checks"]) == set(limits)
    # every per-layer metric of the cell has a reader, found by name
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    # compiles inside the window are counted on an earlier line
    assert '"compiles_in_window": 0' in err


def test_every_metric_and_cell_is_reported_somewhere():
    names = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", names)) <= names
    for w in names:
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if w in m.get("workloads", names)]
        assert "setup_s" in e2e and len(e2e) >= 2


def test_off_a_tpu_the_run_exits_nonzero_with_no_result(capsys):
    from bench import run
    rc = run.main(["--workload", BENCH["workloads"][0]["name"],
                   "--seed", "1", "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out.strip() == ""
    assert "tpu" in err


def test_every_file_a_cell_names_is_under_the_benchmarks_paths():
    paths = [harness.ROOT / p for p in BENCH["paths"]]
    script = harness.ROOT / BENCH["command"][1]
    assert script.is_file() and any(p in script.parents for p in paths)
    for c in BENCH["configs"]:
        assert any(p in (harness.ROOT / c["file"]).parents for p in paths)
    for w in BENCH["workloads"]:
        cell = harness.Cell(BENCH, w["name"])
        assert cell.config_name == w["config"]
        assert harness.load_json("limits", w["name"])


def test_without_the_program_the_command_exits_nonzero_with_no_result(
        tmp_path):
    """Run as the command line gives it, from a directory that holds
    only ``BENCHMARK.json`` and the benchmark's paths."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
        "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no program" in p.stderr
