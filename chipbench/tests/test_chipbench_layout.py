"""The benchmark's files: BENCHMARK.json against the contract, every piece
found by its name, references independent of the program, and the entry
point's refusal to run without a TPU."""
from __future__ import annotations

import ast
import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench import bench, compare

BM = bench.benchmark()
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_has_the_contract_keys():
    assert set(BM) == TOP_KEYS
    assert BM["command"] == ["python3", "chipbench/run.py"]
    assert BM["paths"] == ["chipbench"]
    assert 1 <= BM["run_seconds"] <= 51
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES


def test_names_are_well_formed_and_unique():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BM["workloads"]]:
        assert bench.NAME_RE.match(n), n


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_cell_loads_by_name_and_reports_enough(cell):
    entry = next(w for w in BM["workloads"] if w["name"] == cell)
    wl = bench.workload(cell)
    assert wl["config"] == entry["config"] and wl["traffic"] == entry["traffic"]
    assert wl["limits"] and set(wl["limits"]) <= set(compare.NUMBERS)
    e2e, per_layer = bench.cell_metrics(BM, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and per_layer
    assert all(m["moves"] in names for m in per_layer)


@pytest.mark.parametrize("name", [c["name"] for c in BM["configs"]])
def test_config_loads_by_name(name):
    entry = next(c for c in BM["configs"] if c["name"] == name)
    cfg = bench.config(name)
    assert cfg["name"] == name and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert json.loads((bench.CHECKOUT / entry["file"]).read_text()) == cfg
    mod = bench.config_module(name)
    assert callable(mod.Trainer) and callable(mod.reference)
    assert mod.model_flops_per_sample(cfg, bench.workload(
        next(w["name"] for w in BM["workloads"] if w["config"] == name))) > 0


@pytest.mark.parametrize("name", [m["name"] for m in BM["per_layer"]])
def test_metric_loads_by_name(name):
    assert callable(bench.metric_module(name).read)


@pytest.mark.parametrize("path", sorted((bench.HERE / "configs").glob("*_reference.py")),
                         ids=lambda p: p.name)
def test_references_import_nothing_of_the_program(path):
    tree = ast.parse(path.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "functools", "math", "typing", "jax", "numpy", "chipbench"}
    assert "repro" not in path.read_text().replace("repro/nn/xlstm.py", "")


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    cell = BM["workloads"][0]["name"]
    proc = _run(["--workload", cell, "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
                bench.CHECKOUT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
    assert "platform=cpu" in proc.stderr and "device_kind=" in proc.stderr
    assert "count=" in proc.stderr


def test_run_refuses_an_unknown_cell():
    proc = _run(["--workload", "no-such-cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
                bench.CHECKOUT)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_run_refuses_in_a_checkout_of_only_the_benchmark(tmp_path: pathlib.Path):
    import shutil

    shutil.copy(bench.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = BM["workloads"][0]["name"]
    proc = _run(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"], tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
