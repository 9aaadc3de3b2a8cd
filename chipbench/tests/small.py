"""Cells cut to a size a CPU holds, for tests that drive a whole run.

Only the sizes change: the cell's traffic, optimizer and limits stay its
own.  The plain reference is computed once per configuration and seed and
shared by the runs of every variant, which compare against the same one.
"""
from __future__ import annotations

import time

import pytest

from chipbench import bench, compare, harness

SIZES = {
    "vgg19-c10": {"arch": "vgg11", "plan": [64, "M", 128, "M", 256, 256, "M", 512, 512, "M",
                                            512, 512, "M"]},
    "xlstm-350m": {"d_model": 64, "n_heads": 4, "vocab": 128, "n_layers": 8,
                   "slstm_ffn": 64, "ssm_chunk": 8},
}
SEED = 2**31 + 11  # past 32 signed bits, as the driver's seeds are
_REFERENCES: dict = {}


def small_workload(name: str) -> dict:
    wl = _workload(name)
    wl.update(batch=4, reference_block=2, trace_steps=2)
    if "seq" in wl:
        wl.update(batch=2, seq=256)
    return wl


def small_config(name: str) -> dict:
    cfg = _config(name)
    cfg.update(SIZES[name])
    return cfg


_workload, _config = bench.workload, bench.config


@pytest.fixture
def small_cells(monkeypatch):
    monkeypatch.setattr(bench, "workload", small_workload)
    monkeypatch.setattr(bench, "config", small_config)
    monkeypatch.setattr(harness, "peak_bytes", lambda chips: 0)
    for name in SIZES:
        mod = bench.config_module(name)
        monkeypatch.setattr(mod, "reference", _shared(name, mod.reference))


def _shared(name, reference):
    def ref(cfg, wl, seed, n_steps):
        key = (name, wl["mode"], seed, n_steps)
        if key not in _REFERENCES:
            _REFERENCES[key] = reference(cfg, wl, seed, n_steps)
        return _REFERENCES[key]
    return ref


def run(cell: str, variant: str = "program", traced: bool = False) -> dict:
    return harness.run_cell(cell, SEED, 0.5, traced, time.perf_counter(), variant=variant)


def control_checks(cell: str) -> dict:
    """The cell's compared numbers for its control: the reference, computed
    lower, put in the program's place."""
    wl = bench.workload(cell)
    cfg = bench.config(wl["config"])
    mod = bench.config_module(wl["config"])
    ref = mod.reference(cfg, wl, SEED, harness.FIRST_STEPS)
    control = mod.reference_control(cfg, wl, SEED, harness.FIRST_STEPS)
    return compare.checks(compare.training_numbers(control, ref)[0], wl["limits"])
