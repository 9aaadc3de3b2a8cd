"""The comparison that decides ``correct`` for the BEiT cell, driven through
whole runs with only the look for a chip skipped, at a size a CPU holds
(2 layers, d 64, 4 heads, 32 px images in patches of 8) and under the
cell's own limits: the program comes out correct, each fault does not (one
of them, in the blocks' tap norms alone, put in by patching the program),
and neither does the control (the reference with its operands in float8,
put in the program's place).  A traced run reads the relative position tables'
norm time from a device plane made of the compiled programs' own ops."""
from __future__ import annotations

import pytest

from chipbench import bench, compare, scopes
from chipbench.tests import small
from chipbench import trace as trace_mod
from chipbench.tests.small import control_checks, run, small_cells  # noqa: F401

CELL = "beit-large-c10-224.mixed_ghost"
SIZE = {"image": 32, "patch": 8, "n_layers": 2, "d_model": 64, "n_heads": 4, "head_dim": 16,
        "d_ff": 256, "rel_pos_rows": 52, "params": 113_706}


@pytest.fixture
def beit_cells(monkeypatch, request):
    monkeypatch.setitem(small.SIZES, "beit-large-c10-224", SIZE)
    request.getfixturevalue("small_cells")


@pytest.mark.parametrize("variant", ["program", "unchanged", "half_batch", "sum_dropped"])
def test_only_the_program_comes_out_correct(beit_cells, variant):
    result = run(CELL, variant)
    assert set(result["metrics"]) == {"samples_per_s", "step_s.p95", "peak_hbm_gb", "setup_s"}
    assert result["correct"] is (variant == "program"), result["checks"]


def test_dropped_block_tap_norms_come_out_not_correct(beit_cells, monkeypatch):
    """The program with the per-sample norms of the blocks' matmul taps
    zeroed and all else its own: the blocks' share of each sample's norm is
    missing, so each is clipped too little."""
    from repro.core import ghost

    norm_sq = ghost._tap_norm_sq

    def dropped(meta, a, g, **kw):
        n = norm_sq(meta, a, g, **kw)
        return n * 0 if meta.kind == "matmul" and meta.param_path.startswith("layers") else n

    monkeypatch.setattr(ghost, "_tap_norm_sq", dropped)
    result = run(CELL)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["norm_gap"]["value"] > result["checks"]["norm_gap"]["limit"]


def test_float8_control_comes_out_not_correct(beit_cells):
    checked = control_checks(CELL)
    assert not compare.passed(checked), checked


def test_traced_result_line(beit_cells, monkeypatch):
    """Each compiled instruction that names a traced op runs once, 10 ns
    apiece, on a device plane added to the CPU trace."""
    from chipbench import peaks

    monkeypatch.setattr(peaks, "peaks_for", lambda kind: peaks.PEAKS["TPU v5 lite"])
    mod = bench.config_module("beit-large-c10-224")
    trainers = []

    class Kept(mod.Trainer):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            trainers.append(self)

    monkeypatch.setattr(mod, "Trainer", Kept)
    load = trace_mod.load

    def with_device_plane(path):
        tr = load(path)
        at = next(s.start for s in tr.spans if s.name == "chipbench.sync")
        events = []
        for text in scopes.compiled_programs(trainers[0]).values():
            for name, rest in scopes.INSTRUCTION.findall(text):
                if "op_name" in rest and " parameter(" not in f" {rest}":
                    events.append(trace_mod.Event(f"%{name} = {rest.split(', metadata=')[0]}",
                                                  at, at + 10))
                    at += 10
        tr.ops["/device:TPU:0"] = events
        return tr

    monkeypatch.setattr(trace_mod, "load", with_device_plane)
    result = run(CELL, traced=True)
    assert result["correct"] is True, result["checks"]
    metrics = result["metrics"]
    for name in ("clip.rel_pos_bias_ms", "clip.tap_norms_ms", "clip.norm_pass_ms",
                 "clip.second_pass_ms", "train_step.mfu", "device.idle_share"):
        assert metrics.get(name, {}).get("value", 0) > 0, name
    assert metrics["clip.rel_pos_bias_ms"]["value"] < metrics["clip.tap_norms_ms"]["value"]
