"""The comparison that decides ``correct`` for the VGG cell, driven through
whole runs with only the look for a chip skipped (``harness.run_cell``), at
a size a CPU holds and under the cell's own limits.  The program comes out
correct; each fault the cell can have does not: a step that returns its
state unchanged, half of the batch left out with the mean taken over the
rest (one chip: no exchange between chips to leave out), and a step that
hands the noise a zero clipped-gradient sum.  Nor does the
control, the reference with its operands in float8 in the program's place."""
from __future__ import annotations

import pytest

from chipbench import compare
from chipbench.tests.small import control_checks, run, small_cells  # noqa: F401

CELLS = ["vgg19-c10.mixed_ghost"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("variant", ["program", "unchanged", "half_batch", "sum_dropped"])
def test_only_the_program_comes_out_correct(small_cells, cell, variant):  # noqa: F811
    result = run(cell, variant)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"samples_per_s", "step_s.p95", "peak_hbm_gb", "setup_s"}
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    assert result["correct"] is (variant == "program"), result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_float8_control_comes_out_not_correct(small_cells, cell):  # noqa: F811
    checked = control_checks(cell)
    assert not compare.passed(checked), checked


def test_traced_result_line(small_cells, monkeypatch):  # noqa: F811
    from chipbench import peaks

    monkeypatch.setattr(peaks, "peaks_for", lambda kind: peaks.PEAKS["TPU v5 lite"])
    result = run("vgg19-c10.mixed_ghost", traced=True)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device",
                            "breakdown", "checks"]
    assert result["correct"] is True
    # a CPU trace has no TPU plane: the device's readers find nothing to read
    assert set(result["metrics"]) == {"loop.host_ms_per_step", "train_step.mfu"}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(result["device"])

