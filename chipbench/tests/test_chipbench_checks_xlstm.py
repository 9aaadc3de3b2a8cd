"""The comparison that decides ``correct`` for the xLSTM cell, driven
through whole runs with only the look for a chip skipped, at a size a CPU
holds and under the cell's own limits: the program comes out correct, each
fault does not, and neither does the control (the reference with its matmul
operands in float8, put in the program's place)."""
from __future__ import annotations

import pytest

from chipbench import compare
from chipbench.tests.small import control_checks, run, small_cells  # noqa: F401

CELL = "xlstm-350m.s4096.mixed_ghost"


@pytest.mark.parametrize("variant", ["program", "unchanged", "half_batch", "sum_dropped"])
def test_only_the_program_comes_out_correct(small_cells, variant):  # noqa: F811
    result = run(CELL, variant)
    assert result["correct"] is (variant == "program"), result["checks"]


def test_float8_control_comes_out_not_correct(small_cells):  # noqa: F811
    checked = control_checks(CELL)
    assert not compare.passed(checked), checked

