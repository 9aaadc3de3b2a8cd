"""Trace reduction on a small trace recorded on one TPU v5e: three steps of
a jitted program around the ghost-norm kernel (8 x 64 x 256 inputs), each
step inside the harness's four spans; and on hand-made intervals."""
from __future__ import annotations

import pathlib

import pytest

from chipbench import bench
from chipbench import trace as T

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
ghost = bench.metric_module("ghost_norm_roofline")


@pytest.fixture(scope="module")
def small():
    return T.load(str(SMALL))


def test_small_trace_spans_and_window(small):
    names = [s.name for s in small.spans]
    assert names == ["chipbench.make_batch", "chipbench.dispatch", "chipbench.sync",
                     "chipbench.record_step"] * 3
    assert small.count_spans("chipbench.sync") == 3
    assert small.window_s == pytest.approx(0.12581654, rel=1e-6)
    assert small.span_seconds("chipbench.record_step") == pytest.approx(0.00705456, rel=1e-5)


def test_small_trace_busy_union_and_idle_share(small):
    assert list(small.ops) == ["/device:TPU:0"]
    assert len(small.ops["/device:TPU:0"]) == 30
    assert small.busy_s() == pytest.approx(4.0051e-05, rel=1e-4)
    assert small.idle_share() == pytest.approx(1 - 4.0051e-05 / 0.12581654, rel=1e-6)


def test_small_trace_kernel_time(small):
    kernel = [e for e in small.ops["/device:TPU:0"] if ghost.is_kernel(e.name)]
    assert len(kernel) == 3
    # the fusion that reads the kernel's output is not the kernel
    assert not ghost.is_kernel(next(e.name for e in small.ops["/device:TPU:0"]
                                    if e.name.startswith("%slice_reduce_fusion")))
    assert small.op_seconds(ghost.is_kernel) == pytest.approx(3 * 7.677e-6, rel=1e-3)
    assert small.top_ops(1)[0][0].startswith("%ghost_norm_sq_pallas.1 = ")


def test_small_trace_gaps_are_labelled_by_span(small):
    gaps = small.longest_gaps(3)
    assert gaps[0][0] == "chipbench.make_batch"  # the first batch compiles
    assert gaps[0][1] > 0.1
    assert sum(e - s for s, e in small.gaps()) / 1e9 == pytest.approx(
        small.window_s - small.busy_s(), rel=1e-6)


def test_union_clip_and_gaps_on_hand_made_intervals():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.clip([(0, 3), (5, 8)], 1, 6) == [(1, 3), (5, 6)]
    tr = T.Trace(
        ops={"/device:TPU:0": [T.Event("a", 10, 20), T.Event("b", 15, 30),
                               T.Event("c", 50, 60)]},
        spans=[T.Event("chipbench.dispatch", 0, 40), T.Event("chipbench.sync", 40, 100)],
    )
    assert tr.window == (0, 100)
    assert tr.busy_s() == pytest.approx(30e-9)
    assert tr.gaps() == [(0, 10), (30, 50), (60, 100)]
    assert tr.longest_gaps(2) == [["chipbench.sync", 40e-9], ["chipbench.dispatch", 20e-9]]
    assert tr.op_seconds(lambda n: n == "b") == pytest.approx(15e-9)
