"""Work counts kept with the benchmark, against counts made by hand, and the
table of peaks."""
from __future__ import annotations

import itertools

import pytest

from chipbench import bench, peaks, work

VGG19 = bench.config("vgg19-c10")
XLSTM = bench.config("xlstm-350m")


def test_vgg19_forward_macs_by_hand():
    # stage by stage: side^2 * c_in * c_out * 9, then the 512 -> 10 head
    by_hand = (
        32 * 32 * 3 * 64 * 9 + 32 * 32 * 64 * 64 * 9
        + 16 * 16 * 64 * 128 * 9 + 16 * 16 * 128 * 128 * 9
        + 8 * 8 * 128 * 256 * 9 + 3 * 8 * 8 * 256 * 256 * 9
        + 4 * 4 * 256 * 512 * 9 + 3 * 4 * 4 * 512 * 512 * 9
        + 4 * 2 * 2 * 512 * 512 * 9
        + 512 * 10
    )
    got = work.vgg_forward_macs(VGG19["plan"], image=32, in_ch=3, n_classes=10)
    assert got == by_hand == 398_136_320


def test_vgg19_train_flops_leave_out_the_image_gradient():
    fwd = 398_136_320
    first = 32 * 32 * 3 * 64 * 9
    got = work.vgg_train_flops(VGG19["plan"], image=32, in_ch=3, n_classes=10)
    assert got == 2 * (3 * fwd - first)


def test_xlstm_350m_flops_per_token_by_hand():
    d, di, h, v = 1024, 2048, 4, 50304
    dh = di // h
    mlstm = d * di + d * di + di * di + di * di + di * 2 * h + di * d  # in_x in_z q k gates out
    slstm = d * 4 * d + d * 4 * d + 3 * d * 1344  # wx wr, FFN wg wu wo
    matmul = 3 * (slstm + 7 * mlstm) + d * v  # 3 periods and the head; no embedding
    cells = 3 * (7 * (h * 2 * dh * (dh + 1) + 4 * di) + 4 * d)
    pattern = XLSTM["block_pattern"] * 3
    assert work.xlstm_matmul_params(d_model=d, n_heads=h, vocab=v, pattern=pattern) == matmul
    assert work.xlstm_cell_macs_per_token(d_model=d, n_heads=h, pattern=pattern) == cells
    got = work.xlstm_train_flops_per_token(d_model=d, n_heads=h, vocab=v, pattern=pattern)
    assert got == 6 * (matmul + cells)
    assert work.slstm_ffn_width(d) == XLSTM["slstm_ffn"]


@pytest.mark.parametrize("n,t,d,p", [(1, 1, 3, 2), (2, 4, 5, 3), (3, 7, 2, 6)])
def test_ghost_norm_work_counts_the_lower_triangles(n, t, d, p):
    pairs = sum(1 for i, j in itertools.product(range(t), repeat=2) if j <= i)
    flops, bytes_ = work.ghost_norm_work(n, t, d, p, 4, 2)
    # one multiply-add per entry of each Gram triangle and per feature, and
    # one product and one add per entry of the triangle
    assert flops == n * (2 * pairs * d + 2 * pairs * p + 2 * pairs)
    assert bytes_ == n * t * (4 * d + 2 * p) + 4 * n


def test_roofline_takes_the_larger_bound():
    assert work.roofline_seconds(2e12, 1e9, 1e12, 1e9) == 2.0
    assert work.roofline_seconds(1e9, 3e9, 1e12, 1e9) == 3.0


def test_peaks_table_and_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("cpu")
