"""Work and bytes counts, the peak table, and that the work a cell counts
does not depend on which kernel variant served it."""
import tempfile

import numpy as np
import pytest

from perfbench import check, harness, work
from perfbench.tests import tiny


def test_counts_of_a_small_operand_are_exact():
    op = work.Operand(m=3, k=5, nnz=4, value_bytes=4, index_bytes=4)
    assert work.flops(op, 2) == 16.0
    assert work.bytes_moved(op, 2) == 4 * 8 + 5 * 2 * 4 + 3 * 2 * 4
    assert work.bytes_moved(op, 2, rhs_bytes=2, out_bytes=2) == \
        4 * 8 + 5 * 2 * 2 + 3 * 2 * 2
    peak = work.Peak(flops_per_s=8.0, bytes_per_s=100.0, source="test")
    assert work.roofline_s(op, 2, peak) == pytest.approx(2.0)  # flop-bound
    slow_mem = work.Peak(flops_per_s=1e9, bytes_per_s=1.0, source="test")
    assert work.roofline_s(op, 2, slow_mem) == 96.0           # byte-bound


def test_v5e_peaks_and_unknown_kind():
    p = work.peak_for("TPU v5 lite")
    assert (p.flops_per_s, p.bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peak"):
        work.peak_for("TPU v99 imaginary")


def _served_waves(root, variant, seed=11):
    cell = harness.load_cell("tiny-closed", root)
    cell.traffic = dict(cell.traffic, variant=variant)
    system = harness.build(cell, seed)
    harness.warm(system, cell.traffic)
    win = harness.run_window(system, cell.traffic, seed, 0.5,
                             check.Sampler(seed, 2))
    return system.op, win


def test_two_kernel_variants_count_the_same_work():
    root = tiny.make(tempfile.mkdtemp())
    op_a, win_a = _served_waves(root, "expand")
    op_b, win_b = _served_waves(root, "pipelined")
    assert op_a == op_b
    assert op_a.nnz == 256 * 2 * 8          # 8 kept of every 256 columns
    # Same columns served give the same work, whichever variant ran them.
    cols = min(win_a.cols, win_b.cols)
    assert cols > 0
    assert work.flops(op_a, cols) == work.flops(op_b, cols)
    assert work.bytes_moved(op_a, cols) == work.bytes_moved(op_b, cols)
    assert set(np.unique(win_a.inside())) <= {64, 128}
