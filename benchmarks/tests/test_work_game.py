"""``lib/work_game.py``: the reads a GLMix sweep needs, by hand."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest

from benchmarks.lib import work, work_game


@pytest.mark.parametrize("iterations,from_zero,passes", [
    (0, True, 1), (0, False, 2), (10, True, 21), (10, False, 22), (5, False, 12),
])
def test_lbfgs_passes(iterations, from_zero, passes):
    # start point: backward (and forward unless it is the zero vector), then
    # a forward and a backward read per iteration; trials read nothing
    assert work_game.lbfgs_passes(iterations, from_zero) == passes


def test_re_sweep_bytes_by_hand():
    coordinate = {
        "buckets": [{"entities": 1000, "rows": 1, "d": 16}, {"entities": 10, "rows": 256, "d": 16}],
        "kept_rows": 5000, "d": 16,
    }
    # bucket 0: 1000 x 1 x 16 cells x 4 B = 64 000 B a read, 5 iterations
    # from a point that is not zero: 12 reads; bucket 1: 163 840 B, 3
    # iterations: 8 reads; rescoring 5000 x 16 x 4 B once
    want = 64_000 * 12 + 163_840 * 8 + 320_000
    assert work_game.re_sweep_bytes(coordinate, [5, 3], from_zero=False) == want
    flops, nbytes = work_game.re_solve_work(coordinate["buckets"], [5, 3], False)
    assert nbytes == want - 320_000 and flops == nbytes / 2  # 2 flops a 4-byte cell
    assert work_game.re_rescore_work(5000, 16) == (160_000.0, 320_000.0)
    # from zero each bucket saves its start point's forward read
    assert work_game.re_sweep_bytes(coordinate, [5, 3], from_zero=True) == want - 64_000 - 163_840
    with pytest.raises(ValueError):
        work_game.re_solve_work(coordinate["buckets"], [5], False)


def test_the_cell_s_fixed_effect_block_is_work_py_s_sparse_block():
    # 2**22 rows x 24 slots: 8 B a slot a pass, as in sparse_poisson.solve
    assert work.pass_work({"kind": "sparse", "nnz": (1 << 22) * 24, "re": {}, "re_step_bytes": []}) \
        == (2.0 * (1 << 22) * 24, 8.0 * (1 << 22) * 24)
