"""work.py's operations and bytes against hand counts."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import pytest

from benchmarks.lib import peaks, work


def test_sparse_pass_is_8_bytes_and_2_flops_a_nonzero():
    # 4 rows x 3 stored slots: 12 nonzeros
    assert work.pass_work({"kind": "sparse", "nnz": 12}) == (24.0, 96.0)


def test_dense_pass_reads_the_block_once():
    # [8, 4] f32: 32 elements, 4 B each; a multiply and an add per element
    assert work.pass_work({"kind": "dense", "n": 8, "d": 4, "itemsize": 4}) == (64.0, 128.0)
    assert work.pass_work({"kind": "dense", "n": 8, "d": 4, "itemsize": 2}) == (64.0, 64.0)


def test_a_glmix_step_adds_its_blocks():
    # FE block 100 nnz x 5 passes, one RE bucket [10 entities x 4 rows x 2] f32
    # x 3 passes, one rescoring pass over the FE block
    fe = {"kind": "sparse", "nnz": 100}
    re = {"kind": "dense", "n": 40, "d": 2, "itemsize": 4}
    flops, nbytes = work.step_work([(fe, 5), (re, 3), (fe, 1)])
    assert flops == 2 * 100 * 6 + 2 * 80 * 3
    assert nbytes == 8 * 100 * 6 + 4 * 80 * 3


def test_passes_are_counted_from_the_algorithm():
    # TRON, 4 iterations and 5 Hessian-vector products: the start point (2),
    # forward and backward per product (10) and per candidate point (8). The
    # plain algorithm makes 4 + 3 x 4 + 2 x 5 = 26 calls; the six that need
    # no read of X (the zero vector's two, four curvature passes) are not work
    assert work.tron_passes(4, 5) == 20
    # OWL-QN, a solve's first segment: 2 iterations with 3 trials between
    # them, the start point's forward and backward; a later segment has none
    assert work.owlqn_passes(2, 3, fresh=True) == 3 + 2 + 2
    assert work.owlqn_passes(2, 3, fresh=False) == 3 + 2


def test_unknown_block_kind_raises():
    with pytest.raises(ValueError):
        work.pass_work({"kind": "csr"})


def test_least_seconds_takes_the_binding_bound():
    # 819e9 B at 819e9 B/s is 1 s; 197e12 flop at 197e12 flop/s is 1 s
    assert peaks.least_seconds(1.0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    assert peaks.least_seconds(2 * 197e12, 819e9, "TPU v5 lite") == pytest.approx(2.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
