"""Operations and bytes a GLMix descent sweep NEEDS, beside ``work.py``'s
feature pass: from shapes and the algorithm's own counts (the optimizer
iterations of each solve), never from a pass counter the program keeps.

- fixed effect: an L-BFGS solve reads the feature block once forward at its
  start point and once backward for the gradient there, then once forward
  (the direction's margins) and once backward (the accepted point's
  gradient) per iteration. A line-search trial needs no read: the margins
  are affine in the step. From the zero vector the start's forward read is
  not needed either (X 0 is 0). The program's last exact re-evaluation and
  its rescoring product are its own choices (the margins at the last point
  are known) and are not counted.
- random effect: the same count per bucket, over the bucket's padded block
  ``entities x rows x d`` of ``itemsize`` bytes, for as many iterations as
  the bucket's slowest entity took (every entity of a bucket is carried
  through that many); and one read of the flat ``[kept rows, d]`` block to
  rescore. Labels, offsets, weights and the coefficient tables are left
  out, as ``work.py`` leaves out the [d] table and the [n] vectors.
"""
from __future__ import annotations


def lbfgs_passes(iterations: int, from_zero: bool) -> int:
    """Reads of the feature block that an L-BFGS solve of ``iterations``
    iterations cannot do without."""
    return 2 * iterations + (1 if from_zero else 2)


def re_solve_work(buckets: list[dict], iterations: list[int], from_zero: bool,
                  itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes) of one random-effect coordinate's solves in a sweep:
    ``buckets`` are ``{"entities", "rows", "d"}``, ``iterations`` the
    slowest entity's count per bucket."""
    flops = nbytes = 0.0
    for b, it in zip(buckets, iterations, strict=True):
        cells = b["entities"] * b["rows"] * b["d"]
        passes = lbfgs_passes(it, from_zero)
        flops += 2.0 * cells * passes
        nbytes += float(itemsize) * cells * passes
    return flops, nbytes


def re_rescore_work(kept_rows: int, d: int, itemsize: int = 4) -> tuple[float, float]:
    """(flops, bytes) of rescoring a random-effect coordinate: every kept
    row's features dotted with its entity's coefficients."""
    return 2.0 * kept_rows * d, float(itemsize) * kept_rows * d


def re_sweep_bytes(coordinate: dict, iterations: list[int], from_zero: bool) -> float:
    """Bytes one random-effect coordinate's sweep program needs:
    ``coordinate`` is ``{"buckets": [...], "kept_rows", "d"}``."""
    _, solve = re_solve_work(coordinate["buckets"], iterations, from_zero)
    _, rescore = re_rescore_work(coordinate["kept_rows"], coordinate["d"])
    return solve + rescore
