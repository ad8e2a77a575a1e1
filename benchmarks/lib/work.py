"""Operations and bytes a step NEEDS, from shapes and the algorithm's own
counts (iterations, line-search trials, Hessian-vector products). Nothing
here describes one implementation's fetches or reads a pass counter that the
program keeps: a roofline reads the same work whatever kernel does it.

A feature pass is one product with the feature block, X·v or Xᵀ·r.

- sparse (padded ELL, ``nnz`` stored slots): every slot's 4-byte column
  index and 4-byte value are read once, 8 B per nonzero; one multiply and
  one add per nonzero. The [d] table and the [n] row vector are left out:
  they are read through the indices and a kernel may keep them in fast
  memory.
- dense ([n, d] block of ``itemsize``-byte elements): the block is read
  once, ``itemsize`` x n x d bytes; 2 x n x d operations.
"""
from __future__ import annotations


def tron_passes(iterations: int, n_hvp: int) -> int:
    """Reads of the feature block a TRON solve cannot do without, from its
    iterations and Hessian-vector products: forward and backward at the start
    point, forward and backward for each Hessian-vector product, forward and
    backward at each candidate point. The plain algorithm (and the program's
    ``n_feature_passes``) makes 4 + 3 x iterations + 2 x n_hvp calls; two
    kinds of them need no read of their own, whatever implements them: the
    evaluation at the zero vector (X.0 is 0, and its X'.r rides the start
    point's read of X) and each iteration's curvature pass (the margins at
    an accepted point are those of the evaluation that accepted it). A share
    of a roofline counts the reads that are needed, or it passes 100 %."""
    return 2 + 2 * n_hvp + 2 * iterations


def owlqn_passes(iterations: int, trials: int, fresh: bool) -> int:
    """Reads of the feature block that ``iterations`` OWL-QN iterations with
    ``trials`` line-search trials between them cannot do without: one forward
    per trial (Armijo needs the value alone) and one backward per iteration
    (the accepted point's gradient, from its margins). A solve's first step
    (``fresh``) also evaluates the start point, forward and backward; the
    zero vector's evaluation needs no read of its own (as in
    ``tron_passes``)."""
    return trials + iterations + (2 if fresh else 0)


def sparse_pass(nnz: int) -> tuple[float, float]:
    """(flops, bytes) of one pass over a sparse block of ``nnz`` slots."""
    return 2.0 * nnz, 8.0 * nnz


def dense_pass(n: int, d: int, itemsize: int) -> tuple[float, float]:
    """(flops, bytes) of one pass over a dense [n, d] block."""
    return 2.0 * n * d, float(itemsize) * n * d


def pass_work(shape: dict) -> tuple[float, float]:
    """(flops, bytes) of one feature pass of a block described by
    ``{"kind": "sparse", "nnz": ...}`` or ``{"kind": "dense", "n": ...,
    "d": ..., "itemsize": ...}``."""
    if shape["kind"] == "sparse":
        return sparse_pass(shape["nnz"])
    if shape["kind"] == "dense":
        return dense_pass(shape["n"], shape["d"], shape["itemsize"])
    raise ValueError(f"unknown feature block kind {shape['kind']!r}")


def step_work(blocks: list[tuple[dict, int]]) -> tuple[float, float]:
    """(flops, bytes) of a whole step: ``blocks`` pairs each feature block
    the step touches with the number of passes the step needed over it
    (a GLM step has one block; a GLMix sweep has the FE block, each RE
    coordinate's buckets and one rescoring pass per coordinate)."""
    flops = nbytes = 0.0
    for shape, passes in blocks:
        f, b = pass_work(shape)
        flops += f * passes
        nbytes += b * passes
    return flops, nbytes
