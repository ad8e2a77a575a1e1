"""A solve's boundary evaluations reuse what the solve holds
(optimize/lbfgs.evaluate_start, ops/objective ``at_zero`` / ``full_product``).

The zero point (photon-ml's absolute tolerances) is evaluated with one
backward pass and no forward one, a start point whose values are all zero is
not evaluated a second time, and the last exact re-evaluation hands its
feature product to a caller that scores the solution. All of it is decided
from the input, in the program: these tests hold the results to a plain
driver that evaluates both points the old way, ``n_feature_passes`` to what
ran, and the compiled programs to the passes they may hold.

THE OLD WAY is kept here, not in the package: ``_old_way`` strips the
oracle's ``at_zero``, and a solve over such an oracle calls ``full`` at the
zero point and again at the start point (4 passes), as every solve did.

Bits. Dropping an evaluation whose result is in hand changes no value, but
two XLA programs that hold the same arithmetic need not round alike: the
compiler fuses the zero point's backward pass one way behind a forward pass
and another way behind the offsets alone. So a case is run where both
drivers hand the compiler the SAME evaluation: a zero start eagerly (the
reused values pass through untouched), any other start under ``jit`` (the
start point's evaluation is one subgraph in both programs). There every
array is compared bit for bit. Zeros that arrive as the traced argument of
a jitted caller (the benchmark runner's situation) are held in float64 to
what rounding allows.
"""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.ops.losses import LogisticLoss, PoissonLoss
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optimize import OptimizerConfig, minimize_lbfgs, minimize_owlqn
from photon_tpu.optimize.common import one_solve_a_lane
from photon_tpu.optimize.owlqn import SegmentedOWLQN
from photon_tpu.types import LabeledBatch, SparseBatch

COMPARED = (
    "x", "value", "gradient", "loss_history", "grad_norm_history",
    "iterations", "reason",
)
L1 = 0.05
N, D, K = 300, 24, 5


def _old_way(oracle):
    """The oracle as it was: no evaluation at zero of its own."""
    return oracle._replace(at_zero=None)


def _batch(block: str, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.poisson(1.0, size=N).astype(dtype)
    rows = dict(
        labels=jnp.asarray(labels),
        offsets=jnp.asarray(0.1 * rng.standard_normal(N).astype(dtype)),
        weights=jnp.asarray(rng.uniform(0.5, 2.0, size=N).astype(dtype)),
    )
    if block == "dense":
        x = (0.3 * rng.standard_normal((N, D))).astype(dtype)
        x[:, 0] = 1.0
        return LabeledBatch(features=jnp.asarray(x), **rows)
    idx = rng.integers(1, D, size=(N, K)).astype(np.int32)
    idx[:, 0] = 0
    val = (0.3 * rng.standard_normal((N, K))).astype(dtype)
    val[:, 0] = 1.0
    return SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val), windows=None, **rows
    )


def _objective(shifts: bool, dtype=np.float32, l1=0.0):
    norm = NormalizationContext()
    if shifts:
        rng = np.random.default_rng(7)
        s = (0.2 * rng.standard_normal(D)).astype(dtype)
        f = (1.0 + 0.2 * rng.uniform(size=D)).astype(dtype)
        s[0], f[0] = 0.0, 1.0
        norm = NormalizationContext(
            factors=jnp.asarray(f), shifts=jnp.asarray(s), intercept_index=0
        )
    return GLMObjective(
        loss=PoissonLoss, l2_weight=0.3, l1_weight=l1, normalization=norm
    )


def _start(zero: bool, dtype=np.float32):
    if zero:
        return jnp.zeros((D,), dtype)
    rng = np.random.default_rng(11)
    return jnp.asarray((0.05 * rng.standard_normal(D)).astype(dtype))


CFG = OptimizerConfig(max_iterations=12, tolerance=1e-9)


def _solvers(optimizer: str, objective, batch):
    """``(new, old)``: the solve as the package runs it and over the old-way
    oracle, each ``x0 -> OptimizeResult``-like."""
    if optimizer == "lbfgs":
        oracle = objective.directional_oracle(batch)

        def solve(o):
            return lambda x0: minimize_lbfgs(None, x0, CFG, oracle=o)

        return solve(oracle), solve(_old_way(oracle))
    if optimizer == "owlqn":
        oracle = objective.smooth_margin_oracle(batch)

        def solve(o):
            return lambda x0: minimize_owlqn(None, x0, L1, CFG, oracle=o)

        return solve(oracle), solve(_old_way(oracle))
    assert optimizer == "segmented"

    def solve(factory):
        seg = SegmentedOWLQN(
            None, L1, CFG, oracle_factory=factory, segment_iters=3
        )

        def run(x0):
            state = seg.start(x0, batch)
            state, _ = seg.advance(state, batch)
            return seg.finish(state, batch)

        return run

    return (
        solve(objective.smooth_margin_oracle),
        solve(lambda b: _old_way(objective.smooth_margin_oracle(b))),
    )


def _expected_passes(optimizer: str, res, zero: bool) -> int:
    start = 1 if zero else 3
    it, evals = int(res.iterations), int(res.n_evals)
    if optimizer == "lbfgs":  # 2 an iteration + the last exact re-evaluation
        return start + 2 * it + 2
    return start + (evals - 2) + it  # a pass a trial + one backward an iteration


@pytest.mark.parametrize("zero", [True, False], ids=["from_zero", "from_a_start"])
@pytest.mark.parametrize("shifts", [False, True], ids=["plain", "shifts"])
@pytest.mark.parametrize("block", ["dense", "sparse"])
@pytest.mark.parametrize("optimizer", ["lbfgs", "owlqn", "segmented"])
def test_solve_is_the_old_ways_to_the_bit(optimizer, block, shifts, zero):
    batch = _batch(block)
    objective = _objective(shifts, l1=0.0 if optimizer == "lbfgs" else L1)
    new, old = _solvers(optimizer, objective, batch)
    x0 = _start(zero)
    if not zero and optimizer != "segmented":
        # (SegmentedOWLQN's programs are jitted in any case)
        new, old = jax.jit(new), jax.jit(old)
    res, ref = new(x0), old(x0)
    for name in COMPARED:
        np.testing.assert_array_equal(
            np.asarray(getattr(res, name)), np.asarray(getattr(ref, name)), name
        )
    assert int(res.iterations) > 2
    assert int(res.n_feature_passes) == _expected_passes(optimizer, res, zero)
    # the old way ran both evaluations, whatever the start: 4 passes
    assert int(ref.n_feature_passes) == _expected_passes(
        optimizer, ref, zero
    ) + (3 if zero else 1)


# --- zeros as the traced argument of a jitted caller ------------------------


@pytest.mark.parametrize("block", ["dense", "sparse"])
@pytest.mark.parametrize("optimizer", ["lbfgs", "owlqn"])
def test_zeros_handed_to_a_jitted_caller_take_the_zero_start(optimizer, block):
    """The benchmark runner's situation: ``w0`` is an ARRAY of zeros, an
    argument of the jitted program, so nothing at trace time says that the
    start is the zero point. The program finds out from the values: the
    same compiled solve counts 1 start pass on zeros and 3 on any other
    point, and follows the old way's iterates as far as float64 rounds."""
    batch = _batch(block, np.float64)
    objective = _objective(True, np.float64, l1=0.0 if optimizer == "lbfgs" else L1)
    new, old = (jax.jit(f) for f in _solvers(optimizer, objective, batch))
    for zero in (True, False):
        x0 = _start(zero, np.float64)
        res, ref = new(x0), old(x0)
        assert int(res.n_feature_passes) == _expected_passes(optimizer, res, zero)
        assert int(res.iterations) == int(ref.iterations)
        assert int(res.reason) == int(ref.reason)
        for name in COMPARED[:5]:
            np.testing.assert_allclose(
                np.asarray(getattr(res, name)),
                np.asarray(getattr(ref, name)),
                rtol=1e-9,
                atol=1e-11,
                err_msg=name,
            )
    assert new._cache_size() == 1  # one program served both starts


@pytest.mark.parametrize("zero", [True, False], ids=["from_zero", "from_a_start"])
def test_segmented_start_counts_what_ran(zero):
    """``SegmentedOWLQN.start`` (the runner's ``_init_f``): a state whose
    ``n_passes`` is 1 from an array of zeros and 3 from elsewhere, its
    values the old way's."""
    batch = _batch("sparse", np.float64)
    objective = _objective(False, np.float64, l1=L1)

    def start(factory):
        seg = SegmentedOWLQN(None, L1, CFG, oracle_factory=factory, segment_iters=3)
        return seg.start(_start(zero, np.float64), batch)

    state = start(objective.smooth_margin_oracle)
    ref = start(lambda b: _old_way(objective.smooth_margin_oracle(b)))
    assert int(state.n_passes) == (1 if zero else 3)
    assert int(ref.n_passes) == 4
    for name in ("f", "g_smooth", "carry", "loss_abs_tol", "grad_abs_tol"):
        np.testing.assert_allclose(
            np.asarray(getattr(state, name)),
            np.asarray(getattr(ref, name)),
            rtol=1e-12,
            err_msg=name,
        )


# --- under vmap: a small random-effect bucket --------------------------------


@pytest.mark.parametrize("marked", [False, True], ids=["bare_vmap", "one_solve_a_lane"])
def test_vmapped_bucket_counts_what_ran(marked):
    """Per-entity solves. Under a bare ``vmap`` the ``cond`` is a select:
    both branches run, each lane counts its own start and lands where the
    old way lands, as far as float64 rounds. A random effect's bucket marks
    its ``vmap`` (``one_solve_a_lane``): the start is evaluated outright on
    every lane (3 passes: what the select runs too, less the select), and
    the iterates are the old way's bit for bit: only the tolerances' scale
    comes from ``at_zero``."""
    rng = np.random.default_rng(3)
    e, rows, d = 6, 40, 5
    feats = jnp.asarray(rng.standard_normal((e, rows, d)))
    labels = jnp.asarray((rng.uniform(size=(e, rows)) > 0.5).astype(np.float64))
    offsets = jnp.asarray(0.1 * rng.standard_normal((e, rows)))
    weights = jnp.ones((e, rows))
    w0 = np.zeros((e, d))
    w0[1::2] = 0.1 * rng.standard_normal((e // 2, d))  # every other lane warm
    objective = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    cfg = OptimizerConfig(max_iterations=6, tolerance=1e-12)

    def bucket(strip):
        def one(f, y, o, w, x0):
            b = LabeledBatch(features=f, labels=y, offsets=o, weights=w)
            return minimize_lbfgs(
                None, x0, cfg, oracle=strip(objective.directional_oracle(b))
            )

        with one_solve_a_lane() if marked else contextlib.nullcontext():
            return jax.jit(jax.vmap(one))(
                feats, labels, offsets, weights, jnp.asarray(w0)
            )

    res, ref = bucket(lambda o: o), bucket(_old_way)
    its = np.asarray(res.iterations)
    np.testing.assert_array_equal(its, np.asarray(ref.iterations))
    start = 3 if marked else np.where(np.any(w0 != 0, axis=1), 3, 1)
    np.testing.assert_array_equal(
        np.asarray(res.n_feature_passes), start + 2 * its + 2
    )
    np.testing.assert_array_equal(np.asarray(ref.n_feature_passes), 4 + 2 * its + 2)
    for name in COMPARED[:5]:
        got, want = np.asarray(getattr(res, name)), np.asarray(getattr(ref, name))
        if marked:
            np.testing.assert_array_equal(got, want, name)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-11, err_msg=name)
    assert res.product is None  # a bucket's result grows by no [E, rows] array


# --- the product of the last evaluation -------------------------------------


@pytest.mark.parametrize("shifts", [False, True], ids=["plain", "shifts"])
@pytest.mark.parametrize("block", ["dense", "sparse"])
def test_kept_product_is_the_feature_product_at_x(block, shifts):
    batch = _batch(block)
    objective = _objective(shifts)
    oracle = objective.directional_oracle(batch)
    x0 = _start(True)
    kept = minimize_lbfgs(None, x0, CFG, oracle=oracle, keep_product=True)
    plain = minimize_lbfgs(None, x0, CFG, oracle=oracle)
    assert plain.product is None
    for name in COMPARED:
        np.testing.assert_array_equal(
            np.asarray(getattr(kept, name)), np.asarray(getattr(plain, name)), name
        )
    np.testing.assert_array_equal(
        np.asarray(kept.product), np.asarray(objective.product(kept.x, batch))
    )
    # nothing is added to it: the margins are product + offsets (+ shift)
    np.testing.assert_array_equal(
        np.asarray(objective.margins(kept.x, batch, product=kept.product)),
        np.asarray(objective.margins(kept.x, batch)),
    )
    # no last evaluation, no product: the box path re-evaluates in the loop
    boxed = OptimizerConfig(
        max_iterations=5, lower_bounds=jnp.full((D,), -1.0), upper_bounds=jnp.full((D,), 1.0)
    )
    assert minimize_lbfgs(
        None, x0, boxed, oracle=oracle, keep_product=True
    ).product is None
