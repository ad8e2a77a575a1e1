"""Optimizer tests vs closed forms.

Mirrors the reference's pure unit tier: OptimizerTest / LBFGSTest / OWLQNTest
/ TRONTest optimize TestObjective (a quadratic with known minimum,
photon-lib src/test optimization/TestObjective.scala).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.ops.losses import LogisticLoss, SquaredLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optimize import (
    ConvergenceReason,
    OptimizerConfig,
    minimize_lbfgs,
    minimize_owlqn,
    minimize_tron,
)
from photon_tpu.types import LabeledBatch

D = 8


def _quadratic(center):
    center = jnp.asarray(center)

    def value_and_grad(x):
        d = x - center
        return 0.5 * jnp.dot(d, d), d

    return value_and_grad


def _quadratic_hvp(x, v):
    return v


def _ridge_batch(seed=0, n=200, d=D):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    y = x @ w_true + rng.normal(scale=0.1, size=n)
    return LabeledBatch(
        features=jnp.asarray(x),
        labels=jnp.asarray(y),
        offsets=jnp.zeros((n,)),
        weights=jnp.ones((n,)),
    )


def _ridge_closed_form(batch, l2):
    x = np.asarray(batch.features)
    y = np.asarray(batch.labels)
    d = x.shape[1]
    return np.linalg.solve(x.T @ x + l2 * np.eye(d), x.T @ y)


def test_lbfgs_quadratic_exact():
    center = np.arange(1.0, D + 1)
    res = minimize_lbfgs(_quadratic(center), jnp.zeros((D,)))
    assert int(res.reason) in (
        ConvergenceReason.FUNCTION_VALUES_CONVERGED,
        ConvergenceReason.GRADIENT_CONVERGED,
    )
    np.testing.assert_allclose(res.x, center, atol=1e-6)
    # loss history is monotone non-increasing up to the final iteration
    lh = np.asarray(res.loss_history)[: int(res.iterations) + 1]
    assert np.all(np.diff(lh) <= 1e-12)


def test_lbfgs_ridge_matches_closed_form():
    batch = _ridge_batch()
    l2 = 0.5
    obj = GLMObjective(loss=SquaredLoss, l2_weight=l2)
    res = minimize_lbfgs(
        lambda w: obj.value_and_gradient(w, batch),
        jnp.zeros((D,)),
        OptimizerConfig(tolerance=1e-13),
    )
    np.testing.assert_allclose(res.x, _ridge_closed_form(batch, l2), atol=1e-6)


def test_lbfgs_logistic_gradient_small_at_solution():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(300, D))
    w_true = rng.normal(size=D)
    y = (rng.uniform(size=300) < 1 / (1 + np.exp(-x @ w_true))).astype(float)
    batch = LabeledBatch(
        features=jnp.asarray(x),
        labels=jnp.asarray(y),
        offsets=jnp.zeros((300,)),
        weights=jnp.ones((300,)),
    )
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    res = minimize_lbfgs(
        lambda w: obj.value_and_gradient(w, batch),
        jnp.zeros((D,)),
        OptimizerConfig(tolerance=1e-13),
    )
    g = obj.gradient(res.x, batch)
    assert float(jnp.linalg.norm(g)) < 1e-4


def test_lbfgs_box_constraints():
    center = np.full(D, 2.0)
    lower = jnp.full((D,), -1.0)
    upper = jnp.full((D,), 1.0)
    cfg = OptimizerConfig(lower_bounds=lower, upper_bounds=upper)
    res = minimize_lbfgs(_quadratic(center), jnp.zeros((D,)), cfg)
    np.testing.assert_allclose(res.x, np.ones(D), atol=1e-6)


def test_lbfgs_jit_and_warm_start():
    batch = _ridge_batch()
    obj = GLMObjective(loss=SquaredLoss, l2_weight=0.5)
    solve = jax.jit(
        lambda w0: minimize_lbfgs(
            lambda w: obj.value_and_gradient(w, batch),
            w0,
            OptimizerConfig(tolerance=1e-13),
        )
    )
    cold = solve(jnp.zeros((D,)))
    warm = solve(cold.x)
    # warm start from the solution terminates almost immediately
    assert int(warm.iterations) <= 2
    np.testing.assert_allclose(warm.x, cold.x, atol=1e-5)


def test_owlqn_soft_threshold_orthogonal():
    # With orthonormal design and squared loss, the lasso solution is
    # soft-thresholding of the least-squares solution.
    rng = np.random.default_rng(2)
    q, _ = np.linalg.qr(rng.normal(size=(D, D)))
    x = q.T  # orthonormal rows → X^T X = I
    w_true = np.array([3.0, -2.0, 0.05, 0.0, 1.5, -0.02, 0.8, 0.0])
    y = x @ w_true
    batch = LabeledBatch(
        features=jnp.asarray(x),
        labels=jnp.asarray(y),
        offsets=jnp.zeros((D,)),
        weights=jnp.ones((D,)),
    )
    l1 = 0.1
    obj = GLMObjective(loss=SquaredLoss)
    res = minimize_owlqn(
        lambda w: obj.value_and_gradient(w, batch), jnp.zeros((D,)), l1
    )
    wls = x.T @ y
    expected = np.sign(wls) * np.maximum(np.abs(wls) - l1, 0.0)
    np.testing.assert_allclose(res.x, expected, atol=1e-5)


def test_owlqn_produces_sparsity():
    batch = _ridge_batch(seed=3)
    obj = GLMObjective(loss=SquaredLoss)
    res = minimize_owlqn(
        lambda w: obj.value_and_gradient(w, batch), jnp.zeros((D,)), 50.0
    )
    assert int(jnp.sum(res.x == 0.0)) >= 1


def test_tron_quadratic_one_newton_step():
    center = np.arange(1.0, D + 1)
    res = minimize_tron(_quadratic(center), _quadratic_hvp, jnp.zeros((D,)))
    np.testing.assert_allclose(res.x, center, atol=1e-6)
    assert int(res.iterations) <= 3


def test_tron_ridge_matches_closed_form():
    batch = _ridge_batch(seed=4)
    l2 = 0.5
    obj = GLMObjective(loss=SquaredLoss, l2_weight=l2)
    res = minimize_tron(
        lambda w: obj.value_and_gradient(w, batch),
        lambda w, v: obj.hessian_vector(w, v, batch),
        jnp.zeros((D,)),
        OptimizerConfig(max_iterations=50, tolerance=1e-13),
    )
    np.testing.assert_allclose(res.x, _ridge_closed_form(batch, l2), atol=1e-5)


def test_tron_logistic_converges():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(300, D))
    w_true = rng.normal(size=D)
    y = (rng.uniform(size=300) < 1 / (1 + np.exp(-x @ w_true))).astype(float)
    batch = LabeledBatch(
        features=jnp.asarray(x),
        labels=jnp.asarray(y),
        offsets=jnp.zeros((300,)),
        weights=jnp.ones((300,)),
    )
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    res = minimize_tron(
        lambda w: obj.value_and_gradient(w, batch),
        lambda w, v: obj.hessian_vector(w, v, batch),
        jnp.zeros((D,)),
    )
    g = obj.gradient(res.x, batch)
    assert float(jnp.linalg.norm(g)) < 1e-3


def _plateau(bump):
    """A float32 objective of size 2.7e6 whose true gain over an iteration
    is under the 0.25 its value resolves: a convex quadratic worth at most
    0.18, under a constant. ``bump`` adds a last bit to every value off the
    start point, as a rounded sum of 4 M terms does."""
    big = jnp.float32(2_744_532.0)
    scales = jnp.asarray([1.0, 4.0, 16.0, 64.0, 0.25, 0.0625, 2.0, 8.0], jnp.float32)
    center = jnp.full((D,), 0.3, jnp.float32) / jnp.sqrt(scales)

    def value_and_grad(x):
        d = x - center
        small = 0.5 * jnp.dot(scales * d, d)
        moved = jnp.any(x != 0)
        return big + small + jnp.where(moved, jnp.float32(bump), 0.0), scales * d

    return value_and_grad


@pytest.mark.parametrize("bump", [0.0, 0.25])
def test_lbfgs_crosses_a_rounding_plateau(bump):
    """Where the value cannot resolve an iteration's gain, the search goes
    by the derivative and the solve runs on (optimize/linesearch.py): a
    strict sufficient-decrease test fails every trial once a value reads a
    last bit high, and ends the solve at iteration 0 by rounding."""
    value_and_grad = _plateau(bump)
    config = OptimizerConfig(max_iterations=10, tolerance=-1.0, ls_max_iterations=10)
    res = minimize_lbfgs(value_and_grad, jnp.zeros((D,), jnp.float32), config)
    assert int(res.reason) == ConvergenceReason.MAX_ITERATIONS
    assert int(res.iterations) == 10
    gnorm = np.asarray(res.grad_norm_history)
    assert gnorm[-1] < 0.1 * gnorm[0]


def test_line_search_still_fails_on_a_real_increase():
    """Rounding's allowance is a few last bits of f0 and no more: along an
    ascent direction every trial raises the value by far more, none is
    accepted, and the search reports the failure."""
    from photon_tpu.optimize.linesearch import wolfe_line_search

    value_and_grad = _quadratic(jnp.ones((D,), jnp.float32))
    x0 = jnp.zeros((D,), jnp.float32)
    f0, g0 = value_and_grad(x0)
    res = wolfe_line_search(value_and_grad, x0, g0, f0, g0, max_iterations=10)
    assert not bool(res.success)
    assert float(res.step) == 0.0


def test_vmapped_lbfgs_batch_of_problems():
    # The random-effect pattern: many independent small solves under vmap.
    rng = np.random.default_rng(6)
    centers = jnp.asarray(rng.normal(size=(16, D)))

    def solve(center):
        return minimize_lbfgs(_quadratic(center), jnp.zeros((D,)))

    res = jax.vmap(solve)(centers)
    np.testing.assert_allclose(res.x, centers, atol=1e-5)
    assert res.x.shape == (16, D)


def test_segmented_owlqn_matches_single_program():
    """SegmentedOWLQN (host-re-dispatched bounded segments — the
    preemption-safe driver for long solves) must match the
    single-while-loop solve up to f32 reassociation, reuse its compiled
    segment across calls, and converge by the same criteria."""
    from photon_tpu.optimize.common import ConvergenceReason
    from photon_tpu.optimize.owlqn import SegmentedOWLQN, minimize_owlqn

    rng = np.random.default_rng(11)
    A = jnp.asarray(rng.normal(size=(200, D)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=200).astype(np.float32))

    def vg(x):
        r = A @ x - b
        return 0.5 * jnp.dot(r, r), A.T @ r

    cfg = OptimizerConfig(max_iterations=60, tolerance=1e-9)
    ref = jax.jit(
        lambda x0: minimize_owlqn(vg, x0, 0.3, cfg)
    )(jnp.zeros((D,), jnp.float32))
    solver = SegmentedOWLQN(vg, 0.3, cfg, segment_iters=2)
    seg = solver(jnp.zeros((D,), jnp.float32))
    assert solver.last_num_segments >= 2  # actually segmented
    assert int(seg.reason) != int(ConvergenceReason.NOT_CONVERGED)
    np.testing.assert_allclose(
        np.asarray(ref.x), np.asarray(seg.x), rtol=2e-4, atol=1e-5
    )
    # second call reuses the jit cache (same shapes → no recompile)
    misses_before = solver._segment_f._cache_size()
    seg2 = solver(jnp.full((D,), 0.05, jnp.float32))
    assert solver._segment_f._cache_size() == misses_before
    assert abs(float(seg2.value) - float(seg.value)) <= 1e-4 * abs(
        float(seg.value)
    ) + 1e-6


def test_segmented_owlqn_oracle_factory_data_as_argument():
    """Production path: the batch flows through __call__ as a jit argument
    (oracle built at trace time), matching the closure-based
    minimize_owlqn solve on the same GLM problem."""
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optimize.owlqn import SegmentedOWLQN, minimize_owlqn
    from photon_tpu.types import LabeledBatch

    rng = np.random.default_rng(12)
    x = rng.normal(size=(300, D)).astype(np.float32)
    y = (rng.uniform(size=300) < 0.5).astype(np.float32)
    batch = LabeledBatch(
        features=jnp.asarray(x),
        labels=jnp.asarray(y),
        offsets=jnp.zeros((300,), jnp.float32),
        weights=jnp.ones((300,), jnp.float32),
    )
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5, l1_weight=0.1)
    cfg = OptimizerConfig(max_iterations=40, tolerance=1e-8)
    ref = jax.jit(
        lambda b, x0: minimize_owlqn(
            None, x0, 0.1, cfg, oracle=obj.smooth_margin_oracle(b)
        )
    )(batch, jnp.zeros((D,), jnp.float32))
    solver = SegmentedOWLQN(
        None, 0.1, cfg,
        oracle_factory=obj.smooth_margin_oracle, segment_iters=4,
    )
    seg = solver(jnp.zeros((D,), jnp.float32), batch)
    np.testing.assert_allclose(
        np.asarray(ref.x), np.asarray(seg.x), rtol=5e-4, atol=1e-5
    )


def test_segmented_owlqn_step_handle_is_the_call_in_pieces():
    """``start``/``advance``/``finish`` are what ``__call__`` runs: the same
    bits as ``__call__`` and as the private trio driven by hand, one
    read-back a segment, and one host span a piece."""
    from photon_tpu import obs
    from photon_tpu.optimize.common import ConvergenceReason
    from photon_tpu.optimize.owlqn import SegmentedOWLQN

    rng = np.random.default_rng(13)
    A = jnp.asarray(rng.normal(size=(200, D)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=200).astype(np.float32))

    def vg(x):
        r = A @ x - b
        return 0.5 * jnp.dot(r, r), A.T @ r

    cfg = OptimizerConfig(max_iterations=60, tolerance=1e-9)
    solver = SegmentedOWLQN(vg, 0.3, cfg, segment_iters=2)
    x0 = jnp.zeros((D,), jnp.float32)
    whole = solver(x0)
    n_seg = solver.last_num_segments
    assert n_seg >= 2

    s = solver._init_f(x0, ())
    by_hand = 0
    while int(s.reason) == int(ConvergenceReason.NOT_CONVERGED):
        s = solver._segment_f(s, ())
        by_hand += 1
    trio = solver._final_f(s, ())
    assert by_hand == n_seg

    obs.reset()
    obs.enable()
    try:
        state = solver.start(x0)
        seen = []
        while not seen or not seen[-1].done:
            state, progress = solver.advance(state)
            seen.append(progress)
        stepped = solver.finish(state)
        spans = [r.name for r in obs.get_tracer().spans()]
    finally:
        obs.reset()
        obs.disable()
    assert len(seen) == n_seg
    assert [p.done for p in seen] == [False] * (n_seg - 1) + [True]
    assert seen[-1].iterations == int(whole.iterations)
    assert seen[-1].n_evals == int(whole.n_evals)
    assert seen[-1].reason == int(whole.reason)
    assert seen[-1].value == float(whole.value)
    assert all(a.iterations < b.iterations for a, b in zip(seen, seen[1:]))
    for got in (trio, stepped):
        for name in whole._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)), np.asarray(getattr(whole, name))
            )
    assert {n: spans.count(n) for n in set(spans)} == {
        "owlqn.init": 1, "owlqn.segment": n_seg, "owlqn.sync": n_seg,
        "owlqn.final": 1,
    }
