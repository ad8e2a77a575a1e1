"""Margin-space line search (DirectionalOracle) vs the black-box search.

The GLM oracle must reproduce the black-box L-BFGS solve — the same
Wolfe decisions driven by f/dphi computed from carried margins instead of
full feature passes (ops/objective.GLMObjective.directional_oracle,
optimize/lbfgs.py).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.ops.losses import LogisticLoss, PoissonLoss
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optimize import OptimizerConfig, minimize_lbfgs
from photon_tpu.types import LabeledBatch


def _batch(rng, n, d, poisson=False):
    x = rng.standard_normal((n, d)).astype(np.float32)
    x[:, 0] = 1.0
    w = rng.standard_normal(d).astype(np.float32) * 0.4
    z = x @ w
    if poisson:
        y = rng.poisson(np.exp(np.clip(z - 1.0, -4, 3))).astype(np.float32)
    else:
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    return LabeledBatch(
        features=jnp.asarray(x),
        labels=jnp.asarray(y),
        offsets=jnp.asarray(0.1 * rng.standard_normal(n).astype(np.float32)),
        weights=jnp.asarray(
            rng.uniform(0.5, 2.0, size=n).astype(np.float32)
        ),
    )


@pytest.mark.parametrize("from_zero", [True, False])
@pytest.mark.parametrize("poisson", [False, True])
@pytest.mark.parametrize("normalized", [False, True])
def test_oracle_matches_blackbox(poisson, normalized, from_zero):
    rng = np.random.default_rng(0)
    n, d = 400, 24
    batch = _batch(rng, n, d, poisson=poisson)
    norm = NormalizationContext()
    if normalized:
        shifts = 0.2 * rng.standard_normal(d).astype(np.float32)
        factors = (1.0 + 0.2 * rng.uniform(size=d)).astype(np.float32)
        shifts[0], factors[0] = 0.0, 1.0
        norm = NormalizationContext(
            factors=jnp.asarray(factors),
            shifts=jnp.asarray(shifts),
            intercept_index=0,
        )
    loss = PoissonLoss if poisson else LogisticLoss
    obj = GLMObjective(loss=loss, l2_weight=0.7, normalization=norm)
    cfg = OptimizerConfig(max_iterations=60, tolerance=1e-8)
    w0 = jnp.zeros((d,), jnp.float32)
    if not from_zero:
        w0 = w0.at[1].set(0.05)

    res_full = minimize_lbfgs(
        lambda w: obj.value_and_gradient(w, batch), w0, cfg
    )
    res_m = minimize_lbfgs(
        None, w0, cfg, oracle=obj.directional_oracle(batch)
    )
    assert float(res_m.value) == pytest.approx(
        float(res_full.value), rel=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(res_m.x), np.asarray(res_full.x), rtol=5e-3, atol=5e-4
    )
    # the point of the oracle: feature passes bounded by 2/iteration + the
    # start + one final exact re-evaluation (drift bound), independent of
    # line-search trial count. The start is counted as it ran: the zero
    # point's backward pass alone where the solve starts there, and the
    # start point's two passes on top where it does not
    start = 1 if from_zero else 3
    assert int(res_m.n_feature_passes) == start + 2 * int(res_m.iterations) + 2
    assert int(res_full.n_feature_passes) == 2 * int(res_full.n_evals)


def test_oracle_under_vmap():
    """Per-entity batched solves (the RE path) with the oracle: every lane
    converges to its own solution, matching per-lane black-box solves."""
    rng = np.random.default_rng(1)
    e, n, d = 5, 60, 6
    feats = rng.standard_normal((e, n, d)).astype(np.float32)
    labels = (rng.uniform(size=(e, n)) > 0.5).astype(np.float32)
    weights = np.ones((e, n), dtype=np.float32)
    offsets = np.zeros((e, n), dtype=np.float32)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    cfg = OptimizerConfig(max_iterations=25)

    def solve_oracle(f, y, o, w):
        b = LabeledBatch(features=f, labels=y, offsets=o, weights=w)
        return minimize_lbfgs(
            None,
            jnp.zeros((d,), jnp.float32),
            cfg,
            oracle=obj.directional_oracle(b),
        ).x

    xs = jax.vmap(solve_oracle)(
        jnp.asarray(feats),
        jnp.asarray(labels),
        jnp.asarray(offsets),
        jnp.asarray(weights),
    )
    for i in range(e):
        b = LabeledBatch(
            features=jnp.asarray(feats[i]),
            labels=jnp.asarray(labels[i]),
            offsets=jnp.asarray(offsets[i]),
            weights=jnp.asarray(weights[i]),
        )
        ref = minimize_lbfgs(
            lambda w: obj.value_and_gradient(w, b), jnp.zeros((d,)), cfg
        )
        np.testing.assert_allclose(
            np.asarray(xs[i]), np.asarray(ref.x), rtol=5e-3, atol=5e-4
        )


def test_oracle_with_box_constraints():
    """Projection breaks the affine-margin assumption mid-iteration; the
    box path re-evaluates fully and must still satisfy the bounds."""
    rng = np.random.default_rng(2)
    batch = _batch(rng, 300, 10)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.1)
    lo = jnp.full((10,), -0.05)
    hi = jnp.full((10,), 0.05)
    cfg = OptimizerConfig(
        max_iterations=30, lower_bounds=lo, upper_bounds=hi
    )
    res = minimize_lbfgs(
        None,
        jnp.zeros((10,)),
        cfg,
        oracle=obj.directional_oracle(batch),
    )
    x = np.asarray(res.x)
    assert np.all(x >= -0.05 - 1e-6) and np.all(x <= 0.05 + 1e-6)
    res_full = minimize_lbfgs(
        lambda w: obj.value_and_gradient(w, batch),
        jnp.zeros((10,)),
        cfg,
    )
    assert float(res.value) == pytest.approx(float(res_full.value), rel=1e-4)


@pytest.mark.parametrize("from_zero", [True, False])
def test_owlqn_value_only_trials_match_blackbox(from_zero):
    """OWLQN's SmoothMarginOracle (value-only trials, gradient from carried
    margins) reproduces the black-box solve, including the sparsity
    pattern, and tracks passes = trials + 1 per iteration."""
    from photon_tpu.optimize import minimize_owlqn

    rng = np.random.default_rng(5)
    n, d = 500, 32
    batch = _batch(rng, n, d)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.05, l1_weight=0.1)
    cfg = OptimizerConfig(max_iterations=50)
    w0 = jnp.zeros((d,), jnp.float32)
    if not from_zero:
        w0 = w0.at[1].set(0.05)

    res_full = minimize_owlqn(
        lambda w: obj.value_and_gradient(w, batch), w0, 0.1, cfg
    )
    res_m = minimize_owlqn(
        None, w0, 0.1, cfg, oracle=obj.smooth_margin_oracle(batch)
    )
    assert float(res_m.value) == pytest.approx(
        float(res_full.value), rel=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(res_m.x), np.asarray(res_full.x), rtol=5e-3, atol=5e-4
    )
    np.testing.assert_array_equal(
        np.asarray(res_m.x) == 0.0, np.asarray(res_full.x) == 0.0
    )
    # value-only trials: passes strictly below the black-box 2-per-trial;
    # the start counted as it ran (1 from zero, 3 from elsewhere)
    start = 1 if from_zero else 3
    assert int(res_m.n_feature_passes) == start + int(res_m.n_evals) - 2 + int(
        res_m.iterations
    )
    assert int(res_full.n_feature_passes) == 4 + 2 * (
        int(res_full.n_evals) - 2
    )


def test_owlqn_oracle_with_box_constraints():
    from photon_tpu.optimize import minimize_owlqn

    rng = np.random.default_rng(6)
    batch = _batch(rng, 300, 12)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.05, l1_weight=0.05)
    lo, hi = jnp.full((12,), -0.04), jnp.full((12,), 0.04)
    cfg = OptimizerConfig(max_iterations=30, lower_bounds=lo, upper_bounds=hi)
    res = minimize_owlqn(
        None,
        jnp.zeros((12,)),
        0.05,
        cfg,
        oracle=obj.smooth_margin_oracle(batch),
    )
    x = np.asarray(res.x)
    assert np.all(x >= -0.04 - 1e-6) and np.all(x <= 0.04 + 1e-6)
    ref = minimize_owlqn(
        lambda w: obj.value_and_gradient(w, batch),
        jnp.zeros((12,)),
        0.05,
        cfg,
    )
    assert float(res.value) == pytest.approx(float(ref.value), rel=1e-4)


def test_oracle_sparse_batch_with_windows():
    """Sparse FE solve: oracle margins via ELL gather, accepted gradient
    via the windowed backward."""
    from photon_tpu.ops.sparse_windows import build_column_windows
    from photon_tpu.types import SparseBatch

    rng = np.random.default_rng(3)
    n, k, d = 300, 5, 256
    idx = rng.integers(1, d, size=(n, k)).astype(np.int32)
    idx[:, 0] = 0
    val = (rng.standard_normal((n, k)) / np.sqrt(k)).astype(np.float32)
    val[:, 0] = 1.0
    y = (rng.uniform(size=n) > 0.5).astype(np.float32)

    def mk(windows):
        return SparseBatch(
            indices=jnp.asarray(idx),
            values=jnp.asarray(val),
            labels=jnp.asarray(y),
            offsets=jnp.zeros((n,), jnp.float32),
            weights=jnp.ones((n,), jnp.float32),
            windows=windows,
        )

    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5)
    cfg = OptimizerConfig(max_iterations=40)
    res_plain = minimize_lbfgs(
        lambda w: obj.value_and_gradient(w, mk(None)),
        jnp.zeros((d,), jnp.float32),
        cfg,
    )
    windows = build_column_windows(idx, val, d, window=64)
    res_m = minimize_lbfgs(
        None,
        jnp.zeros((d,), jnp.float32),
        cfg,
        oracle=obj.directional_oracle(mk(windows)),
    )
    assert float(res_m.value) == pytest.approx(
        float(res_plain.value), rel=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(res_m.x), np.asarray(res_plain.x), rtol=5e-3, atol=5e-4
    )
