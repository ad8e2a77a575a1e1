"""Sparse (padded-ELL) feature-path tests.

The reference's compute kernel preserves sparsity end-to-end
(ValueAndGradientAggregator.scala:36-80 streams over SparseVector actives;
AvroDataReader.scala:85-246 produces SparseVectors). The TPU equivalent is
the gather/segment-sum objective over ``SparseBatch``: these tests pin
sparse == dense numerics for every objective quantity, solver convergence on
a config-3-shaped Poisson elastic-net problem, sharded == unsharded under
the mesh, and the AUTO layout rule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import (
    DataSet,
    choose_sparse,
    to_device_batch,
    to_device_sparse_batch,
)
from photon_tpu.game.config import (
    FeatureRepresentation,
    FixedEffectCoordinateConfig,
)
from photon_tpu.game.coordinate import FixedEffectCoordinate
from photon_tpu.game.data import CSRMatrix, GameData
from photon_tpu.model_training import train_glm_grid
from photon_tpu.ops.losses import LogisticLoss, PoissonLoss, SquaredLoss
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.parallel.mesh import make_mesh, shard_batch
from photon_tpu.types import (
    LabeledBatch,
    NormalizationType,
    OptimizerType,
    SparseBatch,
    TaskType,
)


def _sparse_dataset(seed=0, n=96, d=40, row_nnz=6, poisson=False):
    """Random CSR dataset with ``row_nnz`` actives/row (plus intercept col 0)."""
    rng = np.random.default_rng(seed)
    indptr = np.arange(n + 1, dtype=np.int64) * row_nnz
    # distinct column draws per row: first col is the intercept
    cols = np.stack(
        [
            np.concatenate(([0], rng.choice(np.arange(1, d), row_nnz - 1, False)))
            for _ in range(n)
        ]
    )
    cols.sort(axis=1)
    indices = cols.reshape(-1).astype(np.int32)
    values = rng.normal(size=n * row_nnz)
    values[indptr[:-1] - 0] = 1.0  # intercept value
    w_true = rng.normal(size=d) * 0.3
    dense = np.zeros((n, d))
    dense[np.repeat(np.arange(n), row_nnz), indices] = values
    margin = dense @ w_true
    if poisson:
        labels = rng.poisson(np.exp(np.clip(margin, -3, 3))).astype(np.float64)
    else:
        labels = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
            np.float64
        )
    return DataSet(
        indptr=indptr,
        indices=indices,
        values=values,
        labels=labels,
        offsets=rng.normal(scale=0.1, size=n),
        weights=rng.uniform(0.5, 2.0, size=n),
        num_features=d,
    )


def _both_batches(data: DataSet):
    dense = to_device_batch(data, dtype=jnp.float64, pad_to_multiple=8)
    sparse = to_device_sparse_batch(data, dtype=jnp.float64, pad_to_multiple=8)
    assert dense.features.shape[0] == sparse.indices.shape[0]
    return dense, sparse


def test_ell_layout_roundtrip():
    data = _sparse_dataset(seed=1)
    sparse = to_device_sparse_batch(data, dtype=jnp.float64)
    # scatter the ELL slots back to dense and compare
    n = sparse.indices.shape[0]
    dense = np.zeros((n, data.num_features))
    rows = np.repeat(np.arange(n), sparse.indices.shape[1])
    np.add.at(
        dense,
        (rows, np.asarray(sparse.indices).reshape(-1)),
        np.asarray(sparse.values).reshape(-1),
    )
    np.testing.assert_allclose(
        dense[: data.num_samples], data.to_dense(np.float64)
    )


@pytest.mark.parametrize(
    "loss", [LogisticLoss, SquaredLoss, PoissonLoss], ids=lambda l: l.name
)
@pytest.mark.parametrize("normalized", [False, True])
def test_sparse_objective_matches_dense(loss, normalized):
    data = _sparse_dataset(seed=2, poisson=loss is PoissonLoss)
    d = data.num_features
    dense, sparse = _both_batches(data)
    ctx = NormalizationContext()
    if normalized:
        x = data.to_dense(np.float64)
        ctx = NormalizationContext.build(
            NormalizationType.STANDARDIZATION,
            mean=x.mean(axis=0),
            variance=x.var(axis=0) + 0.5,
            intercept_index=0,
            dtype=jnp.float64,
        )
    obj = GLMObjective(loss=loss, l2_weight=0.2, normalization=ctx)
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    v = jnp.asarray(rng.normal(size=d))

    np.testing.assert_allclose(
        obj.value(w, sparse), obj.value(w, dense), rtol=1e-8
    )
    vd, gd = obj.value_and_gradient(w, dense)
    vs, gs = obj.value_and_gradient(w, sparse)
    np.testing.assert_allclose(vs, vd, rtol=1e-8)
    np.testing.assert_allclose(gs, gd, rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(
        obj.hessian_vector(w, v, sparse),
        obj.hessian_vector(w, v, dense),
        rtol=1e-9,
        atol=1e-11,
    )
    np.testing.assert_allclose(
        obj.hessian_diagonal(w, sparse),
        obj.hessian_diagonal(w, dense),
        rtol=1e-9,
        atol=1e-11,
    )
    np.testing.assert_allclose(
        obj.hessian_matrix(w, sparse),
        obj.hessian_matrix(w, dense),
        rtol=1e-9,
        atol=1e-11,
    )


def test_sparse_poisson_elastic_net_solve_matches_dense():
    """Config-3-shaped solve (Poisson, elastic net → OWLQN) on both layouts."""
    data = _sparse_dataset(seed=4, n=128, d=32, poisson=True)
    cfg = GLMProblemConfig(
        task=TaskType.POISSON_REGRESSION,
        optimizer=OptimizerType.OWLQN,
        regularization=RegularizationContext(
            RegularizationType.ELASTIC_NET, elastic_net_alpha=0.5
        ),
    )
    dense, sparse = _both_batches(data)
    m_dense = train_glm_grid(dense, cfg, [40.0, 0.1], dtype=jnp.float64)
    m_sparse = train_glm_grid(
        sparse, cfg, [40.0, 0.1], dtype=jnp.float64, num_features=32
    )
    for md, ms in zip(m_dense, m_sparse):
        np.testing.assert_allclose(
            ms.model.coefficients.means,
            md.model.coefficients.means,
            rtol=1e-6,
            atol=1e-8,
        )
        # elastic net actually sparsifies
    assert np.mean(np.asarray(m_sparse[0].model.coefficients.means) == 0) > 0.1


def test_sparse_batch_requires_num_features():
    data = _sparse_dataset(seed=5)
    sparse = to_device_sparse_batch(data, dtype=jnp.float64)
    with pytest.raises(ValueError, match="num_features"):
        train_glm_grid(sparse, GLMProblemConfig(), [0.0])


def test_auto_layout_rule():
    # small/dense stays dense regardless of density
    assert not choose_sparse(1000, 100, 5000)
    # huge and sparse flips
    assert choose_sparse(1_000_000, 1_000_000, 50_000_000)
    # huge but dense stays dense
    assert not choose_sparse(1 << 20, 1 << 12, (1 << 32) // 2)


def test_sparse_sharded_equals_unsharded():
    """Gather/segment-sum reductions under the mesh must psum to the same
    numbers as the single-device path (test_distributed.py analogue)."""
    data = _sparse_dataset(seed=6, n=160)
    d = data.num_features
    sparse = to_device_sparse_batch(data, dtype=jnp.float64, pad_to_multiple=8)
    mesh = make_mesh()
    sharded = shard_batch(sparse, mesh)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.1)
    w = jnp.asarray(np.random.default_rng(7).normal(size=d) * 0.1)

    @jax.jit
    def vg(w, b):
        return obj.value_and_gradient(w, b)

    v1, g1 = vg(w, sparse)
    v2, g2 = vg(w, sharded)
    np.testing.assert_allclose(v2, v1, rtol=1e-12)
    np.testing.assert_allclose(g2, g1, rtol=1e-11, atol=1e-13)


def test_fixed_effect_coordinate_sparse_matches_dense():
    data = _sparse_dataset(seed=8, n=120, d=24)
    shard = CSRMatrix(
        indptr=data.indptr,
        indices=data.indices,
        values=data.values,
        num_cols=data.num_features,
    )
    game = GameData.build(
        feature_shards={"s": shard},
        labels=data.labels,
        offsets=data.offsets,
        weights=data.weights,
    )
    opt = GLMProblemConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.5,
    )
    out = {}
    for rep in (FeatureRepresentation.DENSE, FeatureRepresentation.SPARSE):
        cfg = FixedEffectCoordinateConfig(
            feature_shard="s",
            optimization=opt,
            regularization_weights=(0.5,),
            representation=rep,
        )
        coord = FixedEffectCoordinate.build(game, cfg, dtype=jnp.float64)
        expected = rep == FeatureRepresentation.SPARSE
        assert isinstance(coord.batch, SparseBatch) == expected
        assert isinstance(coord.batch, LabeledBatch) != expected
        w, _ = coord.train(jnp.zeros(len(data.labels)), coord.initial_state())
        out[rep] = (np.asarray(w), np.asarray(coord.score(w)))
    np.testing.assert_allclose(
        out[FeatureRepresentation.SPARSE][0],
        out[FeatureRepresentation.DENSE][0],
        rtol=1e-7,
        atol=1e-9,
    )
    np.testing.assert_allclose(
        out[FeatureRepresentation.SPARSE][1],
        out[FeatureRepresentation.DENSE][1],
        rtol=1e-7,
        atol=1e-9,
    )
