"""Regression guard: hot jit programs must not embed data as constants.

Closed-over arrays (numpy or jax.Array) lower as HLO literal constants.
The data is then serialized INTO the module: hundreds of MB that the
compiler parses, hashes for the cache and keeps, per program. The
contract is that batches/buckets/index streams ride as
jit ARGUMENTS; this test traces each hot entry point and fails if any
jaxpr constant is larger than a scalar-ish epsilon, naming the offender.

The pass itself (the recursive const walker and the size check) lives in
photon_tpu.analysis.hlo — shared with the audit that runs over every
AOT-precompiled executable (`python -m photon_tpu.analysis --programs`);
this file keeps the hand-picked high-value traces as named regressions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.analysis.hlo import (
    DEFAULT_CONST_BYTES_LIMIT as _CONST_BYTES_LIMIT,
    check_jaxpr_const_embedding,
    collect_jaxpr_consts,
)
from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.coordinate import build_coordinate
from photon_tpu.game.data import GameData
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType


def _assert_no_large_consts(jaxpr, label):
    findings = check_jaxpr_const_embedding(jaxpr, label, _CONST_BYTES_LIMIT)
    assert not findings, "\n".join(f.render() for f in findings)


def test_guard_detects_planted_closure_constant():
    """Meta-test: the walker must SEE a closure constant inside a jitted
    callee — otherwise every other test in this file is vacuous."""
    big = jnp.asarray(np.random.default_rng(0).normal(size=(64, 1024)),
                      jnp.float32)  # 256 KB > limit

    @jax.jit
    def leaky(v):
        return jnp.sum(big * v)

    jaxpr = jax.make_jaxpr(lambda v: leaky(v))(jnp.float32(2.0))
    consts: list = []
    collect_jaxpr_consts(jaxpr, consts)
    sizes = [np.asarray(c).nbytes for c in consts if hasattr(c, "nbytes")]
    assert any(s > _CONST_BYTES_LIMIT for s in sizes), (
        "guard walker failed to find the planted 256 KB closure constant — "
        "the embedding checks below prove nothing"
    )


def _game_fixture(n=512, fe_dim=64, users=32, d_re=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, fe_dim)).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float64)
    ids = rng.integers(0, users, size=n)
    from photon_tpu.game.data import CSRMatrix

    x_re = rng.normal(size=(n, d_re)).astype(np.float32)
    data = GameData.build(
        labels=labels,
        feature_shards={
            "global": CSRMatrix.from_dense(x),
            "per_user": CSRMatrix.from_dense(x_re),
        },
        id_tags={"userId": [f"u{i}" for i in ids]},
    )
    opt = GLMProblemConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer_config=OptimizerConfig(max_iterations=3),
        regularization=RegularizationContext(RegularizationType.L2),
    )
    fe_cfg = FixedEffectCoordinateConfig(
        feature_shard="global", optimization=opt,
        regularization_weights=(1.0,),
    )
    re_cfg = RandomEffectCoordinateConfig(
        random_effect_type="userId", feature_shard="per_user",
        optimization=opt, regularization_weights=(1.0,),
    )
    return data, fe_cfg, re_cfg


def _assert_fe_coordinate_clean(coord, num_samples, label):
    residual = jnp.zeros((num_samples,), jnp.float32)
    w0 = coord.initial_state()
    reg = jnp.asarray(1.0, jnp.float32)
    norm = coord._norm_args()
    jaxpr = jax.make_jaxpr(
        lambda b, nrm, r, w, g: coord._train_jit(b, nrm, r, w, g)
    )(coord.batch, norm, residual, w0, reg)
    _assert_no_large_consts(jaxpr, f"{label}._train_jit")
    jaxpr = jax.make_jaxpr(lambda b, nrm, s: coord._score_jit(b, nrm, s))(
        coord.batch, norm, w0
    )
    _assert_no_large_consts(jaxpr, f"{label}._score_jit")


def test_fe_train_and_score_take_batch_as_argument():
    data, fe_cfg, _ = _game_fixture()
    coord = build_coordinate(data, fe_cfg)
    _assert_fe_coordinate_clean(
        coord, data.num_samples, "FixedEffectCoordinate"
    )


def test_fe_normalization_arrays_are_arguments_not_constants():
    """Non-identity NormalizationContext: factors/shifts are length-D
    device arrays — read through static self they lower as HLO literal
    constants (ADVICE r4 medium). They must ride as traced arguments,
    same contract as the batch. The fixture dim is sized so the
    factors/shifts arrays alone exceed the const-bytes limit."""
    from photon_tpu.ops.normalization import NormalizationContext
    from photon_tpu.types import NormalizationType

    fe_dim = 8192  # 32 KB f32 factors > _CONST_BYTES_LIMIT
    data, fe_cfg, _ = _game_fixture(n=64, fe_dim=fe_dim)
    rng = np.random.default_rng(3)
    norm = NormalizationContext.build(
        NormalizationType.STANDARDIZATION,
        mean=rng.normal(size=fe_dim),
        variance=rng.uniform(0.5, 2.0, size=fe_dim),
        intercept_index=0,
    )
    coord = build_coordinate(data, fe_cfg, normalization=norm)
    _assert_fe_coordinate_clean(
        coord, data.num_samples, "FixedEffectCoordinate[standardized]"
    )


def test_re_bucket_train_takes_buckets_as_arguments():
    from photon_tpu.game.data import build_random_effect_dataset

    data, _, re_cfg = _game_fixture()
    ds = build_random_effect_dataset(data, re_cfg)
    coord = build_coordinate(data, re_cfg, re_dataset=ds)
    residual = jnp.zeros((data.num_samples,), jnp.float32)
    state = coord.initial_state()
    db = coord.device_buckets[0]
    reg = jnp.asarray(1.0, jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda f, l, o, tw, r, sp, w0, g: coord._train_bucket(
            f, l, o, tw, r, sp, w0, g
        )
    )(
        db.features, db.labels, db.offsets, db.train_weights,
        residual, db.sample_pos, state[0], reg,
    )
    _assert_no_large_consts(jaxpr, "RandomEffectCoordinate._train_bucket")


def test_segmented_owlqn_programs_take_data_as_argument():
    from photon_tpu.ops.losses import PoissonLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optimize.owlqn import SegmentedOWLQN
    from photon_tpu.types import SparseBatch

    rng = np.random.default_rng(1)
    n, d, k = 256, 512, 8
    batch = SparseBatch(
        indices=jnp.asarray(rng.integers(0, d, size=(n, k)), jnp.int32),
        values=jnp.asarray(rng.normal(size=(n, k)), jnp.float32),
        labels=jnp.asarray(rng.poisson(1.0, size=n), jnp.float32),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
        windows=None,
    )
    obj = GLMObjective(loss=PoissonLoss, l2_weight=0.1, l1_weight=0.01)
    solver = SegmentedOWLQN(
        None, 0.01, OptimizerConfig(max_iterations=4),
        oracle_factory=obj.smooth_margin_oracle, segment_iters=2,
    )
    x0 = jnp.zeros((d,), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, b: solver._init_f(x, b))(x0, batch)
    _assert_no_large_consts(jaxpr, "SegmentedOWLQN.init")
    s = solver._init_f(x0, batch)
    jaxpr = jax.make_jaxpr(lambda ss, b: solver._segment_f(ss, b))(s, batch)
    _assert_no_large_consts(jaxpr, "SegmentedOWLQN.segment")
