"""Mid-descent checkpoint/resume (SURVEY §5.3: the reference delegates
recovery to Spark task retry + lineage; the TPU-native story is optimizer-
state checkpointing with bit-identical resume)."""
import numpy as np
import pytest

import jax.numpy as jnp

import photon_tpu.game.estimator as estimator_mod
from photon_tpu.evaluation.evaluators import EvaluatorType
from photon_tpu.game.checkpoint import DescentCheckpointer
from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.data import CSRMatrix, GameData
from photon_tpu.game.descent import run_coordinate_descent
from photon_tpu.game.estimator import GameEstimator
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType


def _game_data(n=400, d_fe=12, d_re=4, users=25, seed=0):
    rng = np.random.default_rng(seed)
    x_fe = rng.normal(size=(n, d_fe))
    x_re = rng.normal(size=(n, d_re))
    uid = np.concatenate(
        [np.arange(users), rng.integers(0, users, size=n - users)]
    )
    y = (rng.uniform(size=n) > 0.5).astype(np.float64)
    return GameData.build(
        labels=y,
        feature_shards={
            "fe": CSRMatrix.from_dense(x_fe),
            "re": CSRMatrix.from_dense(x_re),
        },
        id_tags={"userId": uid},
    )


def _estimator(grid=(1.0, 0.1), iters=3):
    opt = GLMProblemConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        regularization=RegularizationContext(
            regularization_type=RegularizationType.L2
        ),
        optimizer_config=OptimizerConfig(
            max_iterations=5, ls_max_iterations=4
        ),
    )
    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectCoordinateConfig(
                feature_shard="fe",
                optimization=opt,
                regularization_weights=grid,
            ),
            "per-user": RandomEffectCoordinateConfig(
                random_effect_type="userId",
                feature_shard="re",
                optimization=opt,
                regularization_weights=grid,
            ),
        },
        update_sequence=["fixed", "per-user"],
        descent_iterations=iters,
        validation_evaluator=EvaluatorType.AUC,
        dtype=jnp.float32,
    )


def _model_arrays(model):
    out = {"fixed": np.asarray(model["fixed"].model.coefficients.means)}
    re = model["per-user"]
    for b, bucket in enumerate(re.buckets):
        out[f"re/{b}"] = np.asarray(bucket.coefficients)
    return out


def _assert_models_identical(a, b):
    arrays_a, arrays_b = _model_arrays(a), _model_arrays(b)
    assert arrays_a.keys() == arrays_b.keys()
    for k in arrays_a:
        np.testing.assert_array_equal(arrays_a[k], arrays_b[k], err_msg=k)


class _KillAfterSweep(Exception):
    pass


def test_kill_and_resume_bit_identical(tmp_path):
    """A run killed after the first sweep of grid point 0 and resumed from
    its checkpoint must produce bit-identical models to an uninterrupted
    run, across the remaining sweeps AND the λ-grid warm start."""
    data = _game_data(seed=1)
    val = _game_data(seed=2)
    ckpt_dir = str(tmp_path / "ckpt")

    # uninterrupted baseline
    res_a = _estimator().fit(data, validation_data=val)
    assert len(res_a) == 2

    # interrupted run: raise out of fit after sweep 0 of grid 0 completes
    # (the checkpoint for that sweep is already on disk)
    real_rcd = estimator_mod.run_coordinate_descent

    def killing_rcd(*args, **kwargs):
        inner = kwargs.get("sweep_callback")
        assert inner is not None  # checkpointing must be wired

        def cb(it, st, bs, bm):
            inner(it, st, bs, bm)
            raise _KillAfterSweep()

        kwargs["sweep_callback"] = cb
        return real_rcd(*args, **kwargs)

    estimator_mod.run_coordinate_descent = killing_rcd
    try:
        with pytest.raises(_KillAfterSweep):
            _estimator().fit(
                data, validation_data=val, checkpoint_dir=ckpt_dir
            )
    finally:
        estimator_mod.run_coordinate_descent = real_rcd

    ckpt = DescentCheckpointer(ckpt_dir).load()
    assert ckpt is not None
    assert (ckpt.grid_index, ckpt.iteration) == (0, 0)

    # resume: picks up at sweep 1 of grid 0, then grid 1
    res_b = _estimator().fit(
        data, validation_data=val, checkpoint_dir=ckpt_dir
    )
    assert len(res_b) == 2 and all(r is not None for r in res_b)
    for a, b in zip(res_a, res_b):
        _assert_models_identical(a.model, b.model)
        assert a.evaluation == b.evaluation

    # resume after FULL completion trains nothing and returns placeholders
    res_c = _estimator().fit(
        data, validation_data=val, checkpoint_dir=ckpt_dir
    )
    assert res_c == [None, None]


def test_kill_between_grid_points_resumes_with_warm_start(tmp_path):
    """Killing after grid point 0 completes must resume directly into grid
    point 1 with grid 0's final states as the warm start."""
    data = _game_data(seed=3)
    ckpt_dir = str(tmp_path / "ckpt")

    res_a = _estimator().fit(data)

    class _Stop(Exception):
        pass

    def killer(gi, result):
        if gi == 0:
            raise _Stop()

    with pytest.raises(_Stop):
        _estimator().fit(data, checkpoint_dir=ckpt_dir, grid_callback=killer)

    # grid 0 completed; mark_grid_done ran before grid_callback? It runs
    # after — so the checkpoint is the last sweep of grid 0. Either way the
    # resumed run must reproduce grid 1 exactly.
    res_b = _estimator().fit(data, checkpoint_dir=ckpt_dir)
    assert res_b[-1] is not None
    _assert_models_identical(res_a[-1].model, res_b[-1].model)


def test_sweep_level_resume_unit(tmp_path):
    """run_coordinate_descent(start_iteration=k) continues exactly where a
    full run's k-th sweep left off (states captured via sweep_callback)."""
    data = _game_data(seed=4)
    est = _estimator(grid=(1.0,), iters=3)
    coords = est.build(data).coordinates

    captured = {}

    def capture(it, st, bs, bm):
        captured[it] = {
            k: (
                [np.asarray(x) for x in v]
                if isinstance(v, list)
                else np.asarray(v)
            )
            for k, v in st.items()
        }

    full = run_coordinate_descent(
        coords, ["fixed", "per-user"], 3, sweep_callback=capture
    )
    assert set(captured) == {0, 1, 2}

    est2 = _estimator(grid=(1.0,), iters=3)
    coords2 = est2.build(data).coordinates
    resumed = run_coordinate_descent(
        coords2,
        ["fixed", "per-user"],
        3,
        initial_states={
            k: (
                [jnp.asarray(x) for x in v]
                if isinstance(v, list)
                else jnp.asarray(v)
            )
            for k, v in captured[0].items()
        },
        start_iteration=1,
    )
    np.testing.assert_array_equal(
        np.asarray(full.states["fixed"]), np.asarray(resumed.states["fixed"])
    )
    for a, b in zip(full.states["per-user"], resumed.states["per-user"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
