"""Compile-bill governance contracts (shape budget + AOT precompile +
compile telemetry; game/data.py ShapePool, game/descent.py
precompile_coordinates/estimate_compile_bill, util/compile_watch.py).

Pins the PR-3 tentpole claims:
1. SHAPE BUDGET — the row-level DP honors a distinct-shape cap, and the
   cross-coordinate ShapePool makes coordinates share ONE level set so
   the global distinct (rows, d) shape count strictly drops versus
   per-coordinate level sets.
2. PRECOMPILE — the parallel AOT pass compiles every hot-path program
   up front (pool wall below the serial-equivalent sum), descent then
   dispatches the stored executables with ZERO further backend
   compiles, and results stay bit-exact against the plain jit path.
3. TELEMETRY — compile_watch counts backend compiles and cache
   outcomes; the descent tracker's per-sweep rows carry the compile
   split and show a compile-free steady state.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_tpu.game.data import (
    CSRMatrix,
    GameData,
    ShapePool,
    _optimal_row_levels,
    build_random_effect_dataset,
    profile_random_effect_shapes,
)
from photon_tpu.game.descent import (
    estimate_compile_bill,
    precompile_coordinates,
    run_coordinate_descent,
)
from photon_tpu.game.estimator import GameEstimator
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType
from photon_tpu.util import compile_watch


def _opt(max_iterations=5):
    return GLMProblemConfig(
        task=TaskType.LINEAR_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        optimizer_config=OptimizerConfig(max_iterations=max_iterations),
    )


def _game_data(seed=0, n=600, d_fe=6, d_re=4, tags=("userId",), sizes=(50,)):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d_fe))
    y = x @ rng.normal(size=d_fe) * 0.3 + rng.normal(size=n) * 0.1
    shards = {"g": CSRMatrix.from_dense(x)}
    id_tags = {}
    for tag, num in zip(tags, sizes):
        ids = rng.zipf(1.4, size=n) % num
        id_tags[tag] = [f"{tag[:1]}{i}" for i in ids]
        shards[f"s_{tag}"] = CSRMatrix.from_dense(
            rng.normal(size=(n, d_re))
        )
    return GameData.build(labels=y, feature_shards=shards, id_tags=id_tags)


def _re_cfg(tag, **kw):
    return RandomEffectCoordinateConfig(
        random_effect_type=tag,
        feature_shard=f"s_{tag}",
        optimization=_opt(),
        regularization_weights=(1.0,),
        **kw,
    )


def _coordinates(seed=0):
    data = _game_data(seed=seed)
    fe = FixedEffectCoordinateConfig(
        feature_shard="g", optimization=_opt(), regularization_weights=(1.0,)
    )
    re = _re_cfg("userId")
    ds = build_random_effect_dataset(data, re, seed=seed)
    return {
        "fixed": FixedEffectCoordinate.build(data, fe),
        "user": RandomEffectCoordinate.build(data, ds, re),
    }


# ---------------------------------------------------------------------------
# 1. shape budget
# ---------------------------------------------------------------------------


def test_optimal_row_levels_honors_shape_budget():
    rng = np.random.default_rng(0)
    sizes = np.minimum(rng.zipf(1.3, size=5000) % 400 + 1, 256)
    unbudgeted = _optimal_row_levels(sizes, waste_target=0.0)  # best at 16
    for budget in (3, 5, 8):
        lv = _optimal_row_levels(sizes, waste_target=0.0, shape_budget=budget)
        assert len(lv) <= budget
        # levels still cover every size (snapping up never fails)
        assert lv[-1] >= sizes.max()
    # a budget at/above the natural level count changes nothing
    lv = _optimal_row_levels(sizes, shape_budget=64)
    assert np.array_equal(lv, _optimal_row_levels(sizes))
    assert len(unbudgeted) > 3  # the cap above actually bound


def test_budgeted_dp_beats_greedy_capping_in_waste():
    """The ≤-budget DP must be at least as good as snapping to ANY
    budget-sized subset chosen greedily — spot-check against truncating
    the unbudgeted levels (keep the largest K)."""
    rng = np.random.default_rng(1)
    sizes = np.minimum(rng.zipf(1.3, size=3000) % 300 + 1, 200)
    K = 4
    dp = _optimal_row_levels(sizes, waste_target=0.0, shape_budget=K)
    naive = _optimal_row_levels(sizes, waste_target=0.0)[-K:]
    naive[-1] = max(naive[-1], sizes.max())

    def padded(levels):
        lv = np.sort(np.asarray(levels))
        return int(lv[np.searchsorted(lv, sizes)].sum())

    assert padded(dp) <= padded(naive)


def test_shape_pool_shares_levels_across_coordinates():
    """Two coordinates with different size skews: pooled builds must draw
    their bucket row-levels from ONE shared set, and the global distinct
    shape count must not exceed the pool's (it strictly drops versus
    unpooled builds for these fixtures)."""
    data = _game_data(
        seed=2, n=4000, tags=("userId", "itemId"), sizes=(600, 60)
    )
    cfg_u = _re_cfg("userId", active_data_upper_bound=32)
    cfg_i = _re_cfg("itemId", active_data_upper_bound=512)

    pool = ShapePool(budget=6)
    for cfg in (cfg_u, cfg_i):
        prof = profile_random_effect_shapes(data, cfg)
        assert prof is not None  # dense shard: exactly profilable
        pool.observe(*prof)
    pool.freeze()
    assert pool.stats()["distinct_shapes"] <= 6

    pooled = {
        c.random_effect_type: build_random_effect_dataset(
            data, c, shape_pool=pool
        )
        for c in (cfg_u, cfg_i)
    }
    solo = {
        c.random_effect_type: build_random_effect_dataset(data, c)
        for c in (cfg_u, cfg_i)
    }

    def global_shapes(dss):
        return {
            tuple(s)
            for ds in dss.values()
            for s in ds.shape_stats()["shapes"]
        }

    shared = set()
    for d, lv in pool.stats()["levels_per_d_group"].items():
        shared |= {(n, int(d)) for n in lv}
    assert global_shapes(pooled) <= shared
    assert len(global_shapes(pooled)) < len(global_shapes(solo))
    # profile exactness: the pooled build never needed the defensive
    # level top-up, so every bucket's rows level is a pool level
    for ds in pooled.values():
        for b in ds.buckets:
            assert (b.padded_samples, b.projected_dim) in shared


def test_shape_budget_disabled_restores_unbudgeted_build(monkeypatch):
    """shape_budget=0 (or PHOTON_RE_SHAPE_BUDGET=0) must reproduce the r5
    unbudgeted behavior — the A/B lever for padding-vs-programs."""
    data = _game_data(seed=3, n=2000, sizes=(300,))
    base = build_random_effect_dataset(data, _re_cfg("userId"))
    off_cfg = build_random_effect_dataset(
        data, _re_cfg("userId", shape_budget=0)
    )
    monkeypatch.setenv("PHOTON_RE_SHAPE_BUDGET", "0")
    off_env = build_random_effect_dataset(data, _re_cfg("userId"))
    monkeypatch.delenv("PHOTON_RE_SHAPE_BUDGET")
    assert (
        off_cfg.shape_stats() == off_env.shape_stats()
    )
    # the default budget is a real constraint OR a no-op depending on the
    # data; what must hold is that disabling adds the greedy-consolidation
    # path back (r5 parity) and budgeting never yields MORE shapes
    assert (
        base.shape_stats()["distinct_shapes"]
        <= off_cfg.shape_stats()["distinct_shapes"] + 1
    )


def test_opted_out_coordinate_ignores_shape_pool():
    """A coordinate with shape_budget=0 must keep its unbudgeted r5 build
    even when another coordinate's ShapePool is passed in — the pool only
    governs budget-participating coordinates, and a standalone rebuild
    from (data, config) alone must reproduce the estimator's buckets."""
    data = _game_data(
        seed=5, n=2000, tags=("userId", "itemId"), sizes=(300, 30)
    )
    opted_out = _re_cfg("userId", shape_budget=0)
    budgeted = _re_cfg("itemId")

    pool = ShapePool(budget=6)
    pool.observe(*profile_random_effect_shapes(data, budgeted))
    pool.freeze()

    pooled = build_random_effect_dataset(data, opted_out, shape_pool=pool)
    standalone = build_random_effect_dataset(data, opted_out)
    assert pooled.shape_stats() == standalone.shape_stats()
    assert len(pooled.buckets) == len(standalone.buckets)
    for bp, bs in zip(pooled.buckets, standalone.buckets):
        np.testing.assert_array_equal(bp.entity_ids, bs.entity_ids)


def test_estimator_pool_matches_standalone_pool_rebuild():
    """The bench accounting contract: rebuilding the datasets with the
    estimator's own pool reproduces the bucket partition the fit used
    (entity ids per bucket identical)."""
    data = _game_data(
        seed=4, n=1500, tags=("userId", "itemId"), sizes=(200, 30)
    )
    cfgs = {
        "fixed": FixedEffectCoordinateConfig(
            feature_shard="g",
            optimization=_opt(),
            regularization_weights=(1.0,),
        ),
        "user": _re_cfg("userId"),
        "item": _re_cfg("itemId"),
    }
    est = GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinate_configs=cfgs,
        update_sequence=["fixed", "user", "item"],
        descent_iterations=1,
    )
    built = est.build(data)
    coords, re_datasets = built.coordinates, built.re_datasets
    pool = est._build_shape_pool(data)
    for cid in ("user", "item"):
        rebuilt = build_random_effect_dataset(
            data, cfgs[cid], shape_pool=pool
        )
        fit_ds = re_datasets[cid]
        assert len(rebuilt.buckets) == len(fit_ds.buckets)
        for a, b in zip(rebuilt.buckets, fit_ds.buckets):
            assert np.array_equal(a.entity_ids, b.entity_ids)
            assert a.features.shape == b.features.shape


# ---------------------------------------------------------------------------
# 2. parallel AOT precompile
# ---------------------------------------------------------------------------


def test_precompile_overlaps_and_descent_is_compile_free():
    coords = _coordinates(seed=5)
    report = precompile_coordinates(coords)
    # 2 coordinates × (fused sweep + initial score)
    assert report["n_programs"] == 4
    labels = {p["program"] for p in report["programs"]}
    assert labels == {"fixed:sweep", "fixed:score", "user:sweep", "user:score"}
    # overlap: the pool wall undercuts the serial-equivalent sum of the
    # per-program walls (XLA releases the GIL during backend compiles)
    assert report["wall_s"] < report["sum_program_walls_s"], report
    # first descent warms the handful of EAGER-op programs the control
    # flow touches (initial-score adds, scalar conversions — milliseconds
    # each, cached per process by shape); the precompiled descent proper
    # must then dispatch ONLY stored executables: zero backend compiles
    result = run_coordinate_descent(coords, ["fixed", "user"], 2)
    assert np.isfinite(np.asarray(result.states["fixed"])).all()
    coords2 = _coordinates(seed=5)
    precompile_coordinates(coords2)
    with compile_watch.watch() as cw:
        run_coordinate_descent(coords2, ["fixed", "user"], 2)
    assert cw["backend_compiles"] == 0, cw


def test_precompiled_descent_is_bit_exact_vs_jit_path():
    fresh = run_coordinate_descent(_coordinates(seed=6), ["fixed", "user"], 3)
    coords = _coordinates(seed=6)
    precompile_coordinates(coords)
    aot = run_coordinate_descent(coords, ["fixed", "user"], 3)
    assert np.array_equal(
        np.asarray(fresh.states["fixed"]), np.asarray(aot.states["fixed"])
    )
    for a, b in zip(fresh.states["user"], aot.states["user"]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_precompile_reports_persistent_cache_hits(tmp_path):
    """With a persistent compilation cache, a second cold process (here:
    cleared in-memory caches) re-precompiling the same programs must
    report cache_hits — the 'what the pass skipped' accounting."""
    from photon_tpu.util.compile_cache import enable_persistent_cache

    data = _game_data(seed=7, n=300)
    fe_cfg = FixedEffectCoordinateConfig(
        feature_shard="g", optimization=_opt(), regularization_weights=(1.0,)
    )
    try:
        # a directory that is already configured (JAX_COMPILATION_CACHE_DIR
        # lands in this same config value) is the one the function keeps
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert enable_persistent_cache() == str(tmp_path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cold = precompile_coordinates(
            {"fixed": FixedEffectCoordinate.build(data, fe_cfg)}
        )
        assert cold["cache_misses"] > 0
        jax.clear_caches()
        warm = precompile_coordinates(
            {"fixed": FixedEffectCoordinate.build(data, fe_cfg)}
        )
        assert warm["cache_hits"] > 0
        assert warm["cache_misses"] == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", None)


def test_persistent_cache_defaults_to_the_checkout():
    """With no directory configured the cache sits at a fixed path in the
    checkout (the path is part of an entry's key: a moving one never
    hits)."""
    import os

    from photon_tpu.util.compile_cache import enable_persistent_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_persistent_cache() == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            root, ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", None)


def test_estimator_precompile_flag_parity_and_stats():
    data = _game_data(seed=8, n=500)
    cfgs = {
        "fixed": FixedEffectCoordinateConfig(
            feature_shard="g",
            optimization=_opt(),
            regularization_weights=(1.0,),
        ),
        "user": _re_cfg("userId"),
    }

    def fit(precompile):
        est = GameEstimator(
            task=TaskType.LINEAR_REGRESSION,
            coordinate_configs=cfgs,
            update_sequence=["fixed", "user"],
            descent_iterations=2,
            precompile=precompile,
        )
        return est.fit(data)[0]

    plain, pre = fit(False), fit(True)
    assert plain.compile_stats is not None
    assert plain.compile_stats["precompile"] is None
    assert pre.compile_stats["precompile"]["n_programs"] == 4
    # precompile is an execution-plan change only: bit-identical models
    np.testing.assert_array_equal(
        np.asarray(plain.model["fixed"].model.coefficients.means),
        np.asarray(pre.model["fixed"].model.coefficients.means),
    )
    lp, lq = (
        m["user"].dense_coefficient_lookup()
        for m in (plain.model, pre.model)
    )
    for a, b in zip(lp, lq):
        assert (a is None) == (b is None)
        if a is not None:
            np.testing.assert_array_equal(a, b)


def test_estimate_compile_bill_enumeration():
    coords = _coordinates(seed=9)
    bill = estimate_compile_bill(coords)
    assert bill["n_top_level_programs"] == 2 * len(coords)
    ds_shapes = {
        (db.features.shape[1], db.features.shape[2])
        for db in coords["user"].device_buckets
    }
    assert bill["n_solve_shapes"] == len(ds_shapes)
    assert bill["n_bucket_solves"] == len(coords["user"].device_buckets)
    assert bill["projected_cold_s"] == pytest.approx(
        (bill["n_top_level_programs"] + bill["n_solve_shapes"])
        * bill["sec_per_program_assumed"]
    )


# ---------------------------------------------------------------------------
# 3. telemetry
# ---------------------------------------------------------------------------


def test_compile_watch_counts_fresh_compiles_once():
    assert compile_watch.install()

    @jax.jit
    def f(x):
        return jnp.tanh(x) * 3.0

    # both inputs built OUTSIDE the watches: eager ops (the add) compile
    # tiny programs of their own that would otherwise pollute the counts
    x = jnp.ones((16,))
    y = x + 1.0
    with compile_watch.watch() as first:
        f(x).block_until_ready()
    assert first["backend_compiles"] >= 1
    assert first["backend_compile_s"] > 0
    with compile_watch.watch() as second:
        f(y).block_until_ready()
    assert second["backend_compiles"] == 0


def test_sweep_tracker_rows_carry_compile_split():
    result = run_coordinate_descent(
        _coordinates(seed=10), ["fixed", "user"], 3
    )
    rows = [r for r in result.tracker if "sweep_seconds" in r]
    assert len(rows) == 3
    # sweep 0 pays the cold compiles; the steady state must be
    # compile-free (a nonzero count there is the retrace regression)
    assert rows[0]["compiles"] > 0
    assert rows[0]["compile_seconds"] > 0
    for r in rows[1:]:
        assert r["compiles"] == 0
        assert r["compile_seconds"] == 0
