"""Column-windowed sparse rmatvec: layout build + the prefix-sum pass agree
with the flat scatter-add reference (ops/sparse_windows.py).

The windowed layout exists to reroute the high-dim backward scatter around
XLA:TPU's serialized scatter lowering; numerics must be identical (up to
f32 reassociation) to the plain ELL path the rest of the suite validates.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.ops.sparse_windows import (
    ColumnWindows,
    build_column_windows,
    maybe_build_windows,
    rmatvec_windows_prefix,
)
from photon_tpu.util import target


def _reference_rmatvec(idx, val, r, d):
    out = np.zeros(d, dtype=np.float64)
    np.add.at(out, idx.reshape(-1), (val * r[:, None]).reshape(-1))
    return out


def _random_ell(rng, n, k, d, hot_column=False, zero_slots=True):
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.standard_normal((n, k)).astype(np.float32)
    if hot_column:
        idx[:, 0] = 0  # every row hits column 0 → instance spill
        val[:, 0] = 1.0
    if zero_slots:
        val[rng.uniform(size=(n, k)) < 0.2] = 0.0  # ELL padding slots
    return idx, val


@pytest.mark.parametrize("hot_column", [False, True])
@pytest.mark.parametrize("d", [64, 300, 1024])
def test_all_impls_match_reference(hot_column, d):
    rng = np.random.default_rng(0)
    n, k = 257, 5
    idx, val = _random_ell(rng, n, k, d, hot_column=hot_column)
    r = rng.standard_normal(n).astype(np.float32)

    windows = build_column_windows(
        idx, val, d, window=32, instance_cap=128, chunk=16
    )
    expect = _reference_rmatvec(idx, val, r, d)

    got = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), d))
    np.testing.assert_allclose(got, expect, rtol=2e-4, atol=1e-4)


def test_build_pads_instances_to_multiple_of_8():
    """A block of whole instances meets the TPU sublane rule only where
    W_inst % 8 == 0; inert padding instances must not change the algebra."""
    rng = np.random.default_rng(3)
    idx, val = _random_ell(rng, 100, 3, 40)
    windows = build_column_windows(idx, val, 40, window=16, instance_cap=64)
    w_inst = windows.rows.shape[0]
    assert w_inst % 8 == 0
    assert np.all(np.diff(np.asarray(windows.inst2win)) >= 0)


def test_bounds_static_invariants():
    """bounds[i] is a monotone exclusive prefix ending at the instance
    length, consistent with a direct per-column count of lcols."""
    rng = np.random.default_rng(4)
    idx, val = _random_ell(rng, 300, 4, 96, hot_column=True)
    windows = build_column_windows(idx, val, 96, window=32, instance_cap=64)
    bounds = np.asarray(windows.bounds)
    lcols = np.asarray(windows.lcols)
    w_inst, length = lcols.shape
    assert bounds.shape == (w_inst, windows.window + 1)
    assert np.all(bounds[:, 0] == 0)
    assert np.all(bounds[:, -1] == length)
    assert np.all(np.diff(bounds, axis=1) >= 0)
    for i in range(w_inst):
        counts = np.bincount(lcols[i], minlength=windows.window)
        np.testing.assert_array_equal(
            np.cumsum(counts), bounds[i, 1:]
        )


def test_prefix_drift_bounded_on_biased_contributions():
    """Variance-path shape: all-positive weights make the raw prefix grow
    linearly in L, the worst case for diff-of-cumsum rounding; the
    mean-centered prefix must stay close to an f64 reference even for
    low-count columns deep inside a 4096-slot instance."""
    rng = np.random.default_rng(6)
    n, k, d = 5000, 8, 256
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.uniform(0.5, 1.5, size=(n, k)).astype(np.float32)
    idx[:, 0] = 0  # hot column → one 4096-deep spill chain
    r = rng.uniform(0.1, 2.0, size=n).astype(np.float32)  # d2-like, > 0
    windows = build_column_windows(
        idx, val, d, window=64, instance_cap=4096
    )
    expect = np.zeros(d, dtype=np.float64)
    np.add.at(
        expect,
        idx.reshape(-1),
        (val.astype(np.float64) * r.astype(np.float64)[:, None]).reshape(-1),
    )
    got = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), d))
    np.testing.assert_allclose(got, expect, rtol=5e-5, atol=1e-3)


def test_flat_sorted_invariant_with_misaligned_cap():
    """Regression: a spill cap that is not a multiple of the length rounding
    must not leave mid-stream padding: a window's stream stays in
    non-decreasing global column order across its instances."""
    rng = np.random.default_rng(11)
    n, k, d = 500, 3, 64
    idx, val = _random_ell(rng, n, k, d, hot_column=True, zero_slots=False)
    windows = build_column_windows(
        idx, val, d, window=16, instance_cap=100, chunk=16
    )
    w = windows.window
    gcols = np.asarray(windows.lcols) + np.asarray(windows.inst2win)[:, None] * w
    assert np.all(np.diff(gcols.reshape(-1)) >= 0), "flat order not sorted"
    r = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    got = np.asarray(rmatvec_windows_prefix(windows, r, d))
    np.testing.assert_allclose(
        got, _reference_rmatvec(idx, val, np.asarray(r), d),
        rtol=2e-4, atol=1e-4,
    )


def test_float64_values_preserved():
    rng = np.random.default_rng(10)
    idx, val = _random_ell(rng, 32, 3, 64)
    w = build_column_windows(idx, val.astype(np.float64), 64)
    assert w.vals.dtype in (jnp.float64, jnp.float32)  # f32 only if x64 off
    import numpy as _np

    assert _np.asarray(w.vals).dtype == (
        _np.float64 if jax.config.jax_enable_x64 else _np.float32
    )


def test_native_builder_matches_numpy(monkeypatch):
    """The C++ counting-sort builder and the numpy argsort path must emit
    byte-identical layouts (both are stable by column over slot order)."""
    from photon_tpu.data.native_index import _load_native_lib

    if _load_native_lib() is None:
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(13)
    n, k, d = 700, 6, 500
    idx, val = _random_ell(rng, n, k, d, hot_column=True)
    w_native = build_column_windows(idx, val, d, window=64, instance_cap=256)
    monkeypatch.setattr(
        "photon_tpu.data.native_index._load_native_lib", lambda: None
    )
    w_numpy = build_column_windows(idx, val, d, window=64, instance_cap=256)
    for a, b in zip(w_native, w_numpy):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spill_layout_shape():
    """A column with N entries must spill across ⌈N/cap⌉ instances instead
    of inflating every window's padded length."""
    rng = np.random.default_rng(1)
    n, k, d = 1000, 4, 256
    idx, val = _random_ell(rng, n, k, d, hot_column=True, zero_slots=False)
    cap = 128
    windows = build_column_windows(
        idx, val, d, window=32, instance_cap=cap, chunk=16
    )
    w_inst, length = windows.rows.shape
    assert length <= cap
    # window 0 holds ≥ n entries → at least ceil(n / cap) instances
    inst_per_win = np.bincount(np.asarray(windows.inst2win), minlength=8)
    assert inst_per_win[0] >= -(-n // cap)
    assert np.all(np.diff(np.asarray(windows.inst2win)) >= 0)
    # padded total bounded: waste < 1 instance per window + rounding
    assert w_inst * length < n * k + (d // 32 + inst_per_win[0]) * length


def test_explicit_zero_slots_dropped():
    """ELL padding slots (value 0, column 0) must not inflate window 0."""
    idx = np.zeros((64, 8), dtype=np.int32)
    val = np.zeros((64, 8), dtype=np.float32)
    idx[:, 0] = np.arange(64) % 16
    val[:, 0] = 1.0  # one real nonzero per row, 7 padding slots
    windows = build_column_windows(idx, val, 16, window=16)
    assert float(jnp.sum((windows.vals != 0).astype(jnp.int32))) == 64.0
    r = jnp.ones((64,), jnp.float32)
    got = np.asarray(rmatvec_windows_prefix(windows, r, 16))
    expect = np.bincount(idx[:, 0], minlength=16).astype(np.float32)
    np.testing.assert_allclose(got, expect)


def test_objective_gradient_with_windows_matches_plain():
    """GLMObjective routed through the windowed path reproduces the plain
    ELL segment_sum gradient bit-for-bit-ish."""
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.types import SparseBatch

    rng = np.random.default_rng(2)
    n, k, d = 128, 6, 96
    idx, val = _random_ell(rng, n, k, d)
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    w = rng.standard_normal(d).astype(np.float32) * 0.1

    def batch(windows):
        return SparseBatch(
            indices=jnp.asarray(idx),
            values=jnp.asarray(val),
            labels=jnp.asarray(labels),
            offsets=jnp.zeros((n,), jnp.float32),
            weights=jnp.ones((n,), jnp.float32),
            windows=windows,
        )

    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5)
    v0, g0 = obj.value_and_gradient(jnp.asarray(w), batch(None))
    windows = build_column_windows(idx, val, d, window=32)
    v1, g1 = obj.value_and_gradient(jnp.asarray(w), batch(windows))
    assert float(v0) == pytest.approx(float(v1), rel=1e-6)
    np.testing.assert_allclose(
        np.asarray(g0), np.asarray(g1), rtol=1e-5, atol=1e-6
    )


def test_hessian_diagonal_with_windows_matches_plain():
    """Variance path: windowed Σ d2·x² (incl. the shift binomial expansion)
    must match the plain segment_sum lowering."""
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.normalization import NormalizationContext
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.types import SparseBatch

    rng = np.random.default_rng(5)
    n, k, d = 96, 5, 80
    idx, val = _random_ell(rng, n, k, d)
    labels = (rng.uniform(size=n) > 0.4).astype(np.float32)
    w = rng.standard_normal(d).astype(np.float32) * 0.2
    shifts = 0.05 * rng.standard_normal(d).astype(np.float32)
    shifts[0] = 0.0  # intercept column: factor 1, shift 0
    factors = 1.0 + 0.1 * rng.uniform(size=d).astype(np.float32)
    factors[0] = 1.0
    norm = NormalizationContext(
        factors=jnp.asarray(factors),
        shifts=jnp.asarray(shifts),
        intercept_index=0,
    )

    def batch(windows):
        return SparseBatch(
            indices=jnp.asarray(idx),
            values=jnp.asarray(val),
            labels=jnp.asarray(labels),
            offsets=jnp.zeros((n,), jnp.float32),
            weights=jnp.ones((n,), jnp.float32),
            windows=windows,
        )

    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.3, normalization=norm)
    d0 = obj.hessian_diagonal(jnp.asarray(w), batch(None))
    windows = build_column_windows(idx, val, d, window=32)
    # all-positive d2: the worst case for a difference of cumsums
    d1 = obj.hessian_diagonal(jnp.asarray(w), batch(windows))
    np.testing.assert_allclose(
        np.asarray(d0), np.asarray(d1), rtol=1e-4, atol=1e-5
    )


def test_bf16_sparse_values_end_to_end():
    """bf16-stored sparse values (config.bf16_features on a sparse shard)
    train close to the f32 path; windows preserve the bf16 storage."""
    from photon_tpu.game.config import (
        FeatureRepresentation,
        FixedEffectCoordinateConfig,
    )
    from photon_tpu.game.coordinate import FixedEffectCoordinate
    from photon_tpu.game.data import CSRMatrix, GameData
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import GLMProblemConfig
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(12)
    n, d, k = 256, 1200, 5
    cols = rng.integers(1, d, size=(n, k))
    cols[:, 0] = 0
    vals = rng.standard_normal((n, k)) / np.sqrt(k)
    shard = CSRMatrix(
        indptr=np.arange(n + 1, dtype=np.int64) * k,
        indices=cols.reshape(-1).astype(np.int32),
        values=vals.reshape(-1),
        num_cols=d,
    )
    labels = (rng.uniform(size=n) > 0.5).astype(np.float64)
    data = GameData.build(labels=labels, feature_shards={"g": shard})

    def train(bf16):
        cfg = FixedEffectCoordinateConfig(
            feature_shard="g",
            representation=FeatureRepresentation.SPARSE,
            bf16_features=bf16,
            optimization=GLMProblemConfig(
                task=TaskType.LOGISTIC_REGRESSION,
                optimizer_config=OptimizerConfig(
                    max_iterations=10, ls_max_iterations=6
                ),
            ),
            regularization_weights=(1.0,),
        )
        # the chip's layout, built here; the fit below runs outside the block
        with target.compiling_for("tpu"):
            coord = FixedEffectCoordinate.build(data, cfg)
        if bf16:
            assert coord.batch.values.dtype == jnp.bfloat16
            assert coord.batch.windows is not None
            assert coord.batch.windows.vals.dtype == jnp.bfloat16
        state, _ = coord.train(
            jnp.zeros((n,), jnp.float32), coord.initial_state()
        )
        return np.asarray(state, np.float32)

    w32, w16 = train(False), train(True)
    assert np.linalg.norm(w16 - w32) / max(np.linalg.norm(w32), 1e-9) < 0.05


def test_maybe_build_windows_policy():
    rng = np.random.default_rng(3)
    idx, val = _random_ell(rng, 32, 4, 4096)
    # a program for the CPU → no windows
    assert maybe_build_windows(idx, val, 4096) is None or (
        jax.default_backend() == "tpu"
    )
    with target.compiling_for("tpu"):
        w = maybe_build_windows(idx, val, 4096)
        assert isinstance(w, ColumnWindows)
        # the defaults of build_column_windows, and nothing else
        assert w.window == 128 and w.instance_len <= 4096
        # host=True keeps leaves in numpy (for mesh placement)
        wh = maybe_build_windows(idx, val, 4096, host=True)
        assert isinstance(wh.rows, np.ndarray)
        with target.compiling_for("cpu"):
            assert maybe_build_windows(idx, val, 4096) is None


def test_sharded_windowed_rmatvec_matches_reference():
    """Instance-sharded shard_map reduction over the full 8-device mesh ==
    the host reference (disjoint column-range partials + one psum)."""
    from photon_tpu.parallel import make_mesh
    from photon_tpu.parallel.sparse import (
        shard_windows,
        sharded_windowed_rmatvec,
    )

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = make_mesh(num_data=len(jax.devices()) // 2, num_entity=2)
    rng = np.random.default_rng(6)
    n, k, d = 513, 7, 1000  # odd sizes: instance padding path exercised
    idx, val = _random_ell(rng, n, k, d, hot_column=True)
    windows = build_column_windows(
        idx, val, d, window=64, instance_cap=256, chunk=32
    )
    sharded = shard_windows(windows, mesh, d)
    assert sharded.rows.shape[0] % len(jax.devices()) == 0
    r = rng.standard_normal(n).astype(np.float32)
    with mesh:
        got = np.asarray(
            jax.jit(
                lambda w_, r_: sharded_windowed_rmatvec(w_, r_, d, mesh)
            )(sharded, jnp.asarray(r))
        )
    np.testing.assert_allclose(
        got, _reference_rmatvec(idx, val, r, d), rtol=2e-4, atol=1e-4
    )


def test_mesh_estimator_sparse_windows_parity(monkeypatch):
    """Full production path: GameEstimator with a mesh + high-dim sparse FE
    and the chip's windows (instance-sharded shard_map backward) must train
    the same coefficients as the single-device run without windows. Build
    and fit are one call, and the fit RUNS here, so the test patches the
    layout's one policy predicate and enters no ``compiling_for``."""
    from photon_tpu.game.config import FixedEffectCoordinateConfig
    from photon_tpu.game.data import CSRMatrix, GameData
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import GLMProblemConfig
    from photon_tpu.parallel import make_mesh
    from photon_tpu.types import TaskType

    if len(jax.devices()) < 4:
        pytest.skip("needs a multi-device mesh")

    rng = np.random.default_rng(8)
    n, d, k = 517, 1536, 6  # d ≥ 1024 → windows eligible; odd n → padding
    cols = rng.integers(1, d, size=(n, k))
    cols[:, 0] = 0
    vals = rng.standard_normal((n, k)) / np.sqrt(k)
    shard = CSRMatrix(
        indptr=np.arange(n + 1, dtype=np.int64) * k,
        indices=cols.reshape(-1).astype(np.int32),
        values=vals.reshape(-1),
        num_cols=d,
    )
    labels = (rng.uniform(size=n) > 0.5).astype(np.float64)
    data = GameData.build(labels=labels, feature_shards={"g": shard})

    def fit(mesh, windows):
        monkeypatch.setattr(
            "photon_tpu.ops.sparse_windows.windows_pay",
            lambda num_features: windows and num_features >= 1024,
        )
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "fixed": FixedEffectCoordinateConfig(
                    feature_shard="g",
                    optimization=GLMProblemConfig(
                        task=TaskType.LOGISTIC_REGRESSION,
                        optimizer_config=OptimizerConfig(
                            max_iterations=8, ls_max_iterations=6
                        ),
                    ),
                    # two λs: the grid reweight must keep the sharded
                    # backward (problem rebuild preserves objective.mesh)
                    regularization_weights=(1.0, 10.0),
                )
            },
            update_sequence=["fixed"],
            descent_iterations=1,
            mesh=mesh,
        )
        if mesh is None:
            results = est.fit(data)
        else:
            with mesh:
                results = est.fit(data)
        return [
            np.asarray(r.model["fixed"].model.coefficients.means)
            for r in results
        ]

    w_plain = fit(None, False)
    mesh = make_mesh(num_data=len(jax.devices()) // 2, num_entity=2)
    w_mesh = fit(mesh, True)
    assert len(w_plain) == len(w_mesh) == 2
    for wp, wm in zip(w_plain, w_mesh):
        np.testing.assert_allclose(wm, wp, rtol=5e-4, atol=5e-5)


def test_windows_survive_jit_closure():
    """ColumnWindows is a pytree of arrays — it must pass through jit as an
    argument without retracing on new residual vectors."""
    rng = np.random.default_rng(4)
    idx, val = _random_ell(rng, 64, 4, 128)
    windows = build_column_windows(idx, val, 128, window=32)

    calls = {"n": 0}

    @jax.jit
    def f(windows, r):
        calls["n"] += 1
        return rmatvec_windows_prefix(windows, r, 128)

    r1 = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    r2 = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    out1, out2 = f(windows, r1), f(windows, r2)
    assert calls["n"] == 1
    assert out1.shape == out2.shape == (128,)


# --- the segmented backward pass (whole instances to a segment) -------------


@pytest.fixture
def row_fetch(monkeypatch):
    """The TPU's gather on the CPU (the tests below run one sparse pass, no
    donated program); yields a setter for the segment size."""
    import photon_tpu.ops.gather as gather_mod

    def set_segment(slots):
        monkeypatch.setattr(gather_mod, "_SEG_BYTES", slots * 512)
        return gather_mod

    with target.compiling_for("tpu"):
        yield set_segment


def _layout(seed, n=1100, k=5, d=300, **build):
    rng = np.random.default_rng(seed)
    idx, val = _random_ell(rng, n, k, d, hot_column=True)
    r = rng.standard_normal(n).astype(np.float32)
    build = {"window": 32, "instance_cap": 32, "chunk": 16, **build}
    return idx, val, r, build_column_windows(idx, val, d, **build)


@pytest.mark.parametrize(
    "per,expect_tail",
    [(8, False), (16, False), (32, True), (40, True), (56, True)],
)
def test_segmented_contrib_bit_identical(row_fetch, per, expect_tail):
    """A layout built for one segment size, run at another: three or more
    segments, an instance count that is not a multiple of the instances
    per segment, and every slot's vals . r[rows] (what the loop hands its
    consumer; here the consumer hands it back) bit-equal to the plain
    lookup's."""
    from photon_tpu.ops.sparse_windows import _over_instances

    idx, val, r, windows = _layout(11)
    w_inst, length = windows.rows.shape
    gather_mod = row_fetch(per * length)
    plan = gather_mod.segment_plan(w_inst, length, 4, 8)
    assert plan.per == per and plan.steps >= 3
    assert bool(plan.tail) == expect_tail, (w_inst, plan)
    contrib = jax.jit(lambda w, r_: _over_instances(w, r_, lambda c: c))
    got = np.asarray(contrib(windows, jnp.asarray(r)))
    expect = np.asarray(windows.vals) * r[np.asarray(windows.rows)]
    assert np.array_equal(got, expect)


@pytest.mark.parametrize("per", [8, 16, 24])
def test_segmented_rmatvec_matches_reference(row_fetch, per):
    idx, val, r, windows = _layout(12)
    d = 300
    w_inst, length = windows.rows.shape
    assert row_fetch(per * length).segment_plan(
        w_inst, length, 4, 8
    ).steps >= 3
    got = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), d))
    np.testing.assert_allclose(
        got, _reference_rmatvec(idx, val, r, d), rtol=2e-4, atol=1e-4
    )


def test_segmented_prefix_agrees_with_one_segment_prefix(row_fetch):
    """Instances are independent: the loop's [I, w] partials are the
    whole layout's, to the rounding of a mean and a cumsum whose order
    the compiler picks by block shape."""
    idx, val, r, windows = _layout(13)
    w_inst, length = windows.rows.shape
    row_fetch(w_inst * length)
    whole = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), 300))
    row_fetch(8 * length)
    cut = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), 300))
    np.testing.assert_allclose(whole, cut, rtol=1e-5, atol=1e-5)


def test_segmented_backward_nonfinite_row_reaches_only_its_columns(row_fetch):
    idx, val, r, windows = _layout(14, n=400, d=256)
    val[val == 0] = 0.5  # every slot stored, so row 130 has 5 live columns
    idx[:, 1:] = np.where(idx[:, 1:] == 0, 1, idx[:, 1:])
    windows = build_column_windows(
        idx, val, 256, window=32, instance_cap=32, chunk=16
    )
    r[130] = np.nan
    row_fetch(8 * windows.rows.shape[1])
    got = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), 256))
    hit = np.zeros(256, bool)
    hit[idx[130]] = True
    # a prefix sum carries a NaN to the later columns of its INSTANCE and no
    # further: the other instances, segments and windows stay finite
    assert np.isnan(got[hit]).all()
    poisoned = np.isnan(got)
    wins = np.unique(idx[130] // 32)
    assert np.isin(np.flatnonzero(poisoned) // 32, wins).all()


def test_build_pads_instances_to_the_segment(row_fetch):
    """Inert instances (row 0, local column w-1, value 0) bring the count
    to a multiple of the instances per segment, so the loop has no ragged
    end; a layout of one segment keeps the multiple of 8."""
    from photon_tpu.ops.sparse_windows import instance_multiple

    row_fetch(1 << 20)
    w8 = _layout(15)[3].rows.shape[0]
    row_fetch(40 * 32)
    idx, val, r, windows = _layout(15)
    w_inst, length = windows.rows.shape
    assert w8 % 8 == 0 and w8 % 40 != 0
    assert length == 32 and w_inst == 160
    # 144 instances are past a lane's worth (128): the multiple is the
    # consumer's block, the least whole segments that hold 128 instances
    assert instance_multiple(w8, length, 4) == 160
    assert np.asarray(windows.inst2win).size == w_inst
    inert = slice(w8, w_inst)
    assert not np.asarray(windows.vals)[inert].any()
    assert (np.asarray(windows.lcols)[inert] == 31).all()
    assert (np.asarray(windows.rows)[inert] == 0).all()
    assert (np.asarray(windows.bounds)[inert, -1] == length).all()
    assert (np.asarray(windows.bounds)[inert, :-1] == 0).all()
    got = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), 300))
    np.testing.assert_allclose(
        got, _reference_rmatvec(idx, val, r, 300), rtol=2e-4, atol=1e-4
    )
    row_fetch(1 << 20)
    assert instance_multiple(w_inst, length, 4) == 8


# --- two levels: the fetch on segments, the consumer on blocks of >= 128 ------

#: (rows, instances per segment the layout is BUILT for, ... it is RUN at):
#: what the pass makes of the layout's instance count
_TWO_LEVEL = {
    # 24 instances: one segment, no loop
    "below_one_segment": (150, None, 32),
    # under a lane's worth of instances: every segment its own block
    "under_128_instances": (600, 32, 32),
    "one_outer_block": (800, 32, 32),
    "several_outer_blocks": (2600, 32, 32),
    # built for another segment: segments and a ragged end after the blocks
    "left_over_after_the_blocks": (2000, None, 40),
    # segments of 128 instances need no second level
    "segment_holds_128": (2600, 128, 128),
}


@pytest.mark.parametrize("case", sorted(_TWO_LEVEL))
def test_two_level_loop_matches_whole_layout_and_reference(row_fetch, case):
    """The backward pass with its consumer on blocks of whole segments
    against the consumer on the whole layout (instances are independent:
    the same numbers to the rounding of a cumsum whose order the compiler
    picks by block shape) and against the segment_sum reference."""
    from photon_tpu.ops.sparse_windows import _backward_cut

    n, built_for, run_at = _TWO_LEVEL[case]
    row_fetch((built_for or 1 << 20) * 32)
    idx, val, r, windows = _layout(21, n=n)
    w_inst, length = windows.rows.shape
    assert length == 32
    row_fetch(w_inst * length)
    whole = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), 300))
    row_fetch(run_at * length)
    plan, group = _backward_cut(w_inst, length, 4)
    blocks, left = divmod(w_inst, plan.per * group)
    assert (plan.steps, group, blocks if group > 1 else 0, left) == {
        "below_one_segment": (1, 1, 0, 0),
        "under_128_instances": (3, 1, 0, 0),
        "one_outer_block": (4, 4, 1, 0),
        "several_outer_blocks": (12, 4, 3, 0),
        "left_over_after_the_blocks": (7, 4, 1, 104),
        "segment_holds_128": (3, 1, 0, 0),
    }[case], (w_inst, plan, group)
    cut = np.asarray(rmatvec_windows_prefix(windows, jnp.asarray(r), 300))
    np.testing.assert_allclose(whole, cut, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        cut, _reference_rmatvec(idx, val, r, 300), rtol=2e-4, atol=1e-4
    )


@pytest.mark.parametrize(
    "w_inst,expect",
    [
        (58384, 58496),  # sparse_poisson's layout: 457 blocks of 4 segments
        (58400, 58496),  # ... from the count PR 30 padded it to
        (25120, 25216),  # glmix_ctr's fixed effect: 197 blocks
        (2056, 2176),  # chip_smoke's fixed effect
        (96, 96),  # three segments under a lane's worth: no second level
        (24, 24),  # one segment: the multiple of 8
    ],
)
def test_instance_multiple_at_quoted_shapes(w_inst, expect):
    """At ``_SEG_BYTES`` = 2^26 and 4096 slots an instance a segment is 32
    instances and a consumer block 4 of them."""
    from photon_tpu.ops.sparse_windows import instance_multiple

    assert w_inst + (-w_inst) % instance_multiple(w_inst, 4096, 4) == expect


@pytest.mark.parametrize("case", ["one_segment", "under_128", "holds_128"])
def test_one_level_programs_are_the_parents(row_fetch, case):
    """Where the second level does not engage, the pass traces to the
    program it was before it had one, operation for operation: the whole
    layout through the consumer (one segment), or ``map_segments`` with the
    consumer in the segment's body."""
    from photon_tpu.ops import gather
    from photon_tpu.ops.sparse_windows import _over_instances, _prefix_partials

    n, per = {
        "one_segment": (150, 32), "under_128": (600, 32), "holds_128": (2600, 128)
    }[case]
    row_fetch(1 << 20)
    idx, val, r, windows = _layout(22, n=n)
    w_inst, length = windows.rows.shape
    gather_mod = row_fetch(per * length)
    plan = gather_mod.segment_plan(w_inst, length, 4, 8)
    assert (plan.steps == 1) == (case == "one_segment")

    def parent(w, r_):
        if plan.steps == 1:
            contrib = w.vals * gather.take_1d(r_, w.rows)
            return _prefix_partials(contrib, w.bounds)
        t2 = gather.lane_rows(r_)

        def instances_block(rows, vals, bounds):
            return _prefix_partials(vals * gather.fetch_select(t2, rows), bounds)

        return gather.map_segments(
            instances_block, (w.rows, w.vals, w.bounds), plan, axis=0
        )

    now = jax.make_jaxpr(
        lambda w, r_: _over_instances(w, r_, _prefix_partials, w.bounds)
    )(windows, jnp.asarray(r))
    assert str(now) == str(jax.make_jaxpr(parent)(windows, jnp.asarray(r)))


def test_build_span_records_the_consumer_block(row_fetch):
    """``windows.build`` carries what the backward pass makes of the
    layout's shapes: its loop's steps and the instances a consumer block
    holds (the whole layout where the pass is one segment)."""
    from photon_tpu import obs

    obs.reset()
    obs.enable()
    try:
        row_fetch(32 * 32)
        w_inst = _layout(23, n=2600)[3].rows.shape[0]
        row_fetch(1 << 20)
        small = _layout(23, n=150)[3].rows.shape[0]
        spans = [
            sp for sp in obs.get_tracer().spans() if sp.name == "windows.build"
        ]
    finally:
        obs.disable()
        obs.reset()
    assert [(sp.args["segments"], sp.args["consumer_block"]) for sp in spans] == [
        (w_inst // 32, 128), (1, small)
    ]


def test_pad_windows_for_mesh_pads_each_shard_to_the_segment(row_fetch):
    from photon_tpu.parallel.sparse import pad_windows_for_mesh

    row_fetch(1 << 20)
    idx, val, r, windows = _layout(16)
    w_inst, length = windows.rows.shape
    gather_mod = row_fetch(16 * length)
    padded = pad_windows_for_mesh(windows, 4, 300)
    per_shard = padded.rows.shape[0] // 4
    # 36 instances a shard: under a lane's worth, so whole segments of 16
    assert padded.rows.shape[0] % 4 == 0 and per_shard % 16 == 0
    assert gather_mod.segment_plan(per_shard, length, 4, 8).tail == 0
    assert padded.bounds.shape[0] == padded.rows.shape[0]
    got = np.asarray(rmatvec_windows_prefix(
        jax.tree_util.tree_map(jnp.asarray, padded), jnp.asarray(r), 300
    ))
    np.testing.assert_allclose(
        got, _reference_rmatvec(idx, val, r, 300), rtol=2e-4, atol=1e-4
    )


def test_pad_windows_for_mesh_pads_each_shard_to_the_consumer_block(row_fetch):
    """A shard of 128 instances or more runs the two-level loop: its count
    is padded to whole consumer blocks, and the shards' passes (each a
    [dim] partial, as ``sharded_windowed_rmatvec`` sums them) add up to the
    reference."""
    from photon_tpu.ops.sparse_windows import _backward_cut
    from photon_tpu.parallel.sparse import pad_windows_for_mesh

    row_fetch(1 << 20)
    idx, val, r, windows = _layout(24, n=4000)
    w_inst, length = windows.rows.shape
    row_fetch(32 * length)
    padded = pad_windows_for_mesh(windows, 4, 300)
    per_shard = padded.rows.shape[0] // 4
    assert -(-w_inst // 4) >= 128 and per_shard % 128 == 0
    plan, group = _backward_cut(per_shard, length, 4)
    assert plan.tail == 0 and group == 4 and plan.segments % group == 0
    total = np.zeros(300)
    for k in range(4):
        own = slice(k * per_shard, (k + 1) * per_shard)
        shard = ColumnWindows(
            *(
                jnp.asarray(x if name == "iota" else x[own])
                for name, x in zip(ColumnWindows._fields, padded)
            )
        )
        total += np.asarray(
            rmatvec_windows_prefix(shard, jnp.asarray(r), 300), np.float64
        )
    np.testing.assert_allclose(
        total, _reference_rmatvec(idx, val, r, 300), rtol=2e-4, atol=1e-4
    )
