"""GLM objective tests: gradient/Hv/Hessian vs autodiff, normalization
margin-invariance (the reference's sparsity-preserving margin algebra,
ValueAndGradientAggregator.scala:36-80, must match materialized transforms).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.ops.losses import LogisticLoss, PoissonLoss, SquaredLoss
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.types import LabeledBatch, NormalizationType


def _batch(seed=0, n=64, d=7, classification=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    x[:, -1] = 1.0  # intercept column
    if classification:
        y = (rng.uniform(size=n) > 0.5).astype(np.float64)
    else:
        y = rng.poisson(2.0, size=n).astype(np.float64)
    return LabeledBatch(
        features=jnp.asarray(x),
        labels=jnp.asarray(y),
        offsets=jnp.asarray(rng.normal(scale=0.1, size=n)),
        weights=jnp.asarray(rng.uniform(0.5, 2.0, size=n)),
    )


@pytest.mark.parametrize("loss", [LogisticLoss, SquaredLoss, PoissonLoss],
                         ids=lambda l: l.name)
@pytest.mark.parametrize("l2", [0.0, 0.3])
def test_gradient_matches_autodiff(loss, l2):
    batch = _batch()
    obj = GLMObjective(loss=loss, l2_weight=l2)
    w = jnp.asarray(np.random.default_rng(1).normal(size=7) * 0.1)
    v, g = obj.value_and_gradient(w, batch)
    v2 = obj.value(w, batch)
    g_auto = jax.grad(lambda w: obj.value(w, batch))(w)
    np.testing.assert_allclose(v, v2, rtol=1e-12)
    np.testing.assert_allclose(g, g_auto, rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("loss", [LogisticLoss, SquaredLoss, PoissonLoss],
                         ids=lambda l: l.name)
def test_hessian_vector_and_matrix_match_autodiff(loss):
    batch = _batch()
    obj = GLMObjective(loss=loss, l2_weight=0.1)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=7) * 0.1)
    v = jnp.asarray(rng.normal(size=7))
    h_auto = jax.hessian(lambda w: obj.value(w, batch))(w)
    np.testing.assert_allclose(obj.hessian_vector(w, v, batch), h_auto @ v,
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(obj.hessian_matrix(w, batch), h_auto,
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(obj.hessian_diagonal(w, batch),
                               jnp.diagonal(h_auto), rtol=1e-8, atol=1e-10)


def _standardization_ctx(batch, d):
    x = np.asarray(batch.features)
    mean = x.mean(axis=0)
    var = x.var(axis=0)
    return NormalizationContext.build(
        NormalizationType.STANDARDIZATION,
        mean=mean,
        variance=var,
        intercept_index=d - 1,
        dtype=jnp.float64,
    )


def test_normalized_objective_equals_materialized_transform():
    batch = _batch(seed=3)
    d = 7
    ctx = _standardization_ctx(batch, d)
    obj_virtual = GLMObjective(loss=LogisticLoss, l2_weight=0.2, normalization=ctx)

    # Materialize x' = (x - shift) .* factor and compare against the
    # margin-shift algebra on raw features.
    xt = (batch.features - ctx.shifts) * ctx.factors
    batch_t = batch._replace(features=xt)
    obj_plain = GLMObjective(loss=LogisticLoss, l2_weight=0.2)

    w = jnp.asarray(np.random.default_rng(4).normal(size=d))
    np.testing.assert_allclose(
        obj_virtual.value(w, batch), obj_plain.value(w, batch_t), rtol=1e-10
    )
    g1 = obj_virtual.gradient(w, batch)
    g2 = obj_plain.gradient(w, batch_t)
    np.testing.assert_allclose(g1, g2, rtol=1e-8, atol=1e-10)
    v = jnp.asarray(np.random.default_rng(5).normal(size=d))
    np.testing.assert_allclose(
        obj_virtual.hessian_vector(w, v, batch),
        obj_plain.hessian_vector(w, v, batch_t),
        rtol=1e-8,
        atol=1e-10,
    )
    np.testing.assert_allclose(
        obj_virtual.hessian_matrix(w, batch),
        obj_plain.hessian_matrix(w, batch_t),
        rtol=1e-8,
        atol=1e-10,
    )


def test_coefficient_space_roundtrip():
    batch = _batch(seed=6)
    d = 7
    ctx = _standardization_ctx(batch, d)
    w_t = jnp.asarray(np.random.default_rng(7).normal(size=d))
    w_orig = ctx.model_to_original_space(w_t)
    # Margin invariance: w'·x' + (intercept handling) == w·x
    xt = (batch.features - ctx.shifts) * ctx.factors
    np.testing.assert_allclose(xt @ w_t, batch.features @ w_orig, rtol=1e-9, atol=1e-9)
    # Roundtrip
    np.testing.assert_allclose(
        ctx.model_to_transformed_space(w_orig), w_t, rtol=1e-9, atol=1e-12
    )


def test_bf16_feature_block_matches_f32_within_tolerance():
    """bfloat16 feature storage with f32 MXU accumulation: margins/gradient/
    Hv close to the f32 path at bf16 resolution; outputs stay f32."""
    rng = np.random.default_rng(11)
    n, d = 128, 32
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) > 0.5).astype(np.float32)
    f32 = LabeledBatch(
        features=jnp.asarray(x),
        labels=jnp.asarray(y),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32),
    )
    bf16 = f32._replace(features=jnp.asarray(x, jnp.bfloat16))
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.1)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.1)
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))

    v32, g32 = obj.value_and_gradient(w, f32)
    v16, g16 = obj.value_and_gradient(w, bf16)
    assert g16.dtype == jnp.float32
    np.testing.assert_allclose(float(v16), float(v32), rtol=2e-2)
    np.testing.assert_allclose(
        np.asarray(g16), np.asarray(g32), rtol=0.1, atol=0.1
    )
    h16 = obj.hessian_vector(w, v, bf16)
    h32 = obj.hessian_vector(w, v, f32)
    assert h16.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(h16), np.asarray(h32), rtol=0.1, atol=0.1
    )


# --- the segmented sparse forward pass (ops/objective._ell_matvec) ----------


def _sparse_case(seed, n, k, d):
    from photon_tpu.types import SparseBatch

    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.standard_normal((n, k)).astype(np.float32)
    v = rng.standard_normal(d).astype(np.float32)
    batch = SparseBatch(
        indices=jnp.asarray(idx),
        values=jnp.asarray(val),
        labels=jnp.zeros((n,), jnp.float32),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
        windows=None,
    )
    return idx, val, v, batch


@pytest.fixture
def row_fetch_in_small_segments(monkeypatch):
    """The TPU's gather on the CPU (one sparse pass, no donated program), a
    segment = 64 KiB of fetched rows."""
    import photon_tpu.ops.gather as gather_mod
    from photon_tpu.util import target

    monkeypatch.setattr(gather_mod, "_SEG_BYTES", 1 << 16)
    with target.compiling_for("tpu"):
        yield gather_mod


@pytest.mark.parametrize(
    "n,k",
    [
        (128 * 3, 1),       # whole segments, one slot a row
        (128 * 3 + 77, 1),  # rows not a multiple of the rows per segment
        (128 * 4 + 1, 1),   # a one-row ragged end
        (1000, 1),
        (128 * 3 + 5, 2),   # 64 rows would fit: floored at the 128 lanes
        (128 * 5 + 9, 13),  # K not a multiple of 8, a segment over budget
    ],
)
def test_segmented_ell_matvec_matches_table_lookup(
    row_fetch_in_small_segments, n, k
):
    """Three or more segments and a ragged end: the gathered values are
    bit-equal to table[idx], the row sums agree at float32 rounding."""
    from photon_tpu.ops.objective import matvec

    gather_mod = row_fetch_in_small_segments
    d = 1000
    idx, val, v, batch = _sparse_case(n + k, n, k, d)
    plan = gather_mod.segment_plan(n, k, 4, 128)
    assert plan.steps >= 3 and plan.per == 128
    assert plan.segments * plan.per + plan.tail == n
    got = np.asarray(jax.jit(matvec)(batch, jnp.asarray(v)))
    t2 = gather_mod.lane_rows(jnp.asarray(v))
    gathered = gather_mod.map_segments(
        lambda b: gather_mod.fetch_select(t2, b).T,
        (batch.indices.T,),
        plan,
        axis=1,
    )
    assert np.array_equal(np.asarray(gathered), v[idx])
    expect = np.sum(v[idx].astype(np.float64) * val, axis=1)
    np.testing.assert_allclose(got, expect, rtol=2e-6, atol=2e-6)


def test_segmented_ell_matvec_nonfinite_entry_reaches_only_its_rows(
    row_fetch_in_small_segments,
):
    from photon_tpu.ops.objective import matvec

    n, k, d = 128 * 3 + 40, 3, 512
    idx, val, v, batch = _sparse_case(5, n, k, d)
    idx[idx == 130] = 131
    idx[idx == 7] = 8
    idx[3, 1], idx[200, 0], idx[n - 1, 2] = 130, 7, 130  # loop, loop, tail
    v[130], v[7] = np.nan, np.inf
    batch = batch._replace(indices=jnp.asarray(idx))
    got = np.asarray(matvec(batch, jnp.asarray(v)))
    bad = np.zeros(n, bool)
    bad[[3, 200, n - 1]] = True
    assert not np.isfinite(got[bad]).any()
    assert np.isfinite(got[~bad]).all()
    clean = np.sum(v[idx][~bad].astype(np.float64) * val[~bad], axis=1)
    np.testing.assert_allclose(got[~bad], clean, rtol=2e-6, atol=2e-6)


def test_one_segment_matvec_under_vmap_lowers_as_before():
    """Per-entity solves call matvec under vmap on blocks of one segment:
    their program is the loop-free one of before the segment loop (the
    row fetch and lane select over the whole block), operation for
    operation."""
    from photon_tpu.ops.objective import matvec
    from photon_tpu.types import SparseBatch
    from photon_tpu.util import target

    e, n, k, d = 6, 40, 5, 300

    def before(v, idx, val):  # ops/gather.chunked_take as PR 29 had it
        n_rows = -(-d // 128)
        padded = jnp.zeros((n_rows * 128,), v.dtype).at[:d].set(v)
        t2 = padded.reshape(n_rows, 128)
        flat = idx.reshape(-1)
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        rows = t2[flat >> 7]
        sel = (flat & 127)[:, None] == lane_iota
        tv = jnp.sum(jnp.where(sel, rows, 0), axis=1).reshape(idx.shape)
        return jnp.sum(tv * val, axis=-1)

    def now(v, idx, val):
        z = jnp.zeros((n,), jnp.float32)
        return matvec(
            SparseBatch(indices=idx, values=val, labels=z, offsets=z,
                        weights=z, windows=None),
            v,
        )

    args = (
        jax.ShapeDtypeStruct((e, d), jnp.float32),
        jax.ShapeDtypeStruct((e, n, k), jnp.int32),
        jax.ShapeDtypeStruct((e, n, k), jnp.float32),
    )
    with target.compiling_for("tpu"):
        texts = [
            jax.jit(jax.vmap(f)).lower(*args).as_text().replace(name, "f")
            for f, name in ((before, "before"), (now, "now"))
        ]
    assert "while" not in texts[1]
    assert texts[0] == texts[1]
