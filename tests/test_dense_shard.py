"""A feature shard handed to ``GameData`` as its dense ``[n, d]`` array
(``DenseMatrix``, PR 36): it stands wherever a ``CSRMatrix`` of the same
numbers stands and builds the same fit, bit for bit; the random effects'
rescoring in row chunks gives the unchunked rescoring's numbers, bit for
bit; and the chunk rules never pass their budgets."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.game import (
    CSRMatrix,
    DenseMatrix,
    FixedEffectCoordinateConfig,
    GameData,
    GameEstimator,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game import coordinate as coordinate_mod
from photon_tpu.game.data import (
    build_random_effect_dataset,
    concat_game_data,
    pad_game_data,
    profile_random_effect_shapes,
    slice_game_data,
)
from photon_tpu.game.descent import run_coordinate_descent
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import LabeledBatch, TaskType

D_FE, D_RE, USERS = 12, 4, 40


def _full_rows(x: np.ndarray) -> CSRMatrix:
    """The CSR shard of the same numbers: every row stores all d columns."""
    n, d = x.shape
    return CSRMatrix(indptr=np.arange(n + 1, dtype=np.int64) * d,
                     indices=np.tile(np.arange(d, dtype=np.int32), n),
                     values=x.reshape(-1), num_cols=d)


def _arrays(seed=0, n=900):
    rng = np.random.default_rng(seed)
    x_fe = rng.standard_normal((n, D_FE), dtype=np.float32)
    x_fe[:, 0] = 1.0
    x_re = rng.standard_normal((n, D_RE), dtype=np.float32)
    users = (rng.zipf(1.4, size=n) - 1) % USERS
    users[:USERS] = np.arange(USERS)
    w = rng.standard_normal(D_FE) * 0.5
    wu = rng.standard_normal((USERS, D_RE)) * 0.5
    margin = x_fe @ w + np.einsum("nd,nd->n", x_re, wu[users])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float64)
    return x_fe, x_re, users, y


def _data(dense: bool, seed=0, n=900) -> GameData:
    x_fe, x_re, users, y = _arrays(seed, n)
    shards = {"global": x_fe, "per_user": x_re} if dense else \
        {"global": _full_rows(x_fe), "per_user": _full_rows(x_re)}
    return GameData.build(labels=y, feature_shards=shards,
                          id_tags={"userId": np.array([f"u{u:03d}" for u in users])})


def _estimator(cap=32, sweeps=2) -> GameEstimator:
    def opt(its):
        return GLMProblemConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer_config=OptimizerConfig(max_iterations=its, tolerance=1e-7),
            regularization=RegularizationContext(RegularizationType.L2))

    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectCoordinateConfig(
                feature_shard="global", optimization=opt(8), regularization_weights=(1.0,)),
            "per_user": RandomEffectCoordinateConfig(
                random_effect_type="userId", feature_shard="per_user", optimization=opt(5),
                regularization_weights=(1.0,), active_data_upper_bound=cap),
        },
        update_sequence=["fixed", "per_user"], descent_iterations=sweeps)


def _descend(built):
    return run_coordinate_descent(
        built.coordinates, built.update_sequence, built.descent_iterations,
        initial_states=built.initial_states(), locked_coordinates=built.locked_coordinates)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


# --- the shard itself -------------------------------------------------------


def test_dense_shard_interface():
    x = np.arange(24, dtype=np.float32).reshape(6, 4)
    m = DenseMatrix(x)
    assert (m.num_rows, m.num_cols) == (6, 4)
    assert np.shares_memory(m.to_dense(np.float32), x)  # the array itself
    cols, vals = m.row(2)
    np.testing.assert_array_equal(cols, np.arange(4))
    np.testing.assert_array_equal(vals, x[2])
    csr = _full_rows(x)
    for name in ("indptr", "indices", "values"):  # the CSR of full rows, on demand
        np.testing.assert_array_equal(getattr(m, name), getattr(csr, name))
    for a, b in zip(m.to_ell(nnz_pad_multiple=8), csr.to_ell(nnz_pad_multiple=8)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError):
        DenseMatrix(np.zeros(5))


def test_game_data_takes_a_bare_array_as_a_dense_shard():
    data = _data(dense=True)
    assert all(isinstance(m, DenseMatrix) for m in data.feature_shards.values())
    with pytest.raises(ValueError):
        GameData.build(labels=np.zeros(3), feature_shards={"g": np.zeros((4, 2))})


@pytest.mark.parametrize("op", ["slice", "concat", "pad"])
def test_dense_shard_rows_move_as_the_csr_shards_do(op):
    dense, csr = _data(True, n=200), _data(False, n=200)

    def moved(data):
        if op == "slice":
            return slice_game_data(data, 30, 170)
        if op == "concat":
            return concat_game_data([slice_game_data(data, 0, 90), slice_game_data(data, 90, 200)])
        return pad_game_data(data, 64)

    a, b = moved(dense), moved(csr)
    assert a.num_samples == b.num_samples
    for name in a.feature_shards:
        assert isinstance(a.feature_shards[name], DenseMatrix)
        np.testing.assert_array_equal(a.feature_shards[name].to_dense(),
                                      b.feature_shards[name].to_dense())


@pytest.mark.parametrize("rows", ["canonical", "permuted", "ragged"])
def test_csr_to_dense_of_full_rows(rows):
    """Full rows go to their columns row by row (no [n x d] row index);
    the numbers are those of the general scatter, stored in any order."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((50, 7))
    m = _full_rows(x)
    if rows == "permuted":
        perm = np.stack([rng.permutation(7) for _ in range(50)])
        m = CSRMatrix(indptr=m.indptr, indices=perm.reshape(-1).astype(np.int32),
                      values=np.take_along_axis(x, perm, axis=1).reshape(-1), num_cols=7)
    if rows == "ragged":
        x = np.where(rng.uniform(size=x.shape) < 0.4, 0.0, x)
        m = CSRMatrix.from_dense(x)
    np.testing.assert_array_equal(m.to_dense(np.float64), x)
    np.testing.assert_array_equal(m.to_dense(np.float32), x.astype(np.float32))


# --- the same fit from either shard ------------------------------------------


def test_dense_and_csr_shards_build_the_same_random_effect_dataset():
    cfg = _estimator().coordinate_configs["per_user"]
    a = build_random_effect_dataset(_data(True), cfg, seed=3)
    b = build_random_effect_dataset(_data(False), cfg, seed=3)
    assert len(a.buckets) == len(b.buckets) > 1
    for ba, bb in zip(a.buckets, b.buckets):
        for field in dataclasses.fields(ba):
            np.testing.assert_array_equal(getattr(ba, field.name), getattr(bb, field.name))
    pa = profile_random_effect_shapes(_data(True), cfg)
    pb = profile_random_effect_shapes(_data(False), cfg)
    for x, y in zip(pa, pb):
        np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module")
def built_pair():
    return _estimator().build(_data(True)), _estimator().build(_data(False))


def test_dense_and_csr_shards_build_the_same_fit(built_pair):
    dense, csr = built_pair
    fe_d, fe_c = dense.coordinates["fixed"], csr.coordinates["fixed"]
    assert isinstance(fe_d.batch, LabeledBatch) and isinstance(fe_c.batch, LabeledBatch)
    for x, y in zip(_leaves(fe_d.batch), _leaves(fe_c.batch)):
        np.testing.assert_array_equal(x, y)
    re_d, re_c = dense.coordinates["per_user"], csr.coordinates["per_user"]
    assert len(re_d.device_buckets) == len(re_c.device_buckets)
    for bd, bc in zip(re_d.device_buckets, re_c.device_buckets):
        for field in ("features", "labels", "offsets", "train_weights", "sample_pos",
                      "score_feats"):
            np.testing.assert_array_equal(np.asarray(getattr(bd, field)),
                                          np.asarray(getattr(bc, field)))
    assert re_d._score_plan() == re_c._score_plan() and re_d.score_layout == "sample_order"
    for x, y in zip(_leaves(re_d._score_args()), _leaves(re_c._score_args())):
        np.testing.assert_array_equal(x, y)
    assert dense.update_sequence == csr.update_sequence


def test_dense_and_csr_shards_run_the_same_sweeps(built_pair):
    dense, csr = built_pair
    a, b = _descend(dense), _descend(csr)
    for cid in ("fixed", "per_user"):
        for x, y in zip(_leaves(a.states[cid]), _leaves(b.states[cid])):
            np.testing.assert_array_equal(x, y)
    ha = [row["health"] for row in a.tracker if "health" in row]
    hb = [row["health"] for row in b.tracker if "health" in row]
    assert ha == hb and len(ha) == 2
    # the health row still carries the solves' counters, a bucket an entry
    assert {"iterations", "evaluations", "feature_passes"} <= set(ha[0]["per_user"])


def test_fixed_effect_places_the_dense_shard_without_a_copy(monkeypatch):
    """What is placed is the shard's own array: ``to_dense`` hands it over
    as it is and nothing densifies or widens it on the host."""
    data = _data(True)
    seen = []
    real = DenseMatrix.to_dense

    def to_dense(self, dtype=np.float32):
        out = real(self, dtype)
        seen.append(np.shares_memory(out, self.array))
        return out

    monkeypatch.setattr(DenseMatrix, "to_dense", to_dense)
    _estimator().build(data)
    assert seen == [True]


# --- the rescoring in row chunks ---------------------------------------------


def _re_coordinate(n=3000, cap=16):
    data = _data(True, seed=4, n=n)
    return _estimator(cap=cap).build(data).coordinates["per_user"]


@pytest.mark.parametrize("rows_a_chunk", [1024, 2048])
def test_chunked_rescoring_is_the_unchunked_one_bit_for_bit(monkeypatch, rows_a_chunk):
    coord = _re_coordinate()
    (block,) = coord.score_blocks  # one width: the whole of sample order
    largest = int(block.slot.shape[0])
    assert largest == coord.num_samples
    rng = np.random.default_rng(0)
    state = [jnp.asarray(rng.standard_normal((db.features.shape[0], db.features.shape[2])),
                         jnp.float32) for db in coord.device_buckets]
    assert coordinate_mod.rescore_chunk_rows(largest, D_RE) == largest  # one chunk as built
    whole = np.asarray(coord.score(state))
    total = jnp.asarray(rng.standard_normal(coord.num_samples), jnp.float32)
    score0 = jnp.zeros(coord.num_samples, jnp.float32)
    whole_sweep = coord.sweep_step(total, score0, state, donate=False)

    monkeypatch.setattr(coordinate_mod, "RE_RESCORE_BYTES",
                        rows_a_chunk * coordinate_mod.rescore_row_bytes(D_RE))
    chunked = _re_coordinate()  # a fresh coordinate: a fresh trace
    chunk = coordinate_mod.rescore_chunk_rows(largest, D_RE)
    assert chunk < largest and -(-largest // chunk) >= 2  # several chunks
    text = jax.jit(lambda s: chunked._score_all_jit(
        chunked._score_args(), s, chunked._score_plan())).lower(state).as_text()
    assert "while" in text
    np.testing.assert_array_equal(np.asarray(chunked.score(state)), whole)
    chunked_sweep = chunked.sweep_step(total, score0, state, donate=False)
    for x, y in zip(_leaves(whole_sweep[:3]), _leaves(chunked_sweep[:3])):
        np.testing.assert_array_equal(x, y)


def test_rescore_chunk_rows_stays_inside_its_budget():
    row = coordinate_mod.rescore_row_bytes(16)
    assert row == 1024  # the compiler's 1025 B a row at d = 16
    fit = coordinate_mod.RE_RESCORE_BYTES // row
    for rows in (1, 1000, 3_343_970, fit, fit + 1, 5_255_525, 8_388_608, 50_000_000):
        chunk = coordinate_mod.rescore_chunk_rows(rows, 16)
        assert 1 <= chunk <= rows and chunk * row <= coordinate_mod.RE_RESCORE_BYTES
        if rows <= fit:
            assert chunk == rows  # glmix_ctr.sweeps' largest bucket stays whole
        else:
            # even chunks: the last one shares under a tile a chunk with its neighbour
            assert -(-rows // chunk) * chunk - rows < 1024 * -(-rows // chunk) + chunk // 2


# --- the solves' chunk rule --------------------------------------------------


@pytest.mark.parametrize("rows", [1, 256, 1024, 4096])
@pytest.mark.parametrize("budget", [None, 1 << 24, 1 << 20])
def test_solve_chunk_entities_never_passes_its_budget(monkeypatch, rows, budget):
    """[E, 1024, 16] and [E, 4096, 16] too: where fewer entities than a
    tile of 1024 fill the budget, the chunk is those, not a tile."""
    if budget is not None:
        monkeypatch.setattr(coordinate_mod, "RE_SOLVE_BYTES", budget)
    opt = OptimizerConfig(max_iterations=10, num_corrections=10)
    each = coordinate_mod.solve_entity_bytes(rows, 16, opt)
    for entities in (1, 500, 1024, 5000, 70_000, 2_000_000):
        chunk = coordinate_mod.solve_chunk_entities(entities, rows, 16, opt)
        assert 1 <= chunk <= entities
        if each <= coordinate_mod.RE_SOLVE_BYTES:
            assert chunk * each <= coordinate_mod.RE_SOLVE_BYTES
        else:
            assert chunk == 1  # one entity is the least a solve can take
        if chunk < entities and chunk >= 1024:
            assert chunk % 1024 == 0


def test_solve_entity_bytes_prices_rows_as_vectors_not_blocks():
    """Six [rows] vectors an entity, not the block twice: 27.9 KB and
    101.6 KB at 1024 and 4096 rows (the compiler: 23.5 and 93.7)."""
    opt = OptimizerConfig(max_iterations=10, num_corrections=10)
    assert coordinate_mod.solve_entity_bytes(1024, 16, opt) == 27_904
    assert coordinate_mod.solve_entity_bytes(4096, 16, opt) == 101_632
    five = OptimizerConfig(max_iterations=5, num_corrections=10)
    assert coordinate_mod.solve_entity_bytes(1, 16, five) == 2072
    # the one-row bucket of glmix_ctr.sweeps: 8 chunks of whole tiles
    chunk = coordinate_mod.solve_chunk_entities(1_997_496, 1, 16, five)
    assert chunk == 259_072 and -(-1_997_496 // chunk) == 8
