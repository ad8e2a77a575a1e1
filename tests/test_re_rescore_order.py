"""The random-effect rescoring in sample order (PR 37).

``RandomEffectCoordinate.build`` merges the host buckets' flat score rows
per bucket width into ascending sample position, so a sweep's rescoring is
one einsum a width and, where the buckets share one width, neither sorts
nor scatters. What it has to equal, bit for bit, is the per-bucket scatter
of the host buckets that it replaced: every bucket's rows dotted with its
own table, added into a zeroed ``[n]`` vector at ``score_pos``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import obs
from photon_tpu.game import coordinate as coordinate_mod
from photon_tpu.game.config import RandomEffectCoordinateConfig
from photon_tpu.game.coordinate import RandomEffectCoordinate
from photon_tpu.game.data import (
    CSRMatrix,
    GameData,
    build_random_effect_dataset,
)
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.parallel.mesh import make_mesh
from photon_tpu.types import TaskType

N, USERS = 1600, 90


def _data(widths, seed=0, n=N):
    """Zipf-sized users over a per-user shard. ``widths`` = 1: a dense
    [n, 12] shard (every bucket 16 wide). 2: a sparse one in which users
    under 30 touch 5 columns and the rest 11 (buckets 8 and 16 wide)."""
    rng = np.random.default_rng(seed)
    ids = (rng.zipf(1.4, size=n) - 1) % USERS
    ids[:USERS] = rng.permutation(USERS)
    x = rng.standard_normal((n, 12)).astype(np.float32)
    if widths == 2:
        x[ids < 30, 5:] = 0.0
        x[:, 11] = 0.0
    labels = (rng.uniform(size=n) < 0.5).astype(np.float64)
    weights = np.where(rng.uniform(size=n) < 0.03, 0.0, 1.0)  # rows that score 0
    return GameData.build(
        labels=labels,
        weights=weights,
        feature_shards={"per_user": x if widths == 1 else CSRMatrix.from_dense(x)},
        id_tags={"userId": np.array([f"u{u:03d}" for u in ids])},
    )


def _config(cap=None, least=1):
    return RandomEffectCoordinateConfig(
        random_effect_type="userId",
        feature_shard="per_user",
        optimization=GLMProblemConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext(RegularizationType.L2),
            optimizer_config=OptimizerConfig(max_iterations=3, ls_max_iterations=6),
        ),
        regularization_weights=(1.0,),
        active_data_upper_bound=cap,
        active_data_lower_bound=least,
    )


def _per_bucket_scatter(ds, state, dtype=jnp.float32):
    """The rescoring this PR replaced, over the HOST buckets: a bucket at a
    time, its rows dotted with its own table and scattered to their
    positions in a zeroed [n] vector, the vectors added up."""
    n = ds.num_samples
    total = jnp.zeros((n,), dtype)
    for b, coefs in zip(ds.buckets, state):
        feats = jnp.asarray(b.score_feats, dtype)
        s = jnp.einsum("md,md->m", feats, coefs[jnp.asarray(b.score_slot)].astype(dtype))
        total = total + jnp.zeros((n,), dtype).at[jnp.asarray(b.score_pos)].add(
            s, unique_indices=True)
    return total


#: case -> (bucket widths, entity cap, least rows an entity needs, forced
#: rows a chunk, mesh, the layout the build must choose)
CASES = {
    "one_width_every_sample_kept": (1, None, 1, None, False, "sample_order"),
    "one_width_unkept_and_passive": (1, 8, 4, None, False, "sample_order"),
    "two_widths": (2, 8, 1, None, False, "sorted_scatter"),
    "two_widths_unkept": (2, 8, 4, None, False, "sorted_scatter"),
    "row_chunk_loop": (1, 8, 4, 512, False, "sample_order"),
    "row_chunk_loop_two_widths": (2, 8, 1, 256, False, "sorted_scatter"),
    "mesh_one_width": (1, 8, 4, None, True, "sample_order"),
    "mesh_two_widths": (2, 8, 1, None, True, "sorted_scatter"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_merged_rescoring_is_the_per_bucket_scatter_bit_for_bit(monkeypatch, case):
    widths, cap, least, rows_a_chunk, meshed, layout = CASES[case]
    if meshed and len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device platform")
    data, cfg = _data(widths), _config(cap, least)
    mesh = make_mesh(num_data=1, num_entity=8) if meshed else None
    ds = build_random_effect_dataset(data, cfg, seed=0, entity_shards=8 if meshed else 1)
    assert len({b.score_feats.shape[1] for b in ds.buckets}) == widths
    kept = sum(len(b.score_pos) for b in ds.buckets)
    active = sum(int((b.sample_pos < N).sum()) for b in ds.buckets)
    assert (kept < N) == (least > 1), "unkept samples where entities are dropped"
    assert (active < kept) == (cap is not None), "passive rows where entities are capped"
    if rows_a_chunk is not None:
        monkeypatch.setattr(coordinate_mod, "RE_RESCORE_BYTES",
                            rows_a_chunk * coordinate_mod.rescore_row_bytes(16))
    coord = RandomEffectCoordinate.build(data, ds, cfg, jnp.float32, mesh=mesh)
    assert coord.score_layout == layout and len(coord.score_blocks) == widths
    for blk in coord.score_blocks:
        pos = np.arange(N) if blk.pos is None else np.asarray(blk.pos)
        assert np.all(np.diff(pos) > 0), "ascending, distinct positions"
        assert (blk.pos is None) == (layout == "sample_order")
        if layout == "sample_order":
            assert blk.feats.shape == (N, 16)  # N divides the mesh: no padding row

    rng = np.random.default_rng(1)
    state = coord.place_state([
        jnp.asarray(rng.standard_normal((db.features.shape[0], db.features.shape[2])),
                    jnp.float32) for db in coord.device_buckets])
    if least > 1:
        # a diverged entity: its Inf must not reach a sample it does not own
        state[0] = state[0].at[0].set(jnp.inf)
    text = jax.jit(lambda s: coord._score_all_jit(
        coord._score_args(), s, coord._score_plan())).lower(state).as_text()
    assert ("while" in text) == (rows_a_chunk is not None)
    assert ("scatter" in text) == (layout == "sorted_scatter")
    assert "sort" not in text.replace("indices_are_sorted", "")

    expect = np.asarray(jax.jit(lambda s: _per_bucket_scatter(ds, s))(state))
    got = np.asarray(coord.score(state))
    np.testing.assert_array_equal(got, expect)
    if least > 1:
        first = ds.buckets[0]
        others = np.ones(N, bool)
        others[first.score_pos[first.score_slot == 0]] = False
        assert np.isfinite(got[others]).all(), "the Inf stays its entity's"
        assert not np.isfinite(got).all()

    # and through the sweep program: the new score is the scatter at the new state
    state = [jnp.where(jnp.isfinite(s), s, 0.0) for s in state]
    total = jnp.asarray(rng.standard_normal(N), jnp.float32)
    new_state, new_score, new_total, *_ = coord.sweep_step(
        total, jnp.zeros(N, jnp.float32), state, donate=False)
    expect = np.asarray(jax.jit(lambda s: _per_bucket_scatter(ds, s))(new_state))
    np.testing.assert_array_equal(np.asarray(new_score), expect)
    np.testing.assert_array_equal(np.asarray(new_total), np.asarray(total) + expect)


def test_merge_pads_a_block_to_the_mesh_past_the_samples():
    """A block whose rows do not divide the devices: the padding rows are
    zero rows on the zero slot, and where the block carries positions they
    go on ascending past ``num_samples``."""
    data, cfg = _data(2, n=1003), _config(8, 4)
    ds = build_random_effect_dataset(data, cfg, seed=0)
    heights = [b.num_entities for b in ds.buckets]
    plain = list(coordinate_mod._merge_score_rows(ds.buckets, heights, 1003, 1))
    padded = list(coordinate_mod._merge_score_rows(ds.buckets, heights, 1003, 8))
    assert len(plain) == len(padded) == 2
    for (f0, s0, p0, m0), (f1, s1, p1, m1) in zip(plain, padded):
        assert m0 == m1 and len(s0) % 8 and len(s1) == -(-len(s0) // 8) * 8
        zero_row = sum(heights[i] for i in m0)
        np.testing.assert_array_equal(f1[: len(s0)], f0)
        assert not f1[len(s0):].any() and np.all(s1[len(s0):] == zero_row)
        np.testing.assert_array_equal(p1[len(s0):], 1003 + np.arange(len(s1) - len(s0)))
        assert np.all(np.diff(p1) > 0) and s0.max() < zero_row
    wide = [i for i, b in enumerate(ds.buckets) if b.score_feats.shape[1] == 16]
    ((feats, slot, pos, members),) = coordinate_mod._merge_score_rows(
        [ds.buckets[i] for i in wide], [heights[i] for i in wide], 1003, 8)
    assert pos is None and feats.shape == (1008, 16)
    assert np.all(slot[1003:] == slot.max())


def test_place_span_carries_the_score_layout():
    """``photon.game.prepare.place`` of a random effect says which way its
    scores reach sample order, and over how many width groups."""
    from photon_tpu.game.estimator import GameEstimator

    seen = {}
    for widths in (1, 2):
        cfg = _config(8, 1)
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION, coordinate_configs={"per_user": cfg},
            update_sequence=["per_user"], descent_iterations=1, dtype=jnp.float32)
        obs.enable()
        try:
            obs.reset()
            est.build(_data(widths))
            rows = [r for r in obs.get_tracer().spans() if r.name == "game.prepare.place"]
        finally:
            obs.disable()
            obs.reset()
        (row,) = rows
        seen[widths] = (row.args["score_layout"], row.args["width_groups"])
    assert seen == {1: ("sample_order", 1), 2: ("sorted_scatter", 2)}
