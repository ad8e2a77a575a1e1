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


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_place_span_carries_the_score_layout(platform):
    """``photon.game.prepare.place`` of a random effect says which way its
    scores reach sample order, over how many width groups, and how a row
    gets its coefficients in a program for this platform: the packed
    table's bytes, and whether the rows are fetched from it."""
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.util import target

    seen = {}
    for widths in (1, 2):
        cfg = _config(8, 1)
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION, coordinate_configs={"per_user": cfg},
            update_sequence=["per_user"], descent_iterations=1, dtype=jnp.float32)
        obs.enable()
        try:
            obs.reset()
            with target.compiling_for(platform):
                built = est.build(_data(widths))
            rows = [r for r in obs.get_tracer().spans() if r.name == "game.prepare.place"]
        finally:
            obs.disable()
            obs.reset()
        (row,) = rows
        seen[widths] = (row.args["score_layout"], row.args["width_groups"])
        # a width's 90 users and the zero row: 8 (16) entities of 16 (8) to a lane row
        tables = [(91, 16)] if widths == 1 else [
            (1 + sum(db.features.shape[0] for db in built.coordinates["per_user"].device_buckets
                     if db.features.shape[2] == d), d) for d in (8, 16)]
        assert sum(e for e, _ in tables) == 90 + len(tables)
        assert row.args["packed_table_bytes"] == sum(-(-e // (128 // d)) * 512 for e, d in tables)
        assert row.args["table_fetch"] == ("packed_rows" if platform == "tpu" else "plain")
    assert seen == {1: ("sample_order", 1), 2: ("sorted_scatter", 2)}

def _hand_made(widths, heights, n, rng, mesh=None):
    """A coordinate with no dataset, and what ``_score_blocks_body`` takes:
    a score block a width (in sample order where there is one width), its
    slots counting through two tables of ``heights`` laid end to end, the
    row after them the zero row."""
    cfg = _config()
    coord = RandomEffectCoordinate(
        config=cfg, dataset=None, device_buckets=[],
        problem_config=cfg.optimization.with_regularization_weight(1.0),
        num_samples=n, dtype=jnp.float32, mesh=mesh)
    state, score_args, plan = [], [], []
    for k, d in enumerate(widths):
        members = (2 * k, 2 * k + 1)
        state += [jnp.asarray(rng.standard_normal((h, d)), jnp.float32) for h in heights]
        m = n if len(widths) == 1 else n // 2
        slot = rng.integers(0, sum(heights) + 1, m).astype(np.int32)
        slot[:3] = sum(heights)  # the zero row, under features that are not zero
        block = (jnp.asarray(rng.standard_normal((m, d)), jnp.float32), jnp.asarray(slot))
        if len(widths) > 1:
            block += (jnp.asarray(np.sort(rng.choice(n, m, replace=False)).astype(np.int32)),)
        score_args.append(block)
        plan.append(members)
    return coord, tuple(score_args), state, tuple(plan)


#: case -> (bucket widths, heights of a width's two tables, samples, bytes a
#: segment or None, the ceiling on a packed table or None, an Inf entity,
#: whether the program for a TPU fetches 128-lane rows)
PACKED_CASES = {
    "table_rows_a_multiple_of_8": ((16,), (40, 55), 1600, None, None, False, True),
    "table_rows_padded_to_8": ((16,), (40, 50), 1600, None, None, False, True),
    "two_widths": ((16, 8), (40, 50), 1600, None, None, False, True),
    "segment_loop_with_a_tail": ((16,), (40, 50), 3000, 1024 * 512, None, False, True),
    "inf_beside_a_finite_entity": ((16,), (40, 50), 1600, None, None, True, True),
    "width_that_does_not_divide_128": ((12,), (40, 50), 1600, None, None, False, False),
    "table_over_the_constant": ((16,), (40, 50), 1600, None, 91 * 64 - 1, False, False),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_packed_rescoring_is_the_plain_gather_bit_for_bit(monkeypatch, case):
    """The program for a TPU fetches a row's coefficients as the 128-lane
    row of the packed table and keeps its entity's lanes; run on the CPU
    backend it gives ``coefs[slot]``'s scores to the last bit. A width or a
    table the rule leaves out compiles to the plain program."""
    from photon_tpu.ops import gather
    from photon_tpu.util import target

    widths, heights, n, seg_bytes, ceiling, inf, packs = PACKED_CASES[case]
    if seg_bytes is not None:
        monkeypatch.setattr(gather, "_SEG_BYTES", seg_bytes)
    if ceiling is not None:
        monkeypatch.setattr(gather, "_PACKED_TABLE_BYTES", ceiling)
    coord, score_args, state, plan = _hand_made(widths, heights, n, np.random.default_rng(3))
    if inf:
        state[0] = state[0].at[5].set(jnp.inf)

    def body():  # a trace is cached by function
        return jax.jit(lambda args, s: coord._score_blocks_body(args, s, plan))

    plain = body().lower(score_args, state)
    with target.compiling_for("tpu"):
        assert coord._packs_table(sum(heights) + 1, widths[0]) == packs
        packed = body().lower(score_args, state)
    wide = "slice_sizes = array<i64: 1, 128>"
    assert wide not in plain.as_text()
    assert (wide in packed.as_text()) == packs
    assert ("while" in packed.as_text()) == (seg_bytes is not None)
    if not packs:
        assert packed.as_text() == plain.as_text()
    expect = np.asarray(plain.compile()(score_args, state))
    got = np.asarray(packed.compile()(score_args, state))
    np.testing.assert_array_equal(got, expect)
    if len(widths) == 1:
        assert not got[:3].any(), "a slot on the zero row scores 0"
    if inf:
        own = np.asarray(score_args[0][1]) == 5
        assert own.any() and np.isfinite(got[~own]).all(), "the Inf stays its entity's"
        assert not np.isfinite(got[own]).all()


def test_a_coordinate_on_a_mesh_keeps_the_plain_gather():
    from photon_tpu.util import target

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device platform")
    rng = np.random.default_rng(0)
    with target.compiling_for("tpu"):
        assert _hand_made((16,), (40, 50), 64, rng)[0]._packs_table(91, 16)
        meshed = _hand_made((16,), (40, 50), 64, rng, make_mesh(num_data=1, num_entity=8))[0]
        assert not meshed._packs_table(91, 16)


def test_rescore_tables_script_rehearses_on_the_cpu(tmp_path):
    """``scripts/rescore_tables.py`` (what ``_PACKED_TABLE_BYTES`` was read
    with on the chip) at tiny shapes: a plain and a packed program a table,
    the packed one again in a segment loop, every packed score the plain
    one's to the last bit."""
    import importlib.util
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "scripts", "rescore_tables.py")
    spec = importlib.util.spec_from_file_location("rescore_tables", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    out = tmp_path / "rows.json"
    cache = jax.config.jax_enable_compilation_cache
    try:
        rc = script.main(["--rehearse", "--rows", "4096", "--heights", "50",
                          "--seg-bytes", "524288", "--out", str(out)])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert rc == 0
    rows = json.loads(out.read_text())
    assert [(r["kind"], r["seg_bytes"]) for r in rows] == [
        ("plain", None), ("packed", None), ("packed", 524288)]
    assert all(r["entities"] == 51 and r["packed_table_bytes"] == 7 * 512 for r in rows)
    assert [r["bit_equal_to_plain"] for r in rows] == [None, True, True]
