"""``glmix_movielens.sweeps`` at its tiny shapes on the CPU, through the very
code that decides ``correct`` (``run.main``): the program over a dense
fixed-effect shard against the plain dense GLMix reference comes out
correct; the bfloat16 control does not; nor does a run whose descent is
broken underneath, once for each fault the limits were set against. And the
new pieces by hand: the dense reference against ``reference_game.Glmix`` on
a problem both can run, the generator's shape, the runner's refusal of a
tree without the dense-shard input, the two new metrics' readers and
``work_game``'s counts at the cell's shapes."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np
import pytest

from benchmarks.tests.test_reference import drive, run_mod, _f32_like_the_chip  # noqa: F401
from benchmarks.readings_game_dense import host_inputs as _inputs  # the first ``cap`` rows active
from benchmarks.tests.test_reference_game import short_fixed, steepest_fixed

CELL = "glmix_movielens.sweeps"


def idle_capped(state):
    """Half of the row-heavy entities are left at their start: every other
    entity of the per-user coordinate's largest bucket (the capped users
    are in it) weighs nothing, so its solve ends where it began."""
    bucket = state.built.coordinates["per_user"].device_buckets[-1]
    assert bucket.features.shape[1] == max(
        b.features.shape[1] for b in state.built.coordinates["per_user"].device_buckets)
    bucket.train_weights = bucket.train_weights.at[::2].set(0.0)


def stale_last(state):
    """The per-movie coordinate is trained on stale scores: its sweep step
    is handed the total as it stood before the per-user coordinate's
    update (``test_reference_game.stale_last`` under this cell's names)."""
    coords = state.built.coordinates
    user, movie = coords["per_user"], coords["per_movie"]
    user_step, movie_step = user.sweep_step, movie.sweep_step
    seen = {}

    def user_sweep(total, score, coord_state, donate=None):
        seen["old"] = np.asarray(score).copy()
        out = user_step(total, score, coord_state, donate=donate)
        seen["new"] = np.asarray(out[1]).copy()
        return out

    def movie_sweep(total, score, coord_state, donate=None):
        import jax.numpy as jnp

        stale = total - jnp.asarray(seen["new"]) + jnp.asarray(seen["old"])
        new_state, new_score, _, info, health = movie_step(
            stale, score, coord_state, donate=donate)
        return new_state, new_score, total - score + new_score, info, health

    user.sweep_step, movie.sweep_step = user_sweep, movie_sweep


def _rehearsal():
    from benchmarks import run as harness

    cell = harness.load_json("benchmarks", "workloads", f"{CELL}.json")
    config = harness.load_json("benchmarks", "configs", f"{cell['config']}.json")
    return harness, cell, {**config, **config["rehearse"]}


def test_program_agrees_with_reference(run_mod, capsys, monkeypatch):
    result = drive(run_mod, capsys, monkeypatch, CELL)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert set(result["check"]) >= {"loss_gap", "x_diff", "dx_gap", "grad_at_x_diff",
                                    "grad_at_x_median", "iters_off", "stop_excess"}
    assert result["check"]["stop_excess"]["value"] == 1.0  # ends on its count of units


def test_lower_precision_control_is_not_correct(run_mod, capsys, monkeypatch):
    result = drive(run_mod, capsys, monkeypatch, CELL, "--control")
    assert result["correct"] is False


@pytest.mark.parametrize("fault", [idle_capped, stale_last], ids=lambda f: f.__name__)
def test_broken_descent_is_not_correct(run_mod, capsys, monkeypatch, fault):
    result = drive(run_mod, capsys, monkeypatch, CELL, fault=fault)
    assert result["correct"] is False
    failed = {k for k, v in result["check"].items() if v["value"] > v["limit"]}
    assert "x_diff" in failed  # the tables are what such a descent gets wrong


def test_fixed_effect_without_its_history_is_not_correct(run_mod, capsys, monkeypatch):
    result = drive(run_mod, capsys, monkeypatch, CELL, fault=steepest_fixed)
    assert result["correct"] is False and result["failed"] == 0
    failed = {k for k, v in result["check"].items() if v["value"] > v["limit"]}
    assert "loss_gap" in failed


def test_fixed_effect_solve_cut_short_is_not_correct(run_mod, capsys, monkeypatch):
    result = drive(run_mod, capsys, monkeypatch, CELL, fault=short_fixed)
    assert result["correct"] is False
    assert result["failed"] == run_mod.FIRST_STEPS


def test_a_tree_without_the_dense_shard_fails_before_any_data_is_made(monkeypatch):
    """What the parent commit does on this cell: the runner's own message,
    at once, and no generator call."""
    harness, _, config = _rehearsal()
    runner = harness.load_module("runners", "game_dense")
    from benchmarks.lib import datagen_movielens
    from photon_tpu.game import data as program_data

    monkeypatch.delattr(program_data, "DenseMatrix")
    monkeypatch.setattr(datagen_movielens, "structure",
                        lambda config: pytest.fail("data was made"))
    with pytest.raises(RuntimeError, match="takes no dense feature shard"):
        runner.setup(config, 1, harness.Spans())


def test_dense_reference_is_reference_game_over_full_sparse_rows():
    """The same deployment, the dense block written as full sparse rows,
    through ``reference_game.Glmix``: the same fit (the two differ in how
    a product's terms are grouped, and in nothing else)."""
    from benchmarks.lib import reference_game, reference_game_dense

    _, _, config = _rehearsal()
    # six iterations: a solve that runs to float64's floor ends on a failed
    # line search, where a last bit decides the iteration
    config = {**config, "features": {"kind": "dense", "n": 2048, "d": 24},
              "solver": {**config["solver"], "fe_max_iterations": 6},
              "random_effects": {"per_user": {"entities": 16, "d": 16, "cap": 64},
                                 "per_movie": {"entities": 9, "d": 16, "cap": 256}}}
    inputs = _inputs(config, seed=7)
    n, d = inputs["fe_x"].shape
    sparse = {**inputs, "fe_vals": inputs["fe_x"],
              "fe_cols": np.tile(np.arange(d, dtype=np.int32), (n, 1))}
    dense = reference_game_dense.GlmixDense(config, inputs)
    plain = reference_game.Glmix(config, sparse)
    a, b = dense.descend(2), plain.descend(2)
    np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-9)
    np.testing.assert_allclose(a["x"], b["x"], rtol=0, atol=1e-6 * np.abs(b["x"]).max())
    assert a["fe_iterations"] == b["fe_iterations"] == [6, 6]
    at_a, at_b = dense.evaluate(dense.unpack(a["x"])), plain.evaluate(plain.unpack(a["x"]))
    np.testing.assert_allclose(at_a["loss"], at_b["loss"], rtol=1e-12)
    np.testing.assert_allclose(at_a["gradient"], at_b["gradient"], rtol=0, atol=1e-9)
    # the control rounds the same operands in both
    low_a = reference_game_dense.GlmixDense(config, inputs, precision="bf16").descend(1)
    low_b = reference_game.Glmix(config, sparse, precision="bf16").descend(1)
    np.testing.assert_allclose(low_a["loss"], low_b["loss"], rtol=1e-9)
    assert abs(low_a["loss"][-1] / a["loss"][1] - 1) > 1e-6


def test_the_reference_plants_this_deployment_s_faults():
    harness, _, config = _rehearsal()
    runner = harness.load_module("runners", "game_dense")
    inputs = _inputs(config, seed=5)
    sound = runner.reference_record(config, inputs, 1)
    change = np.linalg.norm(sound["x"])
    for fault, least in (("idle_capped", 0.05), ("stale_last", 0.05)):
        broken = runner.reference_record(config, {**inputs, "fault": fault}, 1)
        assert np.linalg.norm(broken["x"] - sound["x"]) / change > least, fault
        assert broken["loss"][0] == sound["loss"][0] and broken["loss"][-1] != sound["loss"][-1]
    k = config["follow_fe_iterations"]
    steepest = runner.reference_record(config, {**inputs, "fault": "steepest_fixed"}, 1)
    np.testing.assert_array_equal(steepest["loss"][:2], sound["loss"][:2])
    assert np.max(np.abs(steepest["loss"][2: k + 1] / sound["loss"][2: k + 1] - 1)) > 1e-5
    sweeps = config["solver"]["descent_sweeps"]
    assert len(sound["loss"]) == k + 1 + sweeps and sound["iterations"] == k + sweeps
    assert sound["x"].shape == inputs["w0"].shape


def test_generator_gives_the_ratings_shape():
    """Structure from the structure seed alone, values from ``--seed``;
    every user holds the floor's rows or more, every movie is seen."""
    from benchmarks.lib import datagen_movielens as gen

    _, _, config = _rehearsal()
    a, b = gen.structure(config), gen.structure(config)
    n = config["features"]["n"]
    for name, re in config["random_effects"].items():
        np.testing.assert_array_equal(a[name], b[name])
        counts = np.bincount(a[name], minlength=re["entities"])
        assert counts.sum() == n and counts.min() >= 1
    users = np.bincount(a["per_user"])
    assert users.min() >= config["structure"]["user_rows"]["floor"] and users.max() > 4 * users.mean()
    v1, v2, v3 = gen.values(config, a, 2147483999), gen.values(config, a, 2147483999), \
        gen.values(config, a, 3)
    assert v1["fe_x"].dtype == np.float32 and v1["fe_x"].shape == (n, config["features"]["d"])
    np.testing.assert_array_equal(v1["fe_x"], v2["fe_x"])
    np.testing.assert_array_equal(v1["labels"], v2["labels"])
    assert np.all(v1["fe_x"][:, 0] == 1.0) and not np.array_equal(v1["fe_x"], v3["fe_x"])
    assert 0.3 < v1["labels"].mean() < 0.7  # rating >= 4: about half


def test_cell_config_states_the_source_s_shapes():
    harness, cell, _ = _rehearsal()
    config = harness.load_json("benchmarks", "configs", "glmix_movielens.json")
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "glmix_movielens")
    assert entry["source"] == config["source"] and len(config["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert (config["features"]["d"], config["features"]["n"]) == (128, 1 << 23)
    res = config["random_effects"]
    assert (res["per_user"]["d"], res["per_user"]["cap"], res["per_user"]["entities"]) == (16, 1024, 1 << 16)
    assert (res["per_movie"]["d"], res["per_movie"]["cap"], res["per_movie"]["entities"]) == (16, 4096, 27278)
    assert cell["chips"] == 1 and cell["traffic"]["steps_traced"] == 2
    for name in ("re_rows_solve_ms", "re_rows_roofline"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_rows_per_s"


def test_work_counts_at_the_cell_s_shapes():
    from benchmarks.lib import work, work_game

    # 3 sweeps of a 20-iteration solve: 41 from zero, then 42 and 42
    assert sum(work_game.lbfgs_passes(20, from_zero=i == 0) for i in range(3)) == 125
    block = {"kind": "dense", "n": 1 << 23, "d": 128, "itemsize": 4, "re": {}, "re_step_bytes": []}
    assert work.pass_work(block) == (2.0 * (1 << 30), 4.0 * (1 << 30))  # 4.29 GB a pass
    # a row-heavy bucket: 751 x 4096 x 16 cells x 4 B = 196 870 144 B a read;
    # 10 iterations from a point that is not zero: 22 reads; one rescoring
    # read of its 5 255 525 kept rows
    movie = {"buckets": [{"entities": 751, "rows": 4096, "d": 16}], "kept_rows": 5_255_525, "d": 16}
    assert work_game.re_sweep_bytes(movie, [10], from_zero=False) \
        == 196_870_144 * 22 + 5_255_525 * 64
    assert work_game.re_sweep_bytes(movie, [10], from_zero=True) \
        == 196_870_144 * 21 + 5_255_525 * 64


@pytest.mark.parametrize("seconds,want_ms,want_share", [
    (0.5, 250.0, 100.0 * 8.19e9 / 819e9 / 0.5), (None, None, None), (0.0, 0.0, None)])
def test_re_rows_metric_readers(run_mod, monkeypatch, seconds, want_ms, want_share):
    """By module name over the traced steps; nothing where nothing is read,
    and a share of a roofline is never 0."""
    from benchmarks.lib import trace

    monkeypatch.setattr(trace, "program_seconds",
                        lambda traced, names: seconds if names == ["jit_re_sweep"] else None)
    run = {"trace": {"steps": [0, 1]}, "programs": {"re_rows": ["jit_re_sweep"]},
           "block": {"re_step_bytes": [1.0, 4.19e9, 4.0e9]}, "steps": [{}, {}],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    ms = run_mod.load_module("metrics", "re_rows_solve_ms").read(run)
    share = run_mod.load_module("metrics", "re_rows_roofline").read(run)
    assert ms == want_ms
    assert share == (pytest.approx(want_share) if want_share else None)
    # a tree or a cell without the programs: nothing, and no error
    bare = {**run, "programs": {}, "block": {}}
    assert run_mod.load_module("metrics", "re_rows_solve_ms").read(bare) is None
    assert run_mod.load_module("metrics", "re_rows_roofline").read(bare) is None
