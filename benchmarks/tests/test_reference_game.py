"""``glmix_ctr.sweeps`` at its tiny shapes on the CPU, through the very code
that decides ``correct`` (``run.main``): the program against the plain GLMix
reference comes out correct; the bfloat16 control does not; nor does a run
whose descent is broken underneath, once for each fault the limits were set
against. And the reference's own pieces by hand: the line search's
conditions, the solver on a problem with a known answer, and the planted
faults of ``reference_game`` against the faults planted in the program."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np
import pytest

from benchmarks.tests.test_reference import drive, run_mod, _f32_like_the_chip  # noqa: F401

CELL = "glmix_ctr.sweeps"


def idle_single(state):
    """Half of the single-row entities are left at their start: every other
    entity of the per-user coordinate's one-row bucket weighs nothing, so
    its solve ends where it began."""
    bucket = state.built.coordinates["per_user"].device_buckets[0]
    assert bucket.features.shape[1] == 1
    bucket.train_weights = bucket.train_weights.at[::2].set(0.0)


def stale_last(state):
    """The per-item coordinate is trained on stale scores: its sweep step is
    handed the total as it stood before the per-user coordinate's update."""
    coords = state.built.coordinates
    user, item = coords["per_user"], coords["per_item"]
    user_step, item_step = user.sweep_step, item.sweep_step
    seen = {}

    def user_sweep(total, score, coord_state, donate=None):
        seen["old"] = np.asarray(score).copy()
        out = user_step(total, score, coord_state, donate=donate)
        seen["new"] = np.asarray(out[1]).copy()
        return out

    def item_sweep(total, score, coord_state, donate=None):
        import jax.numpy as jnp

        stale = total - jnp.asarray(seen["new"]) + jnp.asarray(seen["old"])
        new_state, new_score, _, info, health = item_step(
            stale, score, coord_state, donate=donate)
        return new_state, new_score, total - score + new_score, info, health

    user.sweep_step, item.sweep_step = user_sweep, item_sweep


def steepest_fixed(state):
    """The fixed effect's L-BFGS keeps no curvature pair: every direction of
    its solves is the negative gradient, as ``reference_game``'s fault of
    the same name (the random effects keep theirs: the patch is in place
    only while the fixed effect's program is traced)."""
    from photon_tpu.optimize import lbfgs

    fe = state.built.coordinates["fixed"]
    step, real = fe.sweep_step, lbfgs._CURVATURE_EPS

    def sweep(*args, **kwargs):
        lbfgs._CURVATURE_EPS = float("inf")
        try:
            return step(*args, **kwargs)
        finally:
            lbfgs._CURVATURE_EPS = real

    fe.sweep_step = sweep


def short_fixed(state):
    """The fixed effect's solves end at half their stated iterations."""
    import dataclasses

    fe = state.built.coordinates["fixed"]
    config = fe.problem.config
    half = dataclasses.replace(config.optimizer_config,
                               max_iterations=config.optimizer_config.max_iterations // 2)
    fe.problem = dataclasses.replace(
        fe.problem, config=dataclasses.replace(config, optimizer_config=half))


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


@pytest.mark.parametrize("fault", [idle_single, stale_last], ids=lambda f: f.__name__)
def test_broken_descent_is_not_correct(run_mod, capsys, monkeypatch, fault):
    result = drive(run_mod, capsys, monkeypatch, CELL, fault=fault)
    assert result["correct"] is False
    failed = {k for k, v in result["check"].items() if v["value"] > v["limit"]}
    assert "x_diff" in failed  # the coefficients are what such a descent gets wrong


def test_fixed_effect_without_its_history_is_not_correct(run_mod, capsys, monkeypatch):
    """The first sweep's fixed-effect solve is held to the reference's OWN
    solve iteration by iteration: steepest descent parts from it at the
    second iteration (read 2.6e-3 against the rehearsal's limit 2e-5), while
    the tables, trained on whatever fixed effect the program reached, still
    agree."""
    result = drive(run_mod, capsys, monkeypatch, CELL, fault=steepest_fixed)
    assert result["correct"] is False and result["failed"] == 0
    failed = {k for k, v in result["check"].items() if v["value"] > v["limit"]}
    assert failed == {"loss_gap"}


def test_fixed_effect_solve_cut_short_is_not_correct(run_mod, capsys, monkeypatch):
    """The cell states no tolerance for the fixed effect: a solve may end on
    its 10 iterations or on a line search that failed, and on nothing else.
    Every first step (they are the ones whose solves' ends are read) counts
    as failed."""
    result = drive(run_mod, capsys, monkeypatch, CELL, fault=short_fixed)
    assert result["correct"] is False
    assert result["failed"] == run_mod.FIRST_STEPS


@pytest.mark.parametrize("reason, iterations, tolerance, allowed", [
    ("MAX_ITERATIONS", 10, -1.0, True),
    ("MAX_ITERATIONS", 5, -1.0, False),  # cut short
    ("OBJECTIVE_NOT_IMPROVING", 5, -1.0, True),  # a failed line search ends a solve
    ("FUNCTION_VALUES_CONVERGED", 5, -1.0, False),  # no tolerance is stated
    ("GRADIENT_CONVERGED", 5, -1.0, False),
    ("FUNCTION_VALUES_CONVERGED", 5, 1e-7, True),
    ("NOT_CONVERGED", 3, -1.0, False),
])
def test_what_may_end_a_fixed_effect_solve(reason, iterations, tolerance, allowed):
    import types

    from benchmarks import run as harness
    from photon_tpu.optimize.common import ConvergenceReason

    runner = harness.load_module("runners", "game")
    solver = {"fe_max_iterations": 10, "fe_tolerance": tolerance}
    info = types.SimpleNamespace(reason=int(ConvergenceReason[reason]), iterations=iterations)
    assert runner._ended_as_stated(solver, info) is allowed


def test_the_reference_plants_the_same_faults():
    """``reference_game``'s own faults, which the chip's readings use, move
    the coefficients as far as the faults planted in the program do."""
    from benchmarks import run as harness

    cell = harness.load_json("benchmarks", "workloads", f"{CELL}.json")
    config = harness.load_json("benchmarks", "configs", f"{cell['config']}.json")
    config = {**config, **config["rehearse"]}
    runner = harness.load_module("runners", "game")
    struct_inputs = _inputs(config, seed=5)
    sound = runner.reference_record(config, struct_inputs, 1)
    change = np.linalg.norm(sound["x"])
    for fault, least in (("idle_single", 0.3), ("stale_last", 0.1)):
        broken = runner.reference_record(config, {**struct_inputs, "fault": fault}, 1)
        assert np.linalg.norm(broken["x"] - sound["x"]) / change > least, fault
        assert broken["loss"][0] == sound["loss"][0] and broken["loss"][-1] != sound["loss"][-1]
    # a fixed effect without its history parts from the sound one at the
    # second iteration of its first solve, and nowhere before
    k = config["follow_fe_iterations"]
    steepest = runner.reference_record(config, {**struct_inputs, "fault": "steepest_fixed"}, 1)
    np.testing.assert_array_equal(steepest["loss"][:2], sound["loss"][:2])
    assert np.max(np.abs(steepest["loss"][2: k + 1] / sound["loss"][2: k + 1] - 1)) > 2e-4  # read 3.5e-4
    # the fit descends, and its record has the shape check.py reads: the
    # first fixed-effect solve's K iterations, then the sweeps
    assert np.all(np.diff(sound["loss"]) < 0) and len(sound["loss"]) == k + 3
    assert sound["iterations"] == k + 2 and sound["reason"] == "max_iterations"
    assert sound["x"].shape == struct_inputs["w0"].shape


def _inputs(config, seed):
    """The reference's inputs without the program: every row active."""
    from benchmarks.lib import datagen_game

    struct = datagen_game.structure(config)
    vals = datagen_game.values(config, struct, seed)
    inputs = {"fe_cols": struct["fe_cols"], "fe_vals": vals["fe_vals"], "labels": vals["labels"],
              "_shared": {}}
    size = config["features"]["d"]
    for name, re in config["random_effects"].items():
        counts = np.bincount(struct[name], minlength=re["entities"])
        rank = np.zeros(len(struct[name]), np.int64)
        order = np.argsort(struct[name], kind="stable")
        starts = np.concatenate(([0], np.cumsum(counts)))[:-1]
        rank[order] = np.arange(len(order)) - starts[struct[name][order]]
        inputs[name + ".ids"] = struct[name]
        inputs[name + ".features"] = vals[name]
        inputs[name + ".active"] = rank < re["cap"]  # the first ``cap`` rows of each entity
        size += re["entities"] * re["d"]
    inputs["w0"] = np.zeros(size, np.float32)
    return inputs


def test_lbfgs_solves_a_ridge_like_logistic_problem_to_its_optimum():
    """A batch of small logistic regressions: at the solver's end every
    lane's gradient is (near) zero, lanes do not see each other (a lane
    solved alone ends at the same point), and a padded row of weight 0
    changes nothing."""
    from benchmarks.lib import reference_game as rg

    rng = np.random.default_rng(3)
    b, r, d = 7, 12, 4
    feats = rng.normal(size=(b, r, d))
    labels = (rng.uniform(size=(b, r)) < 0.4).astype(float)
    offsets = 0.1 * rng.normal(size=(b, r))

    def batch(sel, weights):
        f = feats[sel]
        return rg.Batch(lambda v: np.einsum("brd,bd->br", f, v),
                        lambda q: np.einsum("brd,br->bd", f, q),
                        labels[sel], offsets[sel], weights, 1.0)

    ones = np.ones((b, r))
    res = rg.lbfgs(batch(slice(None), ones), np.zeros((b, d)), max_iterations=50, tolerance=1e-12)
    # (a lane may stop on its loss change first: 1.8e-6 is the widest read)
    assert np.all(np.linalg.norm(res["gradient"], axis=1) < 1e-5)
    assert np.all(res["reason"] != rg.NOT_CONVERGED) and np.all(res["iterations"] < 50)
    alone = rg.lbfgs(batch(slice(2, 3), ones[2:3]), np.zeros((1, d)), max_iterations=50,
                     tolerance=1e-12)
    np.testing.assert_array_equal(alone["x"][0], res["x"][2])
    # history: entry i is the loss after iteration i, padded with the last
    it = int(res["iterations"][2])
    assert res["loss"][2, it] == res["value"][2] == res["loss"][2, -1]
    assert np.all(np.diff(res["loss"][2, : it + 1]) <= 0)
    # a lane stopped by the iteration cap says so
    capped = rg.lbfgs(batch(slice(None), ones), np.zeros((b, d)), max_iterations=2, tolerance=1e-12)
    assert set(capped["reason"]) == {rg.MAX_ITERATIONS} and set(capped["iterations"]) == {2}


def test_wolfe_search_meets_both_conditions():
    from benchmarks.lib import reference_game as rg

    # phi(a) = (a - t)^2 per lane, slope 2 (a - t): minimum at t
    t = np.array([0.3, 1.0, 5.0, 40.0])
    f0, dphi0 = t ** 2, -2.0 * t
    step, value, found, trials = rg.wolfe_search(
        lambda a: ((a - t) ** 2, 2.0 * (a - t)), f0, dphi0, np.ones(4))
    assert found.all() and np.all(trials >= 1)
    assert np.all(value <= f0 + 1e-4 * step * dphi0)  # sufficient decrease
    assert np.all(np.abs(2.0 * (step - t)) <= 0.9 * np.abs(dphi0))  # curvature
    np.testing.assert_allclose(value, (step - t) ** 2)
    # an ascent direction finds nothing and stays put
    step, value, found, _ = rg.wolfe_search(
        lambda a: (1.0 + a, np.ones_like(a)), np.ones(1), np.ones(1), np.ones(1), max_trials=6)
    assert not found[0] and step[0] == 0.0 and value[0] == 1.0
