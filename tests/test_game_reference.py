"""``GameEstimator.build`` + ``run_coordinate_descent`` against the plain
GLMix reference (``benchmarks/lib/reference_game.py``, NumPy float64) at a
small size: coefficients by coordinate, the objective after every sweep, the
whole gradient by coordinate; and ``GameEstimator.fit`` on the same built
handle gives the same model. The program runs float32, as on the chip.

Each tolerance is 10-50x the widest reading of the four seeds tried (in the
comments) and far under what a wrong descent gives: the faults planted in
``benchmarks/tests/test_reference_game.py`` move the coefficients by 0.25
and 0.5 of their norm and the objective by 5-20 %; bfloat16 products move
the coefficients by 5e-3 and the gradient by 4e-3.
"""
import os
import sys

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = {
    "task": "LOGISTIC_REGRESSION",
    "structure_seed": 9,
    "features": {"kind": "sparse", "n": 6144, "d": 384, "nnz_per_row": 8},
    "random_effects": {
        "per_user": {"entities": 2048, "d": 16, "cap": 8},
        "per_item": {"entities": 192, "d": 16, "cap": 48},
    },
    "zipf_a": 1.3,
    "solver": {
        "fe_max_iterations": 10, "fe_ls_max_iterations": 10, "re_max_iterations": 5,
        "re_ls_max_iterations": 8, "history": 10, "fe_tolerance": 1e-7, "re_tolerance": 1e-7, "l2_weight": 1.0,
        "descent_sweeps": 2,
    },
    "follow_fe_iterations": 4,
}


@pytest.fixture(scope="module")
def runner():
    from benchmarks.runners import game

    return game


@pytest.fixture(scope="module")
def fitted(runner):
    """One fit of the small deployment through the runner (build once, drive
    the sweeps, read everything back) and the reference's fit of the same."""
    from benchmarks.lib import reference_game
    from benchmarks.run import Spans

    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # float32, as on the chip
    try:
        state = runner.setup(CONFIG, 31, Spans())
        out = runner.step(state)
        runner.observe(state, out)
        program = state.first[0]
        sweeps = state.sweeps
        built = state.built
        inputs = state.inputs
        reference = runner.reference_record(CONFIG, inputs, 1)
        own = reference_game.Glmix(CONFIG, inputs).descend(2)
        at_x = runner.reference_at(CONFIG, inputs, program["x"], gradient=True)
        yield {"program": program, "reference": reference, "own": own, "at_x": at_x, "out": out,
               "state": state, "built": built, "sweeps": sweeps}
    finally:
        jax.config.update("jax_enable_x64", x64)


def _parts(x):
    d = CONFIG["features"]["d"]
    out, at = {"fixed": x[:d]}, d
    for name, re in CONFIG["random_effects"].items():
        out[name] = x[at: at + re["entities"] * re["d"]]
        at += re["entities"] * re["d"]
    assert at == len(x)
    return out


# Against the reference's OWN fit (float64 throughout). Widest of seeds 31,
# 32, 33, 34: fixed 3e-6, per_user 4e-5, per_item 2e-5 (float32 against
# float64 over 10 + 5 optimizer iterations and two sweeps; a random effect's
# solve starts from the fixed effect's rounded scores). At this size the two
# still walk one path; at the cell's they do not (benchmarks/runners/game.py).
@pytest.mark.parametrize("coordinate,tolerance", [
    ("fixed", 1e-4), ("per_user", 1e-3), ("per_item", 1e-3)])
def test_coefficients_follow_the_reference(fitted, coordinate, tolerance):
    got = _parts(fitted["program"]["x"])[coordinate]
    want = _parts(fitted["own"]["x"])[coordinate]
    assert np.linalg.norm(want) > 1.0
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < tolerance


def test_tables_follow_the_reference_held_to_the_program_s_fixed_effect(fitted):
    """``x`` of the reference's record: every sweep's fixed effect is the
    program's own, the random effects are solved in float64 on its scores.
    The fixed part is then the program's to the bit and the tables are
    sixteen-wide ridge problems: read 1.3e-5 (per_user) and 9e-6 (per_item)."""
    got, want = _parts(fitted["program"]["x"]), _parts(fitted["reference"]["x"])
    np.testing.assert_array_equal(got["fixed"], want["fixed"])
    for name in ("per_user", "per_item"):
        assert np.linalg.norm(got[name] - want[name]) < 2e-4 * np.linalg.norm(want[name]), name


def test_objective_after_every_sweep(fitted):
    """The record's last entries are the whole regularised objective after
    each sweep; the reference's own fit has entry 0 the zero point, entry i
    after sweep i. float32 sums of 6144 losses against float64: read 6e-7."""
    k = CONFIG["follow_fe_iterations"]
    got, want = fitted["program"]["loss"], fitted["own"]["loss"]
    assert len(got) == k + 3 and len(want) == 3 and want[0] > want[1] > want[2]
    np.testing.assert_allclose(got[[0, -2, -1]], want, rtol=1e-5)
    np.testing.assert_allclose(fitted["program"]["gnorm"][-2:], fitted["own"]["gnorm"][1:], rtol=1e-4)
    # and the record held to the program's fixed effects reads the same
    np.testing.assert_allclose(got[-2:], fitted["reference"]["loss"][-2:], rtol=1e-5)
    assert fitted["program"]["iterations"] == fitted["reference"]["iterations"] == k + 2


def test_first_fixed_effect_solve_follows_the_reference_s_own(fitted):
    """Entries 0..K of the record: the first sweep's fixed-effect solve,
    iteration by iteration, the program's L-BFGS (float32) against the
    reference's own solve from zero (float64), which is given nothing of the
    program's. Read over seeds 31-34: loss 5.8e-7 (the zero point's float32
    sum, on every seed), gradient norm 8e-7 to 3.8e-6."""
    k = CONFIG["follow_fe_iterations"]
    got, want = fitted["program"], fitted["reference"]
    assert np.all(np.diff(want["loss"][: k + 1]) < 0)
    np.testing.assert_allclose(got["loss"][: k + 1], want["loss"][: k + 1], rtol=1e-5)
    np.testing.assert_allclose(got["gnorm"][: k + 1], want["gnorm"][: k + 1], rtol=1e-4)
    # the same solve, run on: the own fit's fixed effect started this way
    np.testing.assert_array_equal(want["loss"][: k + 1], fitted["own"]["fe_first"]["loss"][: k + 1])


def test_gradient_at_the_program_s_point(fitted):
    """At the point the program holds, its own objective ops against the
    reference's, by coordinate: no optimizer in between, so this is float32
    rounding alone. Read: fixed 2e-7, per_user 6e-7, per_item 1.4e-5 of the
    coordinate's own gradient norm; per_item was trained last, so its
    gradient is what is left when terms of size 1 cancel to 0.3."""
    got, want = _parts(fitted["program"]["gradient"]), _parts(fitted["at_x"]["gradient"])
    for name in got:
        assert np.linalg.norm(got[name] - want[name]) <= 1e-4 * np.linalg.norm(want[name]), name
    assert abs(fitted["program"]["loss"][-1] - fitted["at_x"]["loss"]) <= 1e-5 * fitted["at_x"]["loss"]


def test_step_counts_the_fixed_effect_s_passes_from_the_barrier(fitted):
    """What a step reports comes from the counters the sweeps' barriers read
    back: the fixed effect's iterations as the reference counts them."""
    from benchmarks.lib import work_game

    fe = fitted["own"]["fe_iterations"]
    assert fitted["out"]["units"] == 2 and fitted["out"]["ok"]
    assert fitted["out"]["passes"] == sum(
        work_game.lbfgs_passes(it, from_zero=i == 0) for i, it in enumerate(fe))
    per_step = fitted["state"].block["re_step_bytes"]
    assert len(per_step) == 1 and per_step[0] > 0


def test_fixed_effect_solves_count_the_passes_that_ran(fitted):
    """The passes over the feature block a fixed-effect solve RAN, beside the
    ones ``work_game`` says it needed: from the zero states the zero point's
    backward pass, 2 an iteration and the last exact re-evaluation's 2; from
    sweep 1's point the start's own evaluation on top. The rescoring reads
    the block no more (it takes the re-evaluation's product)."""
    from benchmarks.lib import work_game

    infos = fitted["state"].fe_infos
    assert len(infos) == 2
    for i, info in enumerate(infos):
        it = int(info.iterations)
        ran = int(info.n_feature_passes)
        assert ran == 2 * it + (3 if i == 0 else 5)
        assert ran - work_game.lbfgs_passes(it, from_zero=i == 0) == (2 if i == 0 else 3)
        assert info.product is None


def test_fit_runs_on_the_built_handle(fitted, runner):
    """``GameEstimator.fit`` is ``build`` + the same descent: its model holds
    the coefficients the driven sweeps ended on, to the bit."""
    from photon_tpu.game.estimator import BuiltFit

    from benchmarks.lib import datagen_game

    built = fitted["built"]
    assert isinstance(built, BuiltFit)
    assert built.update_sequence == ("fixed", "per_user", "per_item")
    assert set(built.prepare_seconds["per_user"]) == {"buckets", "place"}
    assert set(built.prepare_seconds["fixed"]) == {"place"}
    zero = built.initial_states()
    assert float(abs(np.asarray(zero["fixed"])).max()) == 0.0 and len(zero["per_user"]) == len(
        built.coordinates["per_user"].device_buckets)

    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        struct = datagen_game.structure(CONFIG)
        vals = datagen_game.values(CONFIG, struct, 31)
        data = runner.game_data(CONFIG, struct, vals)
        est = runner.estimator(CONFIG)
        model = est.fit(data)[0].model
    finally:
        jax.config.update("jax_enable_x64", x64)
    d = CONFIG["features"]["d"]
    np.testing.assert_array_equal(
        np.asarray(model.coordinates["fixed"].model.coefficients.means, np.float64),
        fitted["program"]["x"][:d])
    tables = _parts(fitted["program"]["x"])
    for name, re in CONFIG["random_effects"].items():
        want = tables[name].reshape(re["entities"], re["d"])
        rem = model.coordinates[name]
        entity = rem.vocab.astype(np.int64)
        for bucket in rem.buckets:
            np.testing.assert_array_equal(
                np.asarray(bucket.coefficients, np.float64), want[entity[bucket.entity_ids]])
