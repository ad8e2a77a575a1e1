"""Fused sweep execution contracts (game/descent.py + game/coordinate.py).

Pins the three tentpole claims of the fused CD step:
1. DISPATCH MINIMALITY — the steady-state sweep executes exactly ONE
   compiled program per coordinate (all RE buckets inside it), verified
   with jit call counters AND trace counters (no retracing across sweeps
   or λ values).
2. PARITY — fused + donated descent is bit-exact against the unfused
   reference sequence (residual / train / rescore / total as separate
   dispatches), which remains available as ``fused=False``.
3. DONATION — the step actually consumes its total/score/state buffers
   (no fresh steady-state allocations) without any "donated buffer was
   not usable" fallback warnings, while caller-visible snapshots
   (initial_states, best_states) survive.
"""
import collections
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.game import coordinate as coordinate_mod
from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_tpu.game.data import CSRMatrix, GameData, build_random_effect_dataset
from photon_tpu.game.descent import run_coordinate_descent
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType


def _build_coordinates(seed=0, n=500, users=40, d_fe=8, d_re=4):
    """Small GAME fixture: FE + skewed per-user RE, built fresh each call
    so every test owns its jit cache keys (static self)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, users, size=n)
    x = rng.normal(size=(n, d_fe))
    xr = rng.normal(size=(n, d_re))
    y = x @ rng.normal(size=d_fe) * 0.3 + rng.normal(size=n) * 0.1
    data = GameData.build(
        labels=y,
        feature_shards={
            "g": CSRMatrix.from_dense(x),
            "u": CSRMatrix.from_dense(xr),
        },
        id_tags={"userId": [f"u{i}" for i in ids]},
    )
    opt = GLMProblemConfig(
        task=TaskType.LINEAR_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        optimizer_config=OptimizerConfig(max_iterations=5),
    )
    fe_cfg = FixedEffectCoordinateConfig(
        feature_shard="g", optimization=opt, regularization_weights=(1.0,)
    )
    re_cfg = RandomEffectCoordinateConfig(
        random_effect_type="userId",
        feature_shard="u",
        optimization=opt,
        regularization_weights=(1.0,),
    )
    ds = build_random_effect_dataset(data, re_cfg, seed=seed)
    return {
        "fixed": FixedEffectCoordinate.build(data, fe_cfg),
        "user": RandomEffectCoordinate.build(data, ds, re_cfg),
    }


def _counting(counter, name, orig):
    def wrapper(self, *args, **kwargs):
        counter[name] += 1
        return orig(self, *args, **kwargs)

    return wrapper


def test_fused_sweep_single_program_per_coordinate(monkeypatch):
    """Dispatch-count regression: the steady sweep must launch exactly one
    program per coordinate — the fused ``_sweep_jit`` — and never fall
    back onto the legacy per-train/per-score/per-bucket dispatches."""
    calls = collections.Counter()
    for cls, progs in (
        (
            FixedEffectCoordinate,
            ("_sweep_jit", "_sweep_jit_nodonate", "_train_jit",
             "_score_jit"),
        ),
        (
            RandomEffectCoordinate,
            ("_sweep_jit", "_sweep_jit_nodonate", "_train_all_jit",
             "_train_bucket", "_score_all_jit"),
        ),
    ):
        for prog in progs:
            # both donation variants count as THE fused sweep program
            # (which one is active depends on the backend)
            name = f"{cls.__name__}.{prog.replace('_nodonate', '')}"
            monkeypatch.setattr(
                cls, prog, _counting(calls, name, getattr(cls, prog))
            )

    coords = _build_coordinates()
    n_sweeps = 3
    traces_before = dict(coordinate_mod.TRACE_COUNTERS)
    result = run_coordinate_descent(coords, ["fixed", "user"], n_sweeps)

    # initial scoring: one program per coordinate, once
    assert calls["FixedEffectCoordinate._score_jit"] == 1
    assert calls["RandomEffectCoordinate._score_all_jit"] == 1
    # steady sweeps: one fused program per coordinate per sweep, nothing else
    assert calls["FixedEffectCoordinate._sweep_jit"] == n_sweeps
    assert calls["RandomEffectCoordinate._sweep_jit"] == n_sweeps
    assert calls["FixedEffectCoordinate._train_jit"] == 0
    assert calls["RandomEffectCoordinate._train_all_jit"] == 0
    assert calls["RandomEffectCoordinate._train_bucket"] == 0

    # trace counters: each fused program traced ONCE across all sweeps —
    # a count > 1 means the steady state is retracing/recompiling
    for prog in ("fe_sweep", "re_sweep"):
        traced = coordinate_mod.TRACE_COUNTERS[prog] - traces_before.get(
            prog, 0
        )
        assert traced == 1, f"{prog} traced {traced}x across {n_sweeps} sweeps"

    # the tracker's per-sweep rows record the launch profile
    sweep_rows = [r for r in result.tracker if "sweep_seconds" in r]
    assert len(sweep_rows) == n_sweeps
    # one program a coordinate, plus the barrier's one read-back program
    assert all(r["dispatches"] == len(coords) + 1 for r in sweep_rows)
    assert all(r["granularity"] == "sweep" for r in sweep_rows)


def test_fused_descent_matches_unfused_bit_exact():
    """Fused + donated descent must be BIT-EXACT against the unfused
    reference loop: the fused program chains the identical expression
    tree (residual = total − score; solve; rescore; residual + new
    score), so same inputs ⇒ same bits."""
    n_iter = 3
    fused = run_coordinate_descent(
        _build_coordinates(), ["fixed", "user"], n_iter
    )
    unfused = run_coordinate_descent(
        _build_coordinates(), ["fixed", "user"], n_iter, fused=False
    )
    a, b = np.asarray(fused.states["fixed"]), np.asarray(unfused.states["fixed"])
    assert np.array_equal(a, b), f"FE drift {np.max(np.abs(a - b))}"
    for i, (fa, ub) in enumerate(
        zip(fused.states["user"], unfused.states["user"])
    ):
        fa, ub = np.asarray(fa), np.asarray(ub)
        assert np.array_equal(fa, ub), (
            f"RE bucket {i} drift {np.max(np.abs(fa - ub))}"
        )


def test_fused_sweep_donation_mode_and_no_warnings():
    """Where donation is active (off-CPU; see util/target.donation_enabled —
    XLA:CPU donation corrupts the heap in jaxlib 0.4.37) it must be REAL
    (inputs consumed — the steady state reuses buffers instead of
    allocating) and CLEAN (no 'donated buffer was not usable'
    copy-fallback warnings). Where it is gated off, inputs must survive
    untouched."""
    from photon_tpu.util.target import donation_enabled

    coords = _build_coordinates()
    fe = coords["fixed"]
    state = fe.initial_state()
    score = fe.score(state)
    total = jnp.array(np.asarray(score))  # independent buffer
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        new_state, new_score, new_total, info, health = fe.sweep_step(
            total, score, state
        )
        np.asarray(new_total)
    bad = [str(w.message) for w in rec if "donat" in str(w.message).lower()]
    assert bad == [], f"donation fell back to copies: {bad}"
    inputs = (("total", total), ("score", score), ("state", state))
    if donation_enabled():
        for name, donated in inputs:
            assert donated.is_deleted(), f"{name} buffer was not consumed"
    else:
        for name, kept in inputs:
            assert not kept.is_deleted(), f"{name} consumed with donation off"
        assert (np.asarray(state) == 0).all()
    # outputs stay readable
    assert np.isfinite(np.asarray(new_score)).all()


def test_caller_snapshots_survive_donation(monkeypatch):
    """Caller-provided initial_states and the best-by-validation snapshot
    must survive the donation of the live states they seeded/alias.

    On CPU runners donation is gated off (jaxlib 0.4.37 heap corruption),
    which would leave descent's copy machinery DEAD code — so force
    descent's view of the gate on while aliasing each class's donating
    program to its safe non-donating twin: every ``donating`` copy branch
    executes for real, with no actual CPU donation."""
    import photon_tpu.game.descent as descent_mod

    monkeypatch.setattr(descent_mod, "donation_enabled", lambda: True)
    for cls in (FixedEffectCoordinate, RandomEffectCoordinate):
        monkeypatch.setattr(cls, "_sweep_jit", cls._sweep_jit_nodonate)
    coords = _build_coordinates()
    initial = {
        "fixed": coords["fixed"].initial_state(),
        "user": coords["user"].initial_state(),
    }
    metrics = iter([3.0, 2.0, 1.0])  # sweep 0 is best; later sweeps donate

    result = run_coordinate_descent(
        coords,
        ["fixed", "user"],
        3,
        initial_states=initial,
        validation_fn=lambda states: next(metrics),
        larger_is_better=True,
    )
    # the caller's arrays were not consumed by the first sweep's donation
    assert (np.asarray(initial["fixed"]) == 0).all()
    for leaf in initial["user"]:
        assert (np.asarray(leaf) == 0).all()
    # the sweep-0 best snapshot outlived sweeps 1-2 donating the live state
    assert result.best_metric == 3.0
    assert np.isfinite(np.asarray(result.best_states["fixed"])).all()
    for leaf in result.best_states["user"]:
        assert np.isfinite(np.asarray(leaf)).all()


def test_sweep_callback_snapshots_are_donation_stable(monkeypatch):
    """A callback that retains ``np.asarray`` snapshots of the states it
    receives must see STABLE values: on CPU ``np.asarray`` of a jax array
    is a zero-copy view, and without the copy descent hands the callback,
    the next sweep's donation would rewrite the retained snapshot in
    place (the checkpoint-resume corruption this pins). Descent's gate is
    forced on with the donating programs aliased to their safe twins (see
    test_caller_snapshots_survive_donation) so the copy path runs even on
    CPU runners where donation is disabled."""
    import photon_tpu.game.descent as descent_mod

    monkeypatch.setattr(descent_mod, "donation_enabled", lambda: True)
    for cls in (FixedEffectCoordinate, RandomEffectCoordinate):
        monkeypatch.setattr(cls, "_sweep_jit", cls._sweep_jit_nodonate)
    coords = _build_coordinates()
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
        # re-snapshot WITH an explicit copy as the stability reference
        captured[f"{it}_copy"] = {
            k: (
                [np.array(x) for x in v]
                if isinstance(v, list)
                else np.array(v)
            )
            for k, v in st.items()
        }

    run_coordinate_descent(
        coords, ["fixed", "user"], 3, sweep_callback=capture
    )
    for it in (0, 1, 2):
        view, copy = captured[it], captured[f"{it}_copy"]
        assert np.array_equal(view["fixed"], copy["fixed"]), (
            f"sweep {it} snapshot was rewritten by a later donation"
        )
        for a, b in zip(view["user"], copy["user"]):
            assert np.array_equal(a, b), (
                f"sweep {it} RE snapshot was rewritten by a later donation"
            )


def test_tracker_granularity_modes():
    """"sweep" (default): sync-free steady state, honest wall in the
    per-sweep row. "coordinate": opt-in per-coordinate read-backs.
    Anything else: hard error."""
    result = run_coordinate_descent(
        _build_coordinates(), ["fixed", "user"], 2,
        tracker_granularity="coordinate",
    )
    sweep_rows = [r for r in result.tracker if "sweep_seconds" in r]
    assert all(r["granularity"] == "coordinate" for r in sweep_rows)
    assert all(r["barrier_seconds"] == 0.0 for r in sweep_rows)
    coord_rows = [r for r in result.tracker if "coordinate" in r]
    assert len(coord_rows) == 4  # 2 coordinates × 2 sweeps

    with pytest.raises(ValueError, match="tracker_granularity"):
        run_coordinate_descent(
            _build_coordinates(), ["fixed", "user"], 1,
            tracker_granularity="bogus",
        )
