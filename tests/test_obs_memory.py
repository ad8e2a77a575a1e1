"""Capacity & numerical-health observability (photon_tpu/obs/{memory,health}).

Pins the ISSUE 7 acceptance surface:

- the memory ledger's static executable footprints (XLA's own
  ``memory_analysis`` accounting, nonzero for every AOT program),
  phase-boundary live censuses, transfer counters, and the
  ``memory_report.json`` artifact;
- STEADY-STATE NEUTRALITY: enabling the ledger + health monitor adds
  ZERO dispatches and ZERO read-backs to a sweep (the health scalars
  ride the existing barrier fetch);
- the divergence policies: an injected-NaN fit fails at the next sweep
  boundary under the default ``"raise"`` policy, ``"warn"`` completes,
  ``"halt_coordinate"`` freezes exactly the offender;
- ``util/force.queue_scalars`` (the combined barrier+health fetch);
- ``scripts/bench_trend.py`` ingest/align/verdict semantics.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import obs
from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.data import CSRMatrix, GameData
from photon_tpu.game.descent import run_coordinate_descent
from photon_tpu.game.estimator import GameEstimator
from photon_tpu.obs.health import (
    DivergenceError,
    resolve_policy,
    sweep_health,
)
from photon_tpu.obs.memory import MemoryLedger
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType
from photon_tpu.util.force import queue_scalars

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs():
    """Start and end with the pipeline off and the ledger empty (other
    suites rely on telemetry being a disabled no-op)."""
    obs.reset()
    obs.disable()
    obs.memory.get_ledger().clear()
    yield
    obs.reset()
    obs.disable()
    obs.memory.get_ledger().clear()


def _opt(max_iterations=4):
    return GLMProblemConfig(
        task=TaskType.LINEAR_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        optimizer_config=OptimizerConfig(max_iterations=max_iterations),
    )


def _small_fit(seed=3, n=300, users=24, d_fe=5, d_re=3, sweeps=2,
               poison=None, **est_kw):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, users, size=n)
    x = rng.normal(size=(n, d_fe))
    xr = rng.normal(size=(n, d_re))
    y = x @ rng.normal(size=d_fe) * 0.3 + rng.normal(size=n) * 0.1
    if poison == "label_nan":
        y = y.copy()
        y[7] = np.nan
    data = GameData.build(
        labels=y,
        feature_shards={
            "g": CSRMatrix.from_dense(x),
            "u": CSRMatrix.from_dense(xr),
        },
        id_tags={"userId": [f"u{i}" for i in ids]},
    )
    est = GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectCoordinateConfig(
                feature_shard="g",
                optimization=_opt(),
                regularization_weights=(1.0,),
            ),
            "user": RandomEffectCoordinateConfig(
                random_effect_type="userId",
                feature_shard="u",
                optimization=_opt(),
                regularization_weights=(1.0,),
            ),
        },
        update_sequence=["fixed", "user"],
        descent_iterations=sweeps,
        seed=seed,
        **est_kw,
    )
    return est, data


# ---------------------------------------------------------------------------
# ledger units
# ---------------------------------------------------------------------------


def test_ledger_census_groups_and_peak():
    ledger = MemoryLedger()
    # big enough to own the top of the by-bytes group ranking even in a
    # test process with other live arrays
    keep = [
        jnp.ones((512, 128), jnp.float32),
        jnp.ones((512, 128), jnp.float32),
        jnp.zeros((7,)),
    ]
    row = ledger.census("unit")
    assert row["phase"] == "unit"
    assert row["live_bytes"] > 0 and row["n_arrays"] >= len(keep)
    by_key = {
        (g["dtype"], tuple(g["shape"])): g for g in row["groups"]
    }
    g = by_key.get(("float32", (512, 128)))
    assert g is not None and g["count"] >= 2
    assert g["bytes"] >= 2 * 512 * 128 * 4
    # peak is a high-watermark across censuses
    rep = ledger.report()
    assert rep["peak_live_bytes"] == row["live_bytes"]
    del keep


def test_ledger_records_nonzero_static_footprint():
    ledger = MemoryLedger()
    compiled = (
        jax.jit(lambda a: (a @ a).sum())
        .lower(jax.ShapeDtypeStruct((32, 32), jnp.float32))
        .compile()
    )
    entry = ledger.record_executable("unit:prog", compiled)
    assert entry["argument_bytes"] == 32 * 32 * 4
    assert entry["total_bytes"] > 0
    rep = ledger.report()
    assert rep["executables_total"]["n_analyzed"] == 1
    # a non-analyzable object records an error entry, never raises
    bad = ledger.record_executable("unit:bad", object())
    assert "error" in bad


def test_executable_footprints_survive_obs_reset():
    """A scorer precompiled BEFORE obs.enable() must still appear in the
    exported report: obs.reset() is an artifact boundary for censuses
    and counters, not for process-lifetime compiled programs."""
    ledger = obs.memory.get_ledger()
    compiled = (
        jax.jit(lambda a: a + 1)
        .lower(jax.ShapeDtypeStruct((8,), jnp.float32))
        .compile()
    )
    ledger.record_executable("unit:kept", compiled)
    obs.enable()
    ledger.census("before_reset")
    obs.reset()
    rep = ledger.report()
    assert "unit:kept" in rep["executables"]
    assert rep["censuses"] == [] and rep["peak_live_bytes"] == 0


def test_census_gated_off_without_obs(monkeypatch):
    obs.disable()
    assert obs.memory.census("nope") is None
    obs.enable()
    monkeypatch.setenv("PHOTON_OBS_MEM", "0")
    assert obs.memory.census("nope") is None
    monkeypatch.delenv("PHOTON_OBS_MEM")
    assert obs.memory.census("yes")["phase"] == "yes"


# ---------------------------------------------------------------------------
# queue_scalars (the combined barrier + health fetch)
# ---------------------------------------------------------------------------


def test_queue_scalars_values_and_barrier():
    total = jnp.arange(5.0)
    vals = queue_scalars(
        [jnp.asarray(2.5), jnp.asarray(True), 7.0, jnp.asarray(False)],
        barrier=total,
    ).wait()
    assert vals.tolist() == [2.5, 1.0, 7.0, 0.0]
    assert queue_scalars([], barrier=total).wait().tolist() == []
    assert queue_scalars([]).wait().tolist() == []
    assert queue_scalars([3], barrier=None).wait().tolist() == [3.0]


# ---------------------------------------------------------------------------
# fit integration: report contents + artifact
# ---------------------------------------------------------------------------


def test_fit_memory_report_covers_every_aot_executable(tmp_path):
    """Acceptance: every AOT executable of a precompiled fit appears in
    memory_report.json with a NONZERO static footprint, alongside the
    phase censuses and a nonzero H2D placement bill."""
    est, data = _small_fit(precompile=True)
    obs.enable()
    est.fit(data)
    paths = obs.export_artifacts(tmp_path)
    with open(paths["memory"]) as f:
        doc = json.load(f)["memory"]
    execs = doc["executables"]
    for label in ("fixed:sweep", "fixed:score", "user:sweep", "user:score"):
        assert label in execs, sorted(execs)
        assert execs[label]["total_bytes"] > 0, (label, execs[label])
    phases = [c["phase"] for c in doc["censuses"]]
    assert "data_build" in phases and "precompile" in phases
    assert phases.count("sweep_barrier") == est.descent_iterations
    assert doc["peak_live_bytes"] > 0
    assert doc["h2d_bytes"] > 0  # coordinate-build placements counted
    assert doc["d2h_bytes"] > 0  # the per-sweep barrier fetches counted


def test_scorer_precompile_registers_batch_shape_footprint():
    """GameScorer.precompile registers one ledger entry per batch shape
    (acceptance: all scoring batch shapes appear in the report)."""
    from photon_tpu.game.model import FixedEffectModel, GameModel
    from photon_tpu.game.scoring import GameScorer
    from photon_tpu.models.coefficients import Coefficients
    from photon_tpu.models.glm import model_for_task

    rng = np.random.default_rng(0)
    n, d = 100, 6
    data = GameData.build(
        labels=rng.normal(size=n),
        feature_shards={"g": CSRMatrix.from_dense(rng.normal(size=(n, d)))},
    )
    model = GameModel(
        coordinates={
            "fixed": FixedEffectModel(
                model=model_for_task(
                    TaskType.LINEAR_REGRESSION,
                    Coefficients(means=jnp.asarray(rng.normal(size=d))),
                ),
                feature_shard="g",
            )
        },
        task=TaskType.LINEAR_REGRESSION,
    )
    scorer = GameScorer(model, batch_rows=64)
    scorer.precompile(ell_widths={"g": d})
    rep = obs.memory.get_ledger().report()
    score_labels = [k for k in rep["executables"] if k.startswith("score:")]
    assert len(score_labels) == 1
    assert rep["executables"][score_labels[0]]["total_bytes"] > 0
    # streaming a dataset takes start/end censuses and counts transfers
    obs.enable()
    scorer.score_data(data)
    rep = obs.memory.get_ledger().report()
    phases = [c["phase"] for c in rep["censuses"]]
    assert "stream_start" in phases and "stream_end" in phases
    assert rep["h2d_bytes"] > 0 and rep["d2h_bytes"] > 0


# ---------------------------------------------------------------------------
# steady-state neutrality (the hard acceptance gate)
# ---------------------------------------------------------------------------


def test_ledger_and_health_add_zero_dispatches_and_readbacks(monkeypatch):
    """A/B: with the memory ledger + health monitor ENABLED, the
    per-sweep dispatch count and the read-back count are identical to a
    fully-disabled run — censuses are host metadata, and the health
    scalars ride the EXISTING barrier fetch."""
    import photon_tpu.game.descent as descent_mod

    readbacks = {"n": 0}
    real_force = descent_mod.force
    real_fetch = descent_mod.queue_scalars

    def counting_force(*a, **kw):
        readbacks["n"] += 1
        return real_force(*a, **kw)

    def counting_fetch(*a, **kw):
        readbacks["n"] += 1
        return real_fetch(*a, **kw)

    monkeypatch.setattr(descent_mod, "force", counting_force)
    monkeypatch.setattr(descent_mod, "queue_scalars", counting_fetch)

    def run(enabled):
        obs.reset()
        (obs.enable if enabled else obs.disable)()
        est, data = _small_fit(sweeps=3)
        readbacks["n"] = 0
        result = est.fit(data)[0]
        rows = [
            r["dispatches"] for r in result.tracker if "sweep_seconds" in r
        ]
        return rows, readbacks["n"]

    rows_off, rb_off = run(enabled=False)
    rows_on, rb_on = run(enabled=True)
    assert rows_on == rows_off
    assert rb_on == rb_off
    # one combined barrier+health fetch per sweep, nothing else
    assert rb_off == 3
    # one program per coordinate, plus the barrier's one read-back program
    assert all(d == 3 for d in rows_off)
    # and the enabled run actually took its censuses (it measured, for
    # free, what the disabled run didn't)
    snap = obs.get_registry().snapshot()
    assert snap["counters"]["mem.censuses"] >= 3
    assert snap["counters"]["health.checks"] == 3


# ---------------------------------------------------------------------------
# divergence policies
# ---------------------------------------------------------------------------


def test_injected_nan_fails_at_sweep_boundary_by_default():
    """Acceptance: a poisoned fit fails loudly at the NEXT SWEEP
    BOUNDARY under the default policy instead of silently writing NaN
    checkpoints/models, and the failure is attributed."""
    est, data = _small_fit(poison="label_nan")
    assert est.on_divergence == "raise"  # the default
    with pytest.raises(DivergenceError) as exc:
        est.fit(data)
    assert exc.value.iteration == 0
    assert exc.value.coordinate in ("fixed", "user")
    assert exc.value.health["finite"] is False


def test_divergence_failure_emits_lifecycle_event():
    from photon_tpu.util import EventEmitter

    seen = []
    emitter = EventEmitter()
    emitter.register(lambda e: seen.append(e))
    est, data = _small_fit(poison="label_nan", events=emitter)
    with pytest.raises(DivergenceError):
        est.fit(data)
    names = [e.name for e in seen]
    assert "training_failure" in names
    failure = next(e for e in seen if e.name == "training_failure")
    assert "DivergenceError" in failure.payload["error"]


def test_on_divergence_warn_completes_and_records_health():
    est, data = _small_fit(poison="label_nan", on_divergence="warn")
    result = est.fit(data)[0]
    rows = [r for r in result.tracker if "health" in r]
    assert len(rows) == est.descent_iterations
    assert any(
        not h["finite"] for row in rows for h in row["health"].values()
    )


def test_on_divergence_env_override_and_validation(monkeypatch):
    assert resolve_policy(None) == "raise"
    monkeypatch.setenv("PHOTON_ON_DIVERGENCE", "warn")
    assert resolve_policy(None) == "warn"
    est, _ = _small_fit()
    assert est.on_divergence == "warn"
    with pytest.raises(ValueError, match="on_divergence"):
        resolve_policy("explode")
    with pytest.raises(ValueError, match="on_divergence"):
        _small_fit(on_divergence="explode")


class _StubCoordinate:
    """Minimal Coordinate for descent-level policy mechanics: 'bad'
    diverges on sweep 0, then must be re-initialized and frozen while
    'good' keeps training."""

    mesh = None

    def __init__(self, n, diverge_on=None):
        self.n = n
        self.diverge_on = diverge_on
        self.sweeps_run = 0
        self.reinitialized = 0

    def initial_state(self):
        self.reinitialized += 1
        return jnp.zeros((2,))

    def score(self, state):
        return jnp.full((self.n,), float(jnp.sum(state)))

    def sweep_step(self, total, score, state, donate=None):
        self.sweeps_run += 1
        bad = self.diverge_on == self.sweeps_run
        new_state = state + (jnp.nan if bad else 1.0)
        new_score = self.score(new_state)
        residual = total - score
        health = {
            "loss": jnp.asarray(jnp.nan if bad else 1.0, jnp.float32),
            "gnorm": jnp.asarray(0.5, jnp.float32),
            "finite": jnp.asarray(not bad),
        }
        return new_state, new_score, residual + new_score, {}, health


def test_halt_coordinate_freezes_only_the_offender():
    coords = {
        "good": _StubCoordinate(16),
        "bad": _StubCoordinate(16, diverge_on=1),
    }
    result = run_coordinate_descent(
        coords, ["good", "bad"], 3, on_divergence="halt_coordinate"
    )
    # the offender ran once, was re-initialized (initial_state called at
    # descent entry AND at recovery), and sat out sweeps 1-2
    assert coords["bad"].sweeps_run == 1
    assert coords["bad"].reinitialized == 2
    assert coords["good"].sweeps_run == 3
    assert (np.asarray(result.states["bad"]) == 0).all()
    assert np.isfinite(np.asarray(result.states["good"])).all()
    rows = [r for r in result.tracker if "health" in r]
    assert not rows[0]["health"]["bad"]["finite"]
    assert "bad" not in rows[1]["health"]  # frozen: no step, no health


# ---------------------------------------------------------------------------
# bench integration: quality band + trend gate
# ---------------------------------------------------------------------------


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: a healthy meshed 1-vs-8 A/B section (bench._mesh_scaling_ab row) —
#: the glmix bands require it, like the cache section: a published row
#: with the mesh leg silently missing is a capacity claim with no
#: evidence behind it
_HEALTHY_MESH = {
    "parity_max_abs": 1e-13,
    "steady_compiles": 0,
    "audit_findings": 0,
    "table_shard_ratio": 5.3,
}


def test_quality_band_requires_memory_columns():
    from bench import check_quality_bands

    healthy = {
        "scale": "smoke",
        "grouped_auc": {"value": 0.9},
        "mem": {"peak_bytes": 123456, "exec_temp_bytes": 789},
        "cache": {"parity_max_abs": 0.0, "warm_decode_spans": 0},
        "mesh": dict(_HEALTHY_MESH),
    }
    assert check_quality_bands("glmix_game_estimator", healthy) == []
    for broken in (
        {},
        {"mem": {}},
        {"mem": {"peak_bytes": 0, "exec_temp_bytes": 1}},
        {"mem": {"peak_bytes": 100}},
    ):
        detail = dict(healthy, **broken)
        if "mem" in broken:
            detail["mem"] = broken["mem"]
        else:
            detail.pop("mem")
        violations = check_quality_bands("game_ctr_scale", detail)
        assert any("mem." in v for v in violations), (broken, violations)


def _bench_round(tmp_path, name, configs, metric_version=4, wrap=None):
    payload = {"metric_version": metric_version, "configs": configs}
    doc = payload if wrap is None else wrap(payload)
    p = tmp_path / f"{name}.json"
    p.write_text(json.dumps(doc))
    return str(p)


def _cfg(eps, backend="cpu", scale="smoke", **extra):
    return {
        "examples_per_sec": eps,
        "backend": backend,
        "scale": scale,
        "grouped_auc": {"value": 0.9},
        "mem": {"peak_bytes": 1000, "exec_temp_bytes": 10},
        "cache": {"parity_max_abs": 0.0, "warm_decode_spans": 0},
        "mesh": dict(_HEALTHY_MESH),
        **extra,
    }


def test_bench_trend_ingests_all_formats_and_exits_zero(tmp_path, capsys):
    trend = _load_script("bench_trend")
    _bench_round(
        tmp_path, "BENCH_r01", {"glmix_game_estimator": _cfg(100.0)},
        wrap=lambda p: {"rc": 0, "parsed": p, "tail": ""},
    )
    _bench_round(
        tmp_path, "BENCH_r02", {"glmix_game_estimator": _cfg(110.0)},
        wrap=lambda p: {"rc": 0, "parsed": None, "tail": json.dumps(p)},
    )
    # an unparseable (failed) round is reported, never fatal
    (tmp_path / "BENCH_r00.json").write_text(
        json.dumps({"rc": 1, "parsed": None, "tail": "Traceback ..."})
    )
    rc = trend.main(
        ["--history", str(tmp_path / "BENCH_r*.json")]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "glmix_game_estimator" in out
    assert "skipped BENCH_r00" in out
    assert "BENCH_r01" in out and "BENCH_r02" in out


def test_bench_trend_verdicts(tmp_path, capsys):
    trend = _load_script("bench_trend")
    _bench_round(
        tmp_path, "BENCH_r01", {"glmix_game_estimator": _cfg(100.0)}
    )
    out_doc = tmp_path / "trend.json"

    def run(fresh_cfg, extra=()):
        fresh = _bench_round(tmp_path, "fresh_run", fresh_cfg)
        return trend.main(
            [
                "--history", str(tmp_path / "BENCH_r*.json"),
                "--fresh", fresh, "--out", str(out_doc), *extra,
            ]
        )

    # healthy: within tolerance of the comparable row
    assert run({"glmix_game_estimator": _cfg(90.0)}) == 0
    doc = json.loads(out_doc.read_text())
    (v,) = doc["verdicts"]
    assert v["status"] == "ok" and v["vs"]["ratio"] == 0.9

    # regression beyond tolerance fails
    assert run({"glmix_game_estimator": _cfg(50.0)}) == 3

    # non-comparable series (different scale) never reads as regression
    assert run({"glmix_game_estimator": _cfg(50.0, scale="cpu")}) == 0

    # a quality-band violation in the fresh run fails regardless of trend
    bad = _cfg(100.0)
    bad.pop("mem")
    assert run({"glmix_game_estimator": bad}) == 3


def test_bench_trend_over_a_recorded_history(tmp_path, capsys):
    """Acceptance: the gate runs over a history shaped like the driver's
    records — two failed rounds, then rounds whose rows ran at another
    scale than the fresh one — plus a fresh smoke row, and exits 0 with a
    trajectory table."""
    trend = _load_script("bench_trend")
    for name, tail in (
        ("BENCH_r01", "RuntimeError: backend unavailable"),
        ("BENCH_r02", "EOFError"),
    ):
        (tmp_path / f"{name}.json").write_text(
            json.dumps({"n": 1, "rc": 1, "parsed": None, "tail": tail})
        )
    for name, eps in (("BENCH_r03", 80.0), ("BENCH_r04", 95.0)):
        _bench_round(
            tmp_path, name,
            {"glmix_game_estimator": _cfg(eps, scale="cpu")},
            wrap=lambda p: {"n": 3, "rc": 0, "parsed": None,
                            "tail": json.dumps(p)},
        )
    _bench_round(
        tmp_path, "BENCH_r05", {"glmix_game_estimator": _cfg(110.0)},
        wrap=lambda p: {"n": 5, "rc": 0, "parsed": p, "tail": ""},
    )
    fresh = _bench_round(
        tmp_path, "fresh_run", {"glmix_game_estimator": _cfg(123.0)}
    )
    rc = trend.main(
        [
            "--history", str(tmp_path / "BENCH_r*.json"),
            "--fresh", fresh,
            "--out", str(tmp_path / "trend.json"),
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "glmix_game_estimator" in out and "fresh:" in out
    assert "skipped BENCH_r01" in out and "skipped BENCH_r02" in out


# ---------------------------------------------------------------------------
# in-program health fold units
# ---------------------------------------------------------------------------


def test_sweep_health_triple():
    from photon_tpu.optimize.common import OptimizeResult

    def res(value, grad):
        return OptimizeResult(
            x=jnp.zeros(2), value=jnp.asarray(value),
            gradient=jnp.asarray(grad), iterations=jnp.asarray(1),
            reason=jnp.asarray(2), loss_history=jnp.zeros(2),
            grad_norm_history=jnp.zeros(2),
        )

    h = sweep_health(jnp.ones(3), res(2.0, [3.0, 4.0]))
    assert float(h["loss"]) == 2.0
    assert float(h["gnorm"]) == pytest.approx(5.0)
    assert bool(h["finite"])
    # list form (RE multi-bucket): losses sum, gradients pool
    h = sweep_health(
        [jnp.ones((2, 2))], [res(1.0, [3.0, 4.0]), res(2.0, [0.0, 0.0])]
    )
    assert float(h["loss"]) == 3.0
    assert bool(h["finite"])
    # a NaN anywhere in the STATE flips the sentinel even with finite loss
    h = sweep_health(jnp.array([1.0, jnp.nan]), res(1.0, [0.0, 0.0]))
    assert not bool(h["finite"])
