"""Telemetry spine tests (photon_tpu/obs).

Covers the ISSUE 4 acceptance surface: tracer/metrics/exporter units, the
exported Chrome trace-event JSON schema with the nested fit → data build →
precompile → sweep → coordinate taxonomy and per-sweep dispatch/compile
counters, dispatch/read-back neutrality of the disabled tracer, per-fit
(non-cumulative) delta accounting across sequential fits, library-level
lifecycle events, and the metric-shape regression gate
(scripts/check_obs_regression.py).
"""
import importlib.util
import json
import os
import threading

import numpy as np
import pytest

from photon_tpu import obs
from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.data import CSRMatrix, GameData
from photon_tpu.game.estimator import GameEstimator
from photon_tpu.obs import MetricsRegistry, Tracer
from photon_tpu.obs.export import (
    chrome_trace,
    phase_summary,
    summary_table,
    write_run_manifest,
)
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType
from photon_tpu.util import EventEmitter, Timed, compile_watch, dispatch_count

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with the global pipeline empty and OFF
    (other suites rely on telemetry being a disabled no-op)."""
    obs.reset()
    obs.disable()
    yield
    obs.reset()
    obs.disable()


def _opt(max_iterations=4):
    return GLMProblemConfig(
        task=TaskType.LINEAR_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        optimizer_config=OptimizerConfig(max_iterations=max_iterations),
    )


def _small_fit(seed=3, n=300, users=24, d_fe=5, d_re=3, sweeps=2, **est_kw):
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
# tracer / registry units
# ---------------------------------------------------------------------------


def test_tracer_nesting_and_args():
    tr = Tracer(enabled=True, annotate_device=False)
    with tr.span("outer", cat="phase", k=1) as outer:
        with tr.span("inner") as inner:
            inner.set(extra="v")
        tr.instant("marker", why="test")
    recs = {r.name: r for r in tr.spans()}
    assert set(recs) == {"outer", "inner", "marker"}
    assert recs["inner"].parent_id == recs["outer"].span_id
    assert recs["marker"].parent_id == recs["outer"].span_id
    assert recs["outer"].parent_id is None
    assert recs["outer"].args == {"k": 1}
    assert recs["inner"].args == {"extra": "v"}
    assert recs["marker"].instant and recs["marker"].dur_ns == 0
    assert outer.duration_s >= inner.duration_s >= 0


def test_disabled_tracer_measures_but_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("quiet") as sp:
        pass
    tr.instant("quiet-event")
    assert sp.duration_s >= 0  # callers may still read the wall
    assert tr.spans() == []


def test_disabled_span_still_enters_a_photon_annotation(monkeypatch):
    """A profiler capture sees the program's host phases with telemetry
    off: a disabled tracer's span enters ``photon.<name>`` (no IDs: none
    are minted) and still records nothing; an enabled one stamps its ID."""
    import jax.profiler

    log = []

    class Annotation:
        def __init__(self, name, **meta):
            self.name, self.meta = name, meta

        def __enter__(self):
            log.append(("enter", self.name, self.meta))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = Tracer(enabled=False)
    with tr.span("quiet", cat="phase", k=1) as sp:
        assert log == [("enter", "photon.quiet", {})]
    assert log[-1] == ("exit", "photon.quiet")
    assert sp.duration_s >= 0 and tr.spans() == []

    del log[:]
    on = Tracer(enabled=True)
    with on.span("loud"):
        pass
    (rec,) = on.spans()
    assert log == [
        ("enter", "photon.loud", {"span_id": rec.span_id}),
        ("exit", "photon.loud"),
    ]
    del log[:]
    with Tracer(enabled=False, annotate_device=False).span("silent"):
        pass
    assert log == []


def test_span_records_error_class_on_exception():
    tr = Tracer(enabled=True, annotate_device=False)
    with pytest.raises(RuntimeError):
        with tr.span("doomed"):
            raise RuntimeError("boom")
    (rec,) = tr.spans()
    assert rec.args["error"] == "RuntimeError"
    assert rec.dur_ns >= 0


def test_tracer_thread_stacks_are_independent():
    tr = Tracer(enabled=True, annotate_device=False)

    def worker():
        with tr.span("thread-span"):
            pass

    with tr.span("main-span"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    recs = {r.name: r for r in tr.spans()}
    # the other thread's span must NOT parent under main's open span
    assert recs["thread-span"].parent_id is None
    assert recs["thread-span"].tid != recs["main-span"].tid


def test_metrics_registry_and_delta():
    reg = MetricsRegistry()
    reg.counter("a")
    reg.counter("a", 2)
    reg.gauge("g", 7.5)
    for v in (1.0, 3.0, 2.0):
        reg.histogram("h", v)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 7.5
    h = snap["histograms"]["h"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (3, 6.0, 1.0, 3.0)
    assert sum(h["buckets"].values()) == 3  # every sample lands a bucket
    reg.counter("a", 4)
    reg.counter("b")
    d = MetricsRegistry.delta(snap, reg.snapshot())
    assert d["counters"] == {"a": 4, "b": 1}
    json.dumps(snap)  # snapshot must be plain data


def test_histogram_percentiles_within_bucket_resolution():
    """Satellite: streaming p50–p99 from the sparse log buckets must
    land within the documented ~±5% relative resolution, at bounded
    memory (no sample buffer)."""
    reg = MetricsRegistry()
    values = [0.001 * (i + 1) for i in range(1000)]  # 1ms … 1s
    for v in values:
        reg.histogram("lat", v)
    for q in (50, 90, 99):
        true = values[int(len(values) * q / 100) - 1]
        got = reg.percentile("lat", q)
        assert abs(got - true) / true < 0.06, (q, got, true)
    # percentile clamps into the observed range at the extremes
    assert reg.percentile("lat", 100) <= max(values)
    assert reg.percentile("lat", 0.1) >= min(values)
    assert reg.percentile("nope", 50) is None
    # snapshot carries the pNN summaries the exporters render
    h = reg.snapshot()["histograms"]["lat"]
    assert h["p50"] == reg.percentile("lat", 50)
    # bounded memory: 3 decades of range stay at O(log range) buckets
    assert len(h["buckets"]) < 80


def test_histogram_summary_renders_percentiles():
    from photon_tpu.obs.export import histogram_summary

    reg = MetricsRegistry()
    for v in (0.01, 0.02, 0.04):
        reg.histogram("score.batch_seconds", v)
    table = histogram_summary(reg)
    assert "score.batch_seconds" in table
    for col in ("p50", "p90", "p99", "count", "mean"):
        assert col in table
    assert histogram_summary(MetricsRegistry()) == ""


def test_global_instruments_gated_by_enable():
    obs.counter("x.off")
    assert obs.get_registry().snapshot()["counters"] == {}
    obs.enable()
    obs.counter("x.on", 2)
    obs.histogram("h.on", 1.5)
    obs.gauge("g.on", 3.0)
    snap = obs.get_registry().snapshot()
    assert snap["counters"]["x.on"] == 2
    assert snap["histograms"]["h.on"]["count"] == 1
    assert snap["gauges"]["g.on"] == 3.0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _validate_chrome_trace(doc: dict) -> dict:
    """Schema-check a Chrome trace-event JSON object; returns span_id →
    event for the duration events."""
    json.dumps(doc)  # must be serializable as-is
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] in ("ms", "ns")
    by_id = {}
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("M", "X", "i")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["args"], dict)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            by_id[ev["args"]["span_id"]] = ev
        else:
            assert ev["s"] in ("t", "p", "g")
    return by_id


def test_chrome_trace_schema_and_metadata():
    tr = Tracer(enabled=True, annotate_device=False)
    reg = MetricsRegistry()
    with tr.span("a"):
        with tr.span("b", npy=np.int64(3)):
            tr.instant("tick")
    reg.counter("c", 2)
    doc = chrome_trace(tr, reg, meta={"run": "unit"})
    by_id = _validate_chrome_trace(doc)
    names = {ev["name"] for ev in doc["traceEvents"]}
    assert {"process_name", "a", "b", "tick"} <= names
    b = next(e for e in by_id.values() if e["name"] == "b")
    assert b["args"]["npy"] == 3.0  # numpy scalar coerced to JSON number
    assert by_id[b["args"]["parent_id"]]["name"] == "a"
    assert doc["otherData"]["run"] == "unit"
    assert doc["otherData"]["metrics"]["counters"]["c"] == 2


def test_run_manifest_jsonl_and_summary_table(tmp_path):
    tr = Tracer(enabled=True, annotate_device=False)
    reg = MetricsRegistry()
    for _ in range(2):
        with tr.span("phase-x"):
            pass
    reg.counter("n", 5)
    path = write_run_manifest(
        tmp_path / "run.jsonl", tr, reg, meta={"cfg": "t"}
    )
    lines = [json.loads(line) for line in open(path)]
    assert lines[0]["kind"] == "header" and lines[0]["cfg"] == "t"
    assert [ln["kind"] for ln in lines[1:-1]] == ["span", "span"]
    assert lines[-1]["kind"] == "metrics" and lines[-1]["counters"]["n"] == 5
    summary = phase_summary(tr)
    assert summary["phase-x"]["count"] == 2
    assert summary["phase-x"]["total_s"] >= summary["phase-x"]["max_s"]
    table = summary_table(tr)
    assert "phase-x" in table and "total_s" in table
    assert summary_table(Tracer(enabled=True)) == "(no spans recorded)"


def test_exporters_never_throw_on_exotic_args(tmp_path):
    tr = Tracer(enabled=True, annotate_device=False)
    with tr.span("weird", arr=np.arange(3), obj=object(), path=tmp_path):
        pass
    doc = chrome_trace(tr, MetricsRegistry())
    ev = next(e for e in doc["traceEvents"] if e["name"] == "weird")
    assert ev["args"]["arr"] == [0, 1, 2]
    assert isinstance(ev["args"]["obj"], str)
    json.dumps(doc)


# ---------------------------------------------------------------------------
# bridged fragments (Timed, EventEmitter)
# ---------------------------------------------------------------------------


def test_timed_bridges_into_span():
    obs.enable()
    with Timed("bridged-phase"):
        pass
    (rec,) = [r for r in obs.get_tracer().spans() if r.name == "bridged-phase"]
    assert rec.cat == "timed"


def test_event_emitter_mirrors_instant_events():
    obs.enable()
    emitter = EventEmitter()
    emitter.emit("training_start", task="logistic")
    recs = [r for r in obs.get_tracer().spans() if r.name == "training_start"]
    assert len(recs) == 1
    assert recs[0].instant and recs[0].cat == "lifecycle"
    assert recs[0].args == {"task": "logistic"}
    # a payload key colliding with instant()'s own kwargs must neither
    # raise nor skip the listeners
    seen = []
    emitter.register(lambda e: seen.append(e))
    emitter.emit("odd_payload", cat="collides")
    assert [e.name for e in seen] == ["odd_payload"]
    (rec,) = [r for r in obs.get_tracer().spans() if r.name == "odd_payload"]
    assert rec.args["payload"] == {"cat": "collides"}


# ---------------------------------------------------------------------------
# fit integration: span taxonomy + counters in the exported trace
# ---------------------------------------------------------------------------


def test_fit_trace_has_nested_taxonomy_and_counters(tmp_path):
    """Acceptance: the exported Chrome trace contains nested spans for
    fit → data build → precompile → sweep → coordinate, with
    compile/dispatch counters attached to the sweep spans."""
    est, data = _small_fit(precompile=True)
    obs.enable()
    est.fit(data)
    path = obs.write_chrome_trace(tmp_path / "fit.trace.json")
    with open(path) as f:
        doc = json.load(f)
    by_id = _validate_chrome_trace(doc)

    def parent(ev):
        return by_id.get(ev["args"]["parent_id"])

    def events(name):
        return [e for e in by_id.values() if e["name"] == name]

    (fit_ev,) = events("fit")
    assert parent(fit_ev) is None
    for child in ("fit.data_build", "fit.precompile", "fit.grid"):
        (ev,) = events(child)
        assert parent(ev)["name"] == "fit", child
    sweeps = events("descent.sweep")
    assert len(sweeps) == est.descent_iterations
    for sw in sweeps:
        assert parent(sw)["name"] == "fit.grid"
        # per-sweep dispatch/compile attribution rides on the span
        assert isinstance(sw["args"]["dispatches"], int)
        assert sw["args"]["dispatches"] >= 1
        assert sw["args"]["compiles"] >= 0
    coords = events("descent.coordinate")
    assert len(coords) == est.descent_iterations * 2  # fixed + user
    assert {parent(c)["name"] for c in coords} == {"descent.sweep"}
    # fit span carries the per-fit deltas that last_fit_stats reports
    assert fit_ev["args"]["dispatches"] == est.last_fit_stats["dispatches"]


def test_disabled_tracer_is_dispatch_and_readback_neutral(monkeypatch):
    """Acceptance: toggling telemetry must not change the run's device
    profile — identical tracked dispatches per steady-state sweep and
    identical read-back (force) counts either way."""
    import photon_tpu.game.descent as descent_mod

    forces = {"n": 0}
    real_force = descent_mod.force
    real_fetch = descent_mod.fetch_scalars

    def counting_force(*a, **kw):
        forces["n"] += 1
        return real_force(*a, **kw)

    def counting_fetch(*a, **kw):
        # the sweep barrier is a fetch_scalars since the health monitor
        # folded into it — it IS the read-back, so it counts as one
        forces["n"] += 1
        return real_fetch(*a, **kw)

    monkeypatch.setattr(descent_mod, "force", counting_force)
    monkeypatch.setattr(descent_mod, "fetch_scalars", counting_fetch)

    def run(enabled):
        obs.reset()
        (obs.enable if enabled else obs.disable)()
        est, data = _small_fit(sweeps=3)
        forces["n"] = 0
        result = est.fit(data)[0]
        rows = [
            r["dispatches"] for r in result.tracker if "sweep_seconds" in r
        ]
        return rows, forces["n"]

    rows_off, forces_off = run(enabled=False)
    assert obs.get_tracer().spans() == []  # disabled records nothing
    rows_on, forces_on = run(enabled=True)
    assert rows_on == rows_off
    assert forces_on == forces_off
    assert len(rows_off) == 3 and all(d >= 1 for d in rows_off)


def test_two_sequential_fits_report_per_fit_deltas():
    """Satellite: listener registration is idempotent and fit stats are
    per-fit DELTAS — a second fit in the same process reports its own
    bill, not the cumulative process totals."""
    assert compile_watch.install() in (True, False)
    compile_watch.install()  # second call must be a no-op
    assert compile_watch.installed()

    est, data = _small_fit()
    est.fit(data)
    s1 = dict(est.last_fit_stats)
    d0 = dispatch_count.snapshot()
    est.fit(data)
    s2 = dict(est.last_fit_stats)
    # second fit's dispatches == externally measured second-fit delta …
    assert s2["dispatches"] == dispatch_count.snapshot() - d0
    # … and equal to the first fit's own work (same shapes, same grid):
    # cumulative reporting would show ~2× here
    assert s2["dispatches"] == s1["dispatches"]
    assert s2["dispatches"] >= 1
    # warm second fit: compile bill must not accumulate across fits
    assert s2["backend_compiles"] <= s1["backend_compiles"]
    assert s2["wall_s"] > 0


# ---------------------------------------------------------------------------
# lifecycle events from GameEstimator.fit
# ---------------------------------------------------------------------------


def test_fit_emits_lifecycle_events():
    seen = []
    emitter = EventEmitter()
    emitter.register(lambda e: seen.append(e))
    est, data = _small_fit(events=emitter)
    est.fit(data)
    names = [e.name for e in seen]
    assert names[0] == "setup"
    assert names[-1] == "training_finish"
    assert names.count("sweep_complete") == est.descent_iterations
    setup = seen[0].payload
    assert setup["update_sequence"] == ["fixed", "user"]
    assert setup["num_samples"] == 300
    assert setup["grid_length"] == 1
    for ev in seen:
        if ev.name == "sweep_complete":
            assert ev.payload["grid_index"] == 0
            assert ev.payload["dispatches"] >= 1
            assert ev.payload["sweep_seconds"] > 0
    finish = seen[-1].payload
    assert finish["n_grid_points"] == 1
    assert finish["wall_time_s"] > 0


def test_fit_failure_emits_training_failure():
    seen = []
    emitter = EventEmitter()
    emitter.register(lambda e: seen.append(e))
    est, data = _small_fit(events=emitter)
    est.last_fit_stats = {"wall_s": 1.0}  # stand-in for a previous fit
    est.ignore_threshold_for_new_models = True  # invalid without a model
    with pytest.raises(ValueError):
        est.fit(data)
    names = [e.name for e in seen]
    assert names == ["setup", "training_failure"]
    assert "ValueError" in seen[-1].payload["error"]
    # a failed fit must not leave the previous fit's bill behind
    assert est.last_fit_stats is None


def test_driver_run_profile_disables_on_failure():
    """A driver run that raises must still shut the global pipeline off
    (the session is a context manager precisely so the failure path
    can't leave process-wide profiling enabled)."""
    from photon_tpu.cli import game_base

    with pytest.raises(RuntimeError):
        with game_base.run_profile():
            assert obs.enabled()
            with obs.span("doomed"):
                pass
            raise RuntimeError("driver blew up")
    assert not obs.enabled()
    assert obs.get_tracer().spans() == []


def test_driver_run_profile_opt_out_leaves_caller_pipeline_alone(
    monkeypatch,
):
    """PHOTON_OBS=0 means the driver neither enables NOR tears down: an
    embedding process's own library-level telemetry (and its recorded
    spans) must survive a driver call."""
    from photon_tpu.cli import game_base

    monkeypatch.setenv("PHOTON_OBS", "0")
    obs.enable()
    with obs.span("caller_work"):
        pass
    with game_base.run_profile():
        pass
    assert obs.enabled()
    assert [r.name for r in obs.get_tracer().spans()] == ["caller_work"]


# ---------------------------------------------------------------------------
# metric-shape regression gate
# ---------------------------------------------------------------------------


def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "check_obs_regression",
        os.path.join(REPO_ROOT, "scripts", "check_obs_regression.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obs_regression_gate_passes_baseline_and_catches_drift(tmp_path):
    """Acceptance: the gate exits 0 on the committed baseline and
    non-zero on an injected regression."""
    gate = _load_gate()
    snapshot = gate.collect_snapshot()
    clean = tmp_path / "clean.json"
    clean.write_text(json.dumps(snapshot))
    assert gate.main(["--snapshot", str(clean)]) == 0

    # injected regression #1: a dispatch-count drift (the fused-sweep
    # contract) must fail the exact band
    drifted = dict(snapshot, metrics=dict(snapshot["metrics"]))
    drifted["metrics"]["descent.dispatches"] += 5
    bad = tmp_path / "drift.json"
    bad.write_text(json.dumps(drifted))
    assert gate.main(["--snapshot", str(bad)]) == 2

    # injected regression #2: a span vanishing from the taxonomy
    gone = dict(snapshot, metrics=dict(snapshot["metrics"]))
    del gone["metrics"]["span:descent.sweep"]
    bad2 = tmp_path / "gone.json"
    bad2.write_text(json.dumps(gone))
    assert gate.main(["--snapshot", str(bad2)]) == 2

    # injected regression #3: tracker-row field drift (the backward-
    # compatibility surface existing tests consume)
    fields = dict(snapshot, tracker_fields=dict(snapshot["tracker_fields"]))
    fields["tracker_fields"]["sweep_row"] = ["iteration", "renamed_field"]
    bad3 = tmp_path / "fields.json"
    bad3.write_text(json.dumps(fields))
    assert gate.main(["--snapshot", str(bad3)]) == 2


def test_obs_regression_compare_bands():
    """Band semantics, without running a fit: exact / relative /
    presence-only / new-metric."""
    gate = _load_gate()
    baseline = {
        "metrics": {
            "descent.sweeps": {"value": 3, "abs_tol": 0},
            "compile.backend_compiles": {
                "value": 10,
                "rel_tol": 0.5,
                "min_slack": 2,
            },
            "fit.wall_s": {"value": 1.23, "presence_only": True},
        },
        "tracker_fields": {"sweep_row": ["a", "b"]},
    }

    def snap(**over):
        metrics = {
            "descent.sweeps": 3,
            "compile.backend_compiles": 12,
            "fit.wall_s": 99.0,
        }
        metrics.update(over)
        return {
            "metrics": metrics,
            "tracker_fields": {"sweep_row": ["a", "b"]},
        }

    assert gate.compare(snap(), baseline) == []
    assert gate.compare(snap(**{"descent.sweeps": 4}), baseline)
    # inside the compiler-coupled band: 10 ± max(5, 2)
    assert gate.compare(
        snap(**{"compile.backend_compiles": 14}), baseline
    ) == []
    assert gate.compare(snap(**{"compile.backend_compiles": 16}), baseline)
    assert any(
        "new metric" in v
        for v in gate.compare(snap(**{"surprise.metric": 1}), baseline)
    )
    missing = snap()
    del missing["metrics"]["fit.wall_s"]
    assert any("missing" in v for v in gate.compare(missing, baseline))
