"""Causal request tracing (ISSUE 19): trace minting + null discipline,
head-sampling and worst-K exemplar retention, the Chrome-trace export's
flow hygiene and schema contract, fan-in de-duplication through the
serving engine, fault-instant attachment, the fixed serve stage enum,
obs.span's device-annotation bridge, concurrent /slo + /trace scrapes
under live traffic, and the bench trace-overhead band semantics."""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from photon_tpu import obs
from photon_tpu.game.data import slice_game_data
from photon_tpu.obs import causal, slo
from photon_tpu.serve.admission import AdmissionQueue
from photon_tpu.serve.registry import ModelRegistry
from photon_tpu.util import faults

SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in (
        "PHOTON_TRACE",
        "PHOTON_TRACE_SAMPLE_N",
        "PHOTON_TRACE_RING",
        "PHOTON_TRACE_WORST_K",
        "PHOTON_TRACE_WINDOW_S",
        "PHOTON_SLO_SPEC",
    ):
        monkeypatch.delenv(var, raising=False)
    causal.clear()
    slo.clear()
    faults.clear()
    obs.reset()
    yield
    causal.clear()
    slo.clear()
    faults.clear()
    obs.reset()
    obs.disable()


def _workload(seed: int = 0, num_requests: int = 4, batch_rows: int = 32):
    import load_harness

    return load_harness.build_workload(
        num_requests=num_requests,
        batch_rows=batch_rows,
        d=8,
        nnz=4,
        users=8,
        items=4,
        seed=seed,
    )


# -- disarmed discipline ----------------------------------------------------


def test_disarmed_mint_returns_shared_null():
    assert causal.active() is None
    ctx = causal.mint("anything")
    assert ctx is causal.null()
    # every recorder chains as a no-op; active() costs no new object
    assert ctx.event("e", 0.0, 1.0) is ctx
    assert ctx.instant("i") is ctx
    assert ctx.flow("s", 0.0) is ctx
    assert ctx.attach(None) is ctx
    assert ctx.finish("ok") is None
    assert ctx.active() is causal.null().active()
    with ctx.active():
        assert causal.current_trace_id() is None
    assert causal.group("g", [ctx]) is causal.null()
    causal.mark("swap")  # no buffer: silently dropped
    causal.mark_fault("p", "stall")
    doc = causal.chrome_trace()
    assert doc["otherData"]["causal_tracing"] == {"armed": False}
    assert causal.validate_chrome_trace(doc) == []


def test_disarmed_scoring_parity_with_armed():
    """Arming the trace plane may not change a single score."""
    scorer, chunks = _workload(seed=3, num_requests=2, batch_rows=32)
    base = scorer.stream(iter(chunks), collect_scores=True).scores
    causal.install(sample_n=1)
    traced = scorer.stream(iter(chunks), collect_scores=True).scores
    np.testing.assert_array_equal(base, traced)
    traces, _, _, stats = causal.active().export_state()
    assert stats["finished"] >= len(chunks)
    assert traces, "armed run retained no traces"


# -- arming + env knobs -----------------------------------------------------


def test_ensure_from_env_arms_and_is_loud(monkeypatch):
    assert causal.ensure_from_env() is None
    monkeypatch.setenv("PHOTON_TRACE", "1")
    monkeypatch.setenv("PHOTON_TRACE_SAMPLE_N", "5")
    monkeypatch.setenv("PHOTON_TRACE_WORST_K", "3")
    buf = causal.ensure_from_env()
    assert buf is causal.active()
    assert buf.sample_n == 5 and buf.worst_k == 3
    # programmatic install wins over repeated env arming
    assert causal.ensure_from_env() is buf

    causal.clear()
    monkeypatch.setenv("PHOTON_TRACE", "yes")
    with pytest.raises(ValueError):
        causal.ensure_from_env()
    monkeypatch.setenv("PHOTON_TRACE", "1")
    monkeypatch.setenv("PHOTON_TRACE_SAMPLE_N", "0")
    with pytest.raises(ValueError):
        causal.ensure_from_env()


# -- retention policy -------------------------------------------------------


def test_head_sampling_one_in_n():
    buf = causal.install(sample_n=3, ring=64)
    for _ in range(9):
        buf.mint("req").finish("ok", e2e_s=0.01)
    traces, _, _, stats = buf.export_state()
    assert stats["retained_sampled"] == 3
    assert stats["dropped"] == 6
    # head sampling: the 1st, 4th, 7th minted trace
    assert [t.trace_id for t in traces] == [1, 4, 7]


def test_sampled_ring_is_bounded_oldest_out():
    buf = causal.install(sample_n=1, ring=4)
    for _ in range(6):
        buf.mint("req").finish("ok", e2e_s=0.01)
    traces, _, _, stats = buf.export_state()
    assert stats["retained_sampled"] == 4
    assert [t.trace_id for t in traces] == [3, 4, 5, 6]


def test_exemplar_worst_k_eviction_keeps_the_worst():
    # sample_n high so nothing rides the ring; long window = one bucket
    buf = causal.install(sample_n=1000, worst_k=2, window_s=1000.0)
    for e2e in (1.0, 9.0, 5.0):
        buf.mint("req").finish("deadline", e2e_s=e2e)
    traces, _, _, stats = buf.export_state()
    assert stats["retained_exemplars"] == 2
    assert stats["evicted_exemplars"] == 1
    assert sorted(t.e2e_s for t in traces) == [5.0, 9.0]
    # sheds and errors are exemplars too, regardless of sampling
    buf.mint("req").finish("shed:queue_full", e2e_s=99.0)
    _, _, _, stats = buf.export_state()
    assert stats["retained_exemplars"] == 2  # 99.0 evicted the 5.0
    assert any(
        t.outcome == "shed:queue_full" for t in buf.traces()
    )


def test_slo_fast_burn_nominates_ok_traces():
    """A trace that met its own deadline still becomes an exemplar when
    it finishes inside a hot burn window — tail context, not a victim."""
    buf = causal.install(sample_n=1000)  # ring would not keep it
    slo.install("p99<=0.001s@60s")
    tracker = slo.active()
    # saturate the fast window with violations so the budget is burning
    for _ in range(20):
        tracker.observe(1.0, {"dispatch": 1.0})
    assert tracker.fast_burning()
    buf.mint("req").finish("ok", e2e_s=0.5)
    _, _, _, stats = buf.export_state()
    assert stats["retained_exemplars"] == 1


# -- fault + lifecycle instants ---------------------------------------------


def test_mark_fault_attaches_to_active_trace_else_global():
    buf = causal.install(sample_n=1)
    ctx = buf.mint("victim")
    with ctx.active():
        causal.mark_fault("serve.dispatch", "stall")
    assert any(e["name"] == "fault.injected" for e in ctx.events)
    causal.mark_fault("scoring.chunk", "unavailable")  # no active trace
    _, instants, _, _ = buf.export_state()
    assert [e["name"] for e in instants] == ["fault.injected"]
    causal.mark("serve.swap", tenant="default")
    _, instants, _, _ = buf.export_state()
    assert [e["name"] for e in instants] == ["fault.injected", "serve.swap"]


def test_trace_event_cap_counts_overflow():
    buf = causal.install(sample_n=1)
    ctx = buf.mint("noisy")
    for i in range(causal.MAX_EVENTS_PER_TRACE + 10):
        ctx.instant(f"i{i}")
    assert len(ctx.events) == causal.MAX_EVENTS_PER_TRACE
    _, _, _, stats = buf.export_state()
    assert stats["dropped_events"] == 10


# -- export + schema contract -----------------------------------------------


def test_chrome_trace_drops_dangling_flows_and_validates():
    obs.enable()
    buf = causal.install(sample_n=1)
    t0 = time.perf_counter()
    # a full chain: s inside one slice, t and f inside another
    full = buf.mint("full")
    full.event("stage_a", t0, 0.010).flow("s", t0)
    full.event("stage_b", t0 + 0.020, 0.010)
    full.flow("t", t0 + 0.020).flow("f", t0 + 0.020)
    full.finish("ok", e2e_s=0.030)
    # shed at the door: only an "s" flow — must be dropped at export
    shed = buf.mint("shed")
    shed.event("admit", t0, 0.001).flow("s", t0)
    shed.finish("shed:queue_full", e2e_s=0.001)

    doc = causal.chrome_trace()
    assert causal.validate_chrome_trace(doc) == []
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "t", "f")]
    assert {e["id"] for e in flows} == {full.trace_id}
    # the dangling trace's slices survive, only its flows are dropped
    names = [e["name"] for e in doc["traceEvents"]]
    assert "admit" in names
    summaries = doc["otherData"]["causal_tracing"]["traces"]
    assert {s["outcome"] for s in summaries} == {"ok", "shed:queue_full"}


def test_validator_catches_schema_violations():
    base = {"pid": 1, "tid": 1}
    assert causal.validate_chrome_trace({}) == [
        "traceEvents missing or not a list"
    ]
    errs = causal.validate_chrome_trace(
        {"traceEvents": [dict(base, name="x", ph="Z", ts=0.0)]}
    )
    assert any("unknown phase" in e for e in errs)
    errs = causal.validate_chrome_trace(
        {"traceEvents": [dict(base, name="x", ph="X", ts=0.0, dur=-1)]}
    )
    assert any("dur >= 0" in e for e in errs)
    # a dangling flow id, and a flow binding to no slice on its track
    errs = causal.validate_chrome_trace(
        {"traceEvents": [dict(base, name="x", ph="s", ts=5.0, id=7)]}
    )
    assert any("no finish" in e for e in errs)
    assert any("binds to no slice" in e for e in errs)
    ok = causal.validate_chrome_trace(
        {
            "traceEvents": [
                dict(base, name="a", ph="X", ts=0.0, dur=10.0),
                dict(base, name="x", ph="s", ts=5.0, id=7),
                dict(base, name="a", ph="X", ts=20.0, dur=10.0),
                dict(base, name="x", ph="f", ts=20.0, id=7, bp="e"),
            ]
        }
    )
    assert ok == []


# -- serving engine: fan-in, flows, stage enum ------------------------------


def _start_engine(reg, *, cap=64, batch_rows=32, poll_s=0.02):
    from photon_tpu.serve.engine import ServingEngine

    q = AdmissionQueue(cap=cap, default_deadline_s=30.0, max_rows=batch_rows)
    engine = ServingEngine(reg, q, batch_rows=batch_rows, poll_s=poll_s)
    engine.start()
    return engine, q


def test_engine_fan_in_dedups_batch_slices_and_flows_resolve():
    obs.enable()
    causal.install(sample_n=1)
    scorer, chunks = _workload(seed=0, num_requests=4, batch_rows=32)
    requests = [slice_game_data(c, 0, 10) for c in chunks[:3]]
    reg = ModelRegistry()
    reg.register(
        "default", scorer.model, batch_rows=32, ell_widths={"global": 4}
    )
    engine, q = _start_engine(reg, batch_rows=32)
    try:
        futs = [q.submit(r) for r in requests]
        for fut in futs:
            fut.result(timeout=10)
    finally:
        engine.stop()

    doc = causal.chrome_trace()
    assert causal.validate_chrome_trace(doc) == []
    summaries = doc["otherData"]["causal_tracing"]["traces"]
    assert len(summaries) == 3
    assert all(s["outcome"] == "ok" for s in summaries)
    evs = doc["traceEvents"]
    # 3 requests fanned into ONE micro-batch: the shared batch slices
    # appear exactly once (exporter dedups the shared group by identity)
    assert sum(e["name"] == "serve.assemble" for e in evs) == 1
    assert sum(e["name"] == "serve.h2d" for e in evs) == 1
    assert sum(e["name"] == "serve.readback" for e in evs) == 1
    # per-request chain: every trace id has a resolving s→t→f flow
    flow_ids = {e["id"] for e in evs if e["ph"] in ("s", "t", "f")}
    assert flow_ids == {s["trace_id"] for s in summaries}
    # the admit slice is per-request: one per member
    assert sum(e["name"] == "serve.admit" for e in evs) == 3


def test_serve_stage_histogram_keys_are_bounded():
    from photon_tpu.serve.engine import SERVE_STAGES

    obs.enable()
    scorer, chunks = _workload(seed=0, num_requests=2, batch_rows=32)
    reg = ModelRegistry()
    reg.register(
        "default", scorer.model, batch_rows=32, ell_widths={"global": 4}
    )
    engine, q = _start_engine(reg, batch_rows=32)
    try:
        for c in chunks:
            q.submit(slice_game_data(c, 0, 8)).result(timeout=10)
    finally:
        engine.stop()
    hists = obs.get_registry().snapshot()["histograms"]
    stage_keys = [
        k for k in hists if k.startswith("serve.stage_seconds.")
    ]
    assert stage_keys, "engine emitted no stage histograms"
    for k in stage_keys:
        assert k.rsplit(".", 1)[1] in SERVE_STAGES, k


def test_shed_and_faulted_requests_are_exemplars():
    obs.enable()
    causal.install(sample_n=1000)  # retention must come from exemplars
    scorer, chunks = _workload(seed=0, num_requests=2, batch_rows=32)
    q = AdmissionQueue(cap=1, default_deadline_s=30.0, max_rows=8)
    fut = q.submit(slice_game_data(chunks[0], 0, 8))
    with pytest.raises(Exception):
        q.submit(slice_game_data(chunks[0], 0, 32))  # oversize: shed
    _, _, _, stats = causal.active().export_state()
    assert stats["retained_exemplars"] == 1
    (shed,) = causal.active().traces()
    assert shed.outcome.startswith("shed:")
    assert any(e["name"] == "serve.shed" for e in shed.events)
    del fut


# -- streaming scorer: end-to-end chain -------------------------------------


def test_scoring_stream_chain_validates_with_faults():
    obs.enable()
    causal.install(sample_n=1)
    faults.install("scoring.chunk@2=stall:0.01")
    scorer, chunks = _workload(seed=1, num_requests=4, batch_rows=32)
    scorer.stream(iter(chunks), collect_scores=False)
    doc = causal.chrome_trace()
    assert causal.validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"score.decode", "score.assemble", "score.h2d",
            "score.dispatch", "score.readback"} <= names
    # the injected stall landed INSIDE a victim's chain, not globally
    assert any(e["name"] == "fault.injected" for e in evs)
    victims = [
        t for t in causal.active().traces()
        if any(e["name"] == "fault.injected" for e in t.events)
    ]
    assert victims, "no retained trace carries the injected fault"
    flow_ids = {e["id"] for e in evs if e["ph"] in ("s", "t", "f")}
    assert len(flow_ids) >= len(chunks) - 1


# -- tracer bridge ----------------------------------------------------------


def test_obs_span_annotation_carries_span_and_trace_id(monkeypatch):
    """What ``trace_phase`` was for, now ``obs.span``'s own: inside a causal
    trace the span's profiler annotation is stamped with its span ID and
    the trace ID, so a device-profiler slice joins back to both."""
    import jax.profiler

    entered = []

    class Annotation:
        def __init__(self, name, **meta):
            self.row = (name, meta)

        def __enter__(self):
            entered.append(self.row)

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    obs.enable()
    causal.install(sample_n=1)
    ctx = causal.mint("req")
    with ctx.active():
        assert causal.current_trace_id() == ctx.trace_id
        with obs.span("unit_phase", cat="device"):
            pass
    (rec,) = [
        r for r in obs.get_tracer().spans() if r.name == "unit_phase"
    ]
    assert rec.cat == "device"
    assert entered == [
        ("photon.unit_phase",
         {"span_id": rec.span_id, "trace_id": ctx.trace_id})
    ]
    assert causal.current_trace_id() is None


# -- concurrent scrapes under live traffic ----------------------------------


def test_concurrent_slo_and_trace_scrapes_during_traffic():
    from photon_tpu.obs.http import TelemetryServer

    obs.enable()
    causal.install(sample_n=1)
    slo.install("p99<=30s@60s")
    scorer, chunks = _workload(seed=2, num_requests=8, batch_rows=32)
    server = TelemetryServer(0)
    port = server.start()
    failures: list[str] = []
    stop = threading.Event()

    def scrape(path: str):
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5
                ) as resp:
                    if resp.status != 200:
                        failures.append(f"{path}: HTTP {resp.status}")
                    json.loads(resp.read().decode())
            except Exception as exc:  # torn read / invalid JSON
                failures.append(f"{path}: {exc!r}")
            time.sleep(0.005)

    threads = [
        threading.Thread(target=scrape, args=("/slo",), daemon=True),
        threading.Thread(target=scrape, args=("/trace",), daemon=True),
    ]
    try:
        for t in threads:
            t.start()
        scorer.stream(iter(chunks), collect_scores=False)
        # one more scrape cycle against the settled state
        time.sleep(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        server.stop()
    assert failures == []
    doc = causal.chrome_trace()
    assert causal.validate_chrome_trace(doc) == []
    assert doc["otherData"]["causal_tracing"]["finished"] >= len(chunks)


# -- bench band semantics ---------------------------------------------------


def test_trace_overhead_band_semantics():
    import bench

    healthy = {
        "tail": {"p99_s": 0.2, "gate_ok": True, "slo_violations": []},
        "trace_overhead": {"p99_delta_frac": 0.15},
    }
    assert bench.check_quality_bands("game_scoring_tail", healthy) == []
    # legacy rows without the A/B keep passing (presence-gated)
    legacy = {"tail": {"p99_s": 0.2, "gate_ok": True, "slo_violations": []}}
    assert bench.check_quality_bands("game_scoring_tail", legacy) == []
    # a row that RAN the A/B and detonated is gated — as is a vacuous one
    hot = dict(healthy, trace_overhead={"p99_delta_frac": 1.7})
    v = bench.check_quality_bands("game_scoring_tail", hot)
    assert v and "trace plane" in v[0]
    vacuous = dict(healthy, trace_overhead={})
    assert bench.check_quality_bands("game_scoring_tail", vacuous)
