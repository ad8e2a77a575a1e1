"""The descent loop's host phases as spans a profiler capture records.

- the capture rule (obs/tracer.py): with telemetry OFF, spans record while
  a ``jax.profiler`` capture is live and at no other time; a stand-in
  annotation class without ``is_enabled`` reads as "no capture";
- the span tree of one ``run_coordinate_descent`` call under a capture:
  ``descent.run`` ⊃ ``descent.start``, per sweep ``descent.sweep`` ⊃
  ``descent.coordinate`` (one a trained coordinate) and ``descent.barrier``
  ⊃ exactly ``.enqueue`` and ``.wait``, then ``descent.record``;
  ``.enqueue``'s ``values`` is the health rows' device scalars plus the
  barrier's leaves, and its ``pieces`` the one program that reads them;
- ``util/force.queue_scalars`` and its ``wait()`` (what was
  ``fetch_scalars``, split where the host stops queueing) return what a
  direct read of every scalar gives, bit for bit, through one launch of
  one jitted program traced once a fit structure, or piece by piece
  where that launch raises.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import obs
from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.coordinate import FixedEffectCoordinate, RandomEffectCoordinate
from photon_tpu.game.data import CSRMatrix, GameData, build_random_effect_dataset
from photon_tpu.game.descent import run_coordinate_descent
from photon_tpu.obs import Tracer
from photon_tpu.optimize.common import OptimizerConfig
from photon_tpu.optimize.problem import (
    GLMProblemConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_tpu.types import TaskType
from photon_tpu.util import dispatch_count
from photon_tpu.util import force as force_mod
from photon_tpu.util.force import queue_scalars

SWEEPS = 2


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    obs.disable()
    yield
    obs.reset()
    obs.disable()


def _coordinates(seed=3, n=240, users=24):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, users, size=n)
    x = rng.normal(size=(n, 6))
    xr = rng.normal(size=(n, 3))
    y = x @ rng.normal(size=6) * 0.3 + rng.normal(size=n) * 0.1
    data = GameData.build(
        labels=y,
        feature_shards={"g": CSRMatrix.from_dense(x), "u": CSRMatrix.from_dense(xr)},
        id_tags={"userId": [f"u{i}" for i in ids]},
    )
    opt = GLMProblemConfig(
        task=TaskType.LINEAR_REGRESSION,
        regularization=RegularizationContext(RegularizationType.L2),
        optimizer_config=OptimizerConfig(max_iterations=4),
    )
    re_cfg = RandomEffectCoordinateConfig(
        random_effect_type="userId",
        feature_shard="u",
        optimization=opt,
        regularization_weights=(1.0,),
    )
    fe_cfg = FixedEffectCoordinateConfig(
        feature_shard="g", optimization=opt, regularization_weights=(1.0,)
    )
    ds = build_random_effect_dataset(data, re_cfg, seed=seed)
    return {
        "fixed": FixedEffectCoordinate.build(data, fe_cfg),
        "user": RandomEffectCoordinate.build(data, ds, re_cfg),
    }


# -- the capture rule --------------------------------------------------------


def test_spans_record_inside_a_capture_and_nowhere_else(tmp_path):
    tr = Tracer(enabled=False)
    with tr.span("before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("outer", k=1):
            with tr.span("inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    with tr.span("after"):
        pass
    recs = {r.name: r for r in tr.spans()}
    assert set(recs) == {"outer", "inner"}
    assert recs["inner"].parent_id == recs["outer"].span_id
    assert recs["outer"].args == {"k": 1}
    assert recs["outer"].dur_ns >= recs["inner"].dur_ns > 0


def test_a_capture_leaves_counters_and_instants_to_the_switch(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.counter("descent.sweeps")
        obs.instant("lifecycle")
        with obs.span("phase"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [r.name for r in obs.get_tracer().spans()] == ["phase"]
    assert obs.get_registry().snapshot().get("descent.sweeps") is None


@pytest.mark.parametrize("has_is_enabled", [False, True])
def test_a_stand_in_annotation_reads_as_its_is_enabled(monkeypatch, has_is_enabled):
    """A stand-in class without ``is_enabled`` is "no capture"; one with it
    decides."""

    class Annotation:
        def __init__(self, name, **meta):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    if has_is_enabled:
        Annotation.is_enabled = staticmethod(lambda: True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = Tracer(enabled=False)
    with tr.span("quiet"):
        pass
    assert [r.name for r in tr.spans()] == (["quiet"] if has_is_enabled else [])


# -- the descent loop's span tree under a capture ----------------------------


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """One two-sweep fit with telemetry off, inside a capture: (its
    result, its spans by name, every span by ID, the dispatches counted
    over the call)."""
    coords = _coordinates()
    # warm: compiles fall outside the captured call
    run_coordinate_descent(coords, ["fixed", "user"], 1)
    obs.reset()
    obs.disable()
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("capture")))
    try:
        d0 = dispatch_count.snapshot()
        result = run_coordinate_descent(coords, ["fixed", "user"], SWEEPS)
        dispatches = dispatch_count.snapshot() - d0
    finally:
        jax.profiler.stop_trace()
    recs = obs.get_tracer().spans()
    obs.reset()
    by_name: dict = {}
    for r in recs:
        by_name.setdefault(r.name, []).append(r)
    return result, by_name, {r.span_id: r for r in recs}, dispatches


def _children(by_id, rec):
    return sorted(
        (r for r in by_id.values() if r.parent_id == rec.span_id),
        key=lambda r: r.t0_ns,
    )


def test_one_run_holds_start_sweeps_and_records(captured):
    _, by_name, by_id, dispatches = captured
    (run,) = by_name["descent.run"]
    assert run.parent_id is None
    assert run.args == {"coordinates": 2, "sweeps": SWEEPS}
    names = [r.name for r in _children(by_id, run)]
    assert names == ["descent.start"] + ["descent.sweep", "descent.record"] * SWEEPS
    (start,) = by_name["descent.start"]
    sweep_dispatches = sum(r.args["dispatches"] for r in by_name["descent.sweep"])
    assert start.args["dispatches"] >= 2  # a score program a coordinate
    assert start.args["dispatches"] + sweep_dispatches == dispatches


def test_a_sweep_is_a_launch_a_coordinate_then_one_barrier(captured):
    _, by_name, by_id, _ = captured
    assert len(by_name["descent.sweep"]) == SWEEPS
    for sweep in by_name["descent.sweep"]:
        kids = _children(by_id, sweep)
        assert [k.name for k in kids] == [
            "descent.coordinate", "descent.coordinate", "descent.barrier",
        ]
        assert [k.args["coordinate"] for k in kids[:2]] == ["fixed", "user"]
        assert "barrier_seconds" not in sweep.args
        assert [k.name for k in _children(by_id, kids[2])] == [
            "descent.barrier.enqueue", "descent.barrier.wait",
        ]


def test_enqueue_pieces_are_the_health_scalars_and_the_barrier(captured):
    result, by_name, _, _ = captured
    rows = [r for r in result.tracker if "health" in r]
    enqueues = by_name["descent.barrier.enqueue"]
    assert len(rows) == len(enqueues) == SWEEPS
    for row, enqueue in zip(rows, enqueues):
        scalars = sum(
            3 + sum(len(h[k]) for k in ("iterations", "evaluations", "feature_passes"))
            for h in row["health"].values()
        )
        assert enqueue.args["values"] == scalars + 1  # the total's one leaf
        assert enqueue.args["pieces"] == 1  # one program reads them all


def test_record_follows_its_barrier_and_ends_before_the_next_launch(captured):
    _, by_name, _, _ = captured
    waits = by_name["descent.barrier.wait"]
    records = by_name["descent.record"]
    coords = by_name["descent.coordinate"]
    for i, (wait, record) in enumerate(zip(waits, records)):
        assert record.t0_ns >= wait.t0_ns + wait.dur_ns
        assert record.args == {"iteration": i}
        later = [c for c in coords if c.t0_ns > wait.t0_ns]
        if later:
            assert record.t0_ns + record.dur_ns <= later[0].t0_ns


# -- the read-back, queued then waited for -----------------------------------


def _scalars():
    return [
        jnp.asarray(1.5, jnp.float32),
        jnp.asarray(2.0 ** -20, jnp.float64),
        jnp.asarray(True),
        jnp.asarray(7, jnp.int32),
        0.25,  # a host number passes through
        np.float64(-3.0),
        jnp.asarray([9.0, 8.0], jnp.float32)[1],
    ]


def _direct(scalars):
    return np.asarray(
        [np.float32(np.asarray(s).reshape(-1)[0]) for s in scalars], np.float32
    )


@pytest.mark.parametrize("barrier", [None, "array", "tree"])
def test_queue_then_wait_reads_every_scalar_bit_for_bit(barrier):
    total = {
        None: None,
        "array": jnp.arange(4.0),
        "tree": {"a": jnp.ones(3), "b": jnp.zeros((2, 2), jnp.int32), "c": 1.0},
    }[barrier]
    scalars = _scalars()
    pending = queue_scalars(scalars, barrier=total)
    leaves = {None: 0, "array": 1, "tree": 2}[barrier]
    assert pending.values == 5 + leaves  # the two host numbers are no value
    assert pending.pieces == 1  # one launch reads every device value
    got = pending.wait()
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, _direct(scalars))


@pytest.mark.parametrize("why", ["refused", "two_devices"])
def test_a_failed_concatenation_reads_piece_by_piece(monkeypatch, why):
    """A launch of the one program that raises (values on different
    devices or platforms) leaves each value to its own read: same values,
    a piece each, no dispatch counted."""
    scalars = _scalars()
    barrier = jnp.arange(3.0)
    want = queue_scalars(scalars, barrier=barrier).wait()
    if why == "refused":
        def refuse(*a, **kw):
            raise ValueError("mixed devices")

        monkeypatch.setattr(force_mod, "_first_elements", refuse)
    else:
        if len(jax.devices()) < 2:
            pytest.skip("needs more than one device")
        barrier = jax.device_put(barrier, jax.devices()[1])
        scalars = [jax.device_put(s, jax.devices()[0])
                   if isinstance(s, jax.Array) else s for s in scalars]
    d0 = dispatch_count.snapshot()
    pending = queue_scalars(scalars, barrier=barrier)
    assert dispatch_count.snapshot() == d0
    assert pending.pieces == pending.values == 6
    np.testing.assert_array_equal(pending.wait(), want)
    np.testing.assert_array_equal(want, _direct(scalars))


def test_nothing_to_read_is_an_empty_array():
    d0 = dispatch_count.snapshot()
    assert queue_scalars([]).pieces == 0
    assert queue_scalars([3]).pieces == queue_scalars([3]).values == 0
    assert dispatch_count.snapshot() == d0  # nothing on the device: no launch
    assert queue_scalars([], barrier=jnp.ones(2)).wait().tolist() == []
    assert queue_scalars([3]).wait().tolist() == [3.0]


def test_one_device_value_is_one_launch_counted_as_a_dispatch():
    d0 = dispatch_count.snapshot()
    pending = queue_scalars([jnp.asarray(4.0), 2.0])
    assert dispatch_count.snapshot() - d0 == 1
    assert (pending.pieces, pending.values) == (1, 1)
    assert pending.wait().tolist() == [4.0, 2.0]


def test_multi_device_values_are_read_alone_beside_the_one_program():
    """A sharded barrier leaf (a mesh fit's total) is read on its own,
    never gathered into the one program: a piece of its own."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_tpu.parallel.mesh import make_mesh

    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs more than one device")
    mesh = make_mesh(num_data=n)
    total = jax.device_put(
        np.arange(2.0 * n, dtype=np.float32), NamedSharding(mesh, P("data"))
    )
    scalars = _scalars()
    pending = queue_scalars(scalars, barrier=total)
    assert (pending.pieces, pending.values) == (2, 6)
    np.testing.assert_array_equal(pending.wait(), _direct(scalars))
    assert queue_scalars([], barrier=total).pieces == 1


def test_the_read_back_traces_once_a_fit_structure():
    """A 3-sweep fit traces the one program once; a second fit of the
    same structure traces it not at all."""
    coords = _coordinates(seed=5)
    force_mod._first_elements.clear_cache()
    before = force_mod.TRACE_COUNTERS["first_elements"]
    first = run_coordinate_descent(coords, ["fixed", "user"], 3)
    assert force_mod.TRACE_COUNTERS["first_elements"] - before == 1
    second = run_coordinate_descent(_coordinates(seed=5), ["fixed", "user"], 3)
    assert force_mod.TRACE_COUNTERS["first_elements"] - before == 1
    rows = [[r["health"] for r in res.tracker if "health" in r]
            for res in (first, second)]
    assert rows[0] == rows[1] and len(rows[0]) == 3


# -- scripts/scope_join.py --gaps --------------------------------------------


def _scope_join():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "scripts", "scope_join.py")
    spec = importlib.util.spec_from_file_location("scope_join", path)
    join = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(join)
    return join


def test_gaps_go_to_the_innermost_photon_span_over_their_midpoint():
    join = _scope_join()
    host = [("bench.step", 0.0, 10.0),
            ("photon.descent.run", 1.0, 9.0),
            ("photon.descent.barrier", 4.0, 6.0),
            ("photon.descent.barrier.wait", 4.5, 6.0),
            ("photon.descent.record", 6.0, 7.0),
            ("bench.step", 20.0, 22.0)]
    device = [("jit_fe_sweep", 2.0, 5.0), ("jit_re_sweep", 5.5, 6.5),
              ("jit_fe_sweep", 20.5, 21.0)]
    by_name, per_step = join.charge_gaps(host, device)
    # step 1: [0, 2) mid 1.0 under run; [5, 5.5) under wait; [6.5, 10) mid
    # 8.25 under run; step 2: [20, 20.5) and [21, 22) under no span
    assert by_name == pytest.approx({"photon.descent.run": 2.0 + 3.5,
                                     "photon.descent.barrier.wait": 0.5,
                                     "bench.step": 1.5})
    assert per_step == pytest.approx([(10.0, 6.0), (2.0, 1.5)])


def test_gaps_read_the_photon_host_events_of_a_capture(tmp_path):
    join = _scope_join()
    tr = Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.step"):
            with tr.span("descent.run"):
                with tr.span("descent.barrier.wait"):
                    jnp.ones(4).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.other"):
            pass
    finally:
        jax.profiler.stop_trace()
    from benchmarks.lib import trace

    host = join.photon_host(trace.find_xplane(str(tmp_path)))
    assert [name for name, _, _ in host] == [
        "bench.step", "photon.descent.run", "photon.descent.barrier.wait",
    ]
    (_, s0, e0), (_, s1, e1), (_, s2, e2) = host
    assert s0 <= s1 <= s2 <= e2 <= e1 <= e0


def test_the_gaps_add_up_to_the_harness_host_gap():
    """The join's idle a step is ``host_gap_ms``'s wall less busy union,
    read from the same events by ``benchmarks/lib/trace.reduce``."""
    from benchmarks.lib import trace

    join = _scope_join()
    host = [("bench.step", 0.0, 10.0), ("photon.descent.run", 1.0, 9.0),
            ("bench.step", 12.0, 15.0)]
    ops = [("fusion.1", 0.5, 3.0), ("fusion.2", 2.0, 4.0), ("copy.3", 6.0, 7.5),
           ("fusion.1", 13.0, 14.5)]
    _, per_step = join.charge_gaps(host, ops)
    reduced = trace.reduce({"devices": {0: {"modules": [], "ops": ops}},
                            "host": [ev for ev in host if ev[0] == "bench.step"]})
    assert [w - i for w, i in per_step] == pytest.approx(
        [s["busy_s"] for s in reduced["steps"]])
    assert [w for w, _ in per_step] == pytest.approx(
        [s["wall_s"] for s in reduced["steps"]])
