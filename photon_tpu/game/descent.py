"""Block coordinate descent over GAME coordinates.

Reference parity: photon-lib algorithm/CoordinateDescent.scala:39-280 —
per iteration, per coordinate: residual score = total − own score fed as
offsets, retrain, rescore, update total; validation evaluator tracks the
best model across iterations; locked coordinates are scored but never
retrained (partial retraining, :44-49).

TPU redesign: coordinate scores are dense device arrays aligned by sample
position, so the residual update is a vectorized subtract/add instead of
the reference's full-outer-join shuffles (CoordinateDataScores.scala:53-62).
The Python loop here is pure control flow — each coordinate's whole step
(residual → train → rescore → total update) is ONE compiled program
(``Coordinate.sweep_step``) with the total, the old score, and the old
state donated, and the steady-state loop runs sync-free: the honest
read-back barrier (util/force.py) is paid once per SWEEP, not once per
coordinate.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Mapping, Sequence

import jax

from photon_tpu import obs
from photon_tpu.game.coordinate import Coordinate
from photon_tpu.obs.health import DivergenceError, resolve_policy
from photon_tpu.util import compile_watch, dispatch_count, faults
from photon_tpu.util.force import fetch_scalars, force
from photon_tpu.util.sanitize import sanctioned_transfers, transfer_sanitizer
from photon_tpu.util.target import donation_enabled

logger = logging.getLogger(__name__)


def precompile_coordinates(
    coordinates: Mapping[str, Coordinate],
    *,
    donate=None,
    locked: frozenset = frozenset(),
    max_workers: int | None = None,
    include_score: bool = True,
) -> dict:
    """AOT-compile every hot-path program a fit will dispatch — all
    coordinates' fused ``sweep_step`` programs (PR 2's trace-once
    structure: one program per coordinate with every RE bucket shape as a
    sub-solve) plus the initial ``score`` programs — on a thread pool, so
    independent compiles OVERLAP instead of serializing inside the first
    sweep. XLA releases the GIL during backend compiles, so the pool wall
    approaches the slowest program instead of the sum.

    The compiled executables are stored on each coordinate
    (``Coordinate.aot_executables``) and dispatched by
    ``sweep_step``/``score`` — the AOT path is mandatory for the win
    because ``jit(...).lower().compile()`` does not feed the jit call
    cache on this jax. λ rides as a traced scalar, so one precompiled
    set serves the whole regularization grid.

    Locked coordinates get only their score program (they never train).
    Returns a report: total ``wall_s`` vs ``sum_program_walls_s`` (the
    overlap evidence), per-program compile walls, and persistent-cache
    hit counts — what the pass SKIPPED because a previous run already
    paid for it.
    """
    compile_watch.install()
    t0 = time.perf_counter()
    specs = []
    for cid, coord in coordinates.items():
        try:
            entries = coord.precompile_specs(
                donate=donate,
                include_sweep=cid not in locked,
                include_score=include_score,
            )
        except NotImplementedError:
            logger.warning("coordinate %s does not support precompile", cid)
            continue
        specs.extend(
            (coord, key, f"{cid}:{label}", lowered)
            for key, label, lowered in entries
        )
    lower_wall_s = time.perf_counter() - t0

    def compile_one(item):
        coord, key, label, lowered = item
        try:
            with compile_watch.thread_scope() as cw, obs.span(
                "precompile.program", cat="compile", program=label
            ):
                t1 = time.perf_counter()
                compiled = lowered.compile()
                wall = time.perf_counter() - t1
        except Exception as e:
            # one program's compile failure (transient runtime error, OOM)
            # must not abort the fit — that coordinate simply compiles
            # lazily on the jit path like an un-precompiled run
            logger.warning(
                "precompile of %s failed (%s: %s); the jit path will "
                "compile it lazily", label, type(e).__name__, e,
            )
            return {
                "program": label,
                "error": f"{type(e).__name__}: {e}",
                "wall_s": 0.0,
                "backend_compile_s": 0.0,
                "cache_hits": 0,
                "cache_misses": 0,
            }
        coord.aot_executables()[key] = compiled
        # static footprint into the memory ledger: XLA's own
        # argument/output/temp/generated-code accounting per executable
        # (recorded unconditionally — compile time, never the hot path)
        obs.memory.record_executable(label, compiled)
        return {
            "program": label,
            "wall_s": round(wall, 4),
            "backend_compile_s": cw["backend_compile_s"],
            "cache_hits": cw["cache_hits"],
            "cache_misses": cw["cache_misses"],
        }

    workers = max_workers or min(8, len(specs) or 1)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=max(1, workers)) as ex:
        programs = list(ex.map(compile_one, specs))
    wall_s = time.perf_counter() - t0
    report = {
        "n_programs": len(programs),
        "max_workers": workers,
        "lower_wall_s": round(lower_wall_s, 4),
        "wall_s": round(wall_s, 4),
        # Σ of per-program walls measured inside their threads: the
        # serial-equivalent cost. wall_s < this ⇒ compiles overlapped.
        "sum_program_walls_s": round(sum(p["wall_s"] for p in programs), 4),
        "cache_hits": sum(p["cache_hits"] for p in programs),
        "cache_misses": sum(p["cache_misses"] for p in programs),
        "programs": programs,
    }
    logger.info(
        "precompiled %d programs in %.2fs (serial-equivalent %.2fs, "
        "%d persistent-cache hits skipped cold compiles)",
        report["n_programs"], report["wall_s"],
        report["sum_program_walls_s"], report["cache_hits"],
    )
    return report


def compile_sec_per_program() -> float:
    """Assumed cold-compile seconds per program for bill projections:
    ``PHOTON_COMPILE_SEC_PER_PROGRAM`` override, else 2 s on every
    platform. A projection basis, not a measurement — every consumer
    records it alongside the projection — and a placeholder: it is to be
    replaced by the per-program figure ``chip_smoke.py`` measures on the
    chip (PERF.md, Open questions)."""
    env = os.environ.get("PHOTON_COMPILE_SEC_PER_PROGRAM", "").strip()
    if env:
        return float(env)  # phl-ok: PHL002 parses an env-var string, not device data
    return 2.0


def project_compile_bill(
    n_top_level_programs: int, n_solve_shapes: int
) -> dict:
    """THE cold-bill pricing formula, shared by every projector (the
    built-coordinates path below and bench's pre-build ShapePool path):
    one unit of XLA work per top-level program plus one per distinct RE
    solve shape, priced at ``compile_sec_per_program`` each."""
    sec = compile_sec_per_program()
    return {
        "n_top_level_programs": int(n_top_level_programs),
        "n_solve_shapes": int(n_solve_shapes),
        "sec_per_program_assumed": sec,
        "projected_cold_s": round(
            (n_top_level_programs + n_solve_shapes) * sec, 1
        ),
    }


def estimate_compile_bill(coordinates: Mapping[str, Coordinate]) -> dict:
    """Projected cold-cache compile bill for a fit over ``coordinates`` —
    computable BEFORE anything is enqueued, from the program enumeration
    alone (VERDICT r5 next #5: config 5's cold bill must be projected up
    front, not discovered inside a benchmark timeout).

    The basis is explicit and recorded (see ``project_compile_bill``, the
    single pricing site): 2 top-level programs per coordinate (fused
    sweep + initial score) plus one unit of XLA work per DISTINCT RE
    bucket solve shape (each distinct (rows, d) shape is one solve body
    the compiler must build inside the fused modules — the quantity the
    shape budget governs).
    """
    from photon_tpu.game.coordinate import RandomEffectCoordinate

    shapes = set()
    n_bucket_solves = 0
    for coord in coordinates.values():
        if isinstance(coord, RandomEffectCoordinate):
            for db in coord.device_buckets:
                shapes.add(
                    (int(db.features.shape[1]), int(db.features.shape[2]))
                )
                n_bucket_solves += 1
    bill = project_compile_bill(2 * len(coordinates), len(shapes))
    return {**bill, "n_bucket_solves": n_bucket_solves}


@dataclasses.dataclass
class CoordinateDescentResult:
    states: dict  # coordinate id → final state
    tracker: list  # per (iteration, coordinate) + per-sweep log rows
    best_states: dict | None = None  # best-by-validation snapshot
    best_metric: float | None = None


@jax.jit
def _copy_tree_jit(tree):
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.copy, tree)


def _copy_device_leaves(tree):
    """Device-side copy of every array leaf, as ONE compiled program. The
    fused sweep step DONATES its state buffers, so any array that must
    outlive the next step (caller-provided warm starts, the
    best-by-validation snapshot, callback hand-offs) needs its own
    storage — and a per-leaf eager copy would pay one dispatch round trip
    per state leaf (~20 at the config-5 shape), so the
    whole tree copies in a single dispatch, counted like every other
    sweep-path launch. Streaming coordinates keep their states as HOST
    numpy (game/streaming.py) — those trees copy on host; routing them
    through the jit copy would be an implicit round-trip the sanitizer
    flags."""
    leaves = jax.tree_util.tree_leaves(tree)
    if leaves and not any(isinstance(l, jax.Array) for l in leaves):
        import numpy as np

        return jax.tree_util.tree_map(np.array, tree)
    dispatch_count.record(1)
    return _copy_tree_jit(tree)


@jax.jit
def _poison_tree_jit(tree):
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: (x * jnp.nan).astype(x.dtype), tree
    )


def _poison_state_nan(state):
    """Chaos-only (util/faults.py ``descent.coordinate`` → ``nan``):
    overwrite every leaf of a coordinate state with NaN on device — the
    injected divergence the health monitor must catch at the next sweep
    boundary. One dispatch, and only on the injection path."""
    dispatch_count.record(1)
    return _poison_tree_jit(state)


#: the per-solve counters of a health row (``obs/health.sweep_health``)
_SOLVE_COUNTERS = ("iterations", "evaluations", "feature_passes")


def _read_health(
    health_dev: Mapping[str, dict | None], barrier
) -> dict[str, dict]:
    """Host health rows from the per-coordinate device triples, fetched
    in ONE device→host round trip that doubles as the sweep's completion
    barrier when ``barrier`` is given (util/force.fetch_scalars). The
    phl annotation below marks the ONE sanctioned steady-state sync —
    the same barrier the sweep always paid, now carrying the health
    payload."""
    order = [cid for cid, h in health_dev.items() if h is not None]
    flat = []
    for cid in order:
        h = health_dev[cid]
        flat.extend((h["loss"], h["gnorm"], h["finite"]))
        # the solves' counters, one of each per solve (obs/health.py); a
        # coordinate kind that folds none gives empty lists
        for name in _SOLVE_COUNTERS:
            flat.extend(h.get(name, ()))
    # phl-ok: PHL002 THE per-sweep barrier read-back — health scalars ride the existing sync
    vals = fetch_scalars(flat, barrier=barrier).tolist()
    out: dict[str, dict] = {}
    at = 0
    for cid in order:
        loss, gnorm, finite = vals[at : at + 3]
        at += 3
        out[cid] = {"loss": loss, "gnorm": gnorm, "finite": bool(finite)}
        for name in _SOLVE_COUNTERS:
            solves = len(health_dev[cid].get(name, ()))
            out[cid][name] = [int(v) for v in vals[at : at + solves]]
            at += solves
    return out


def _record_health_metrics(health: Mapping[str, dict]) -> None:
    """Mirror the sweep's host health rows into ``health.*`` telemetry
    (no-ops while obs is disabled)."""
    obs.counter("health.checks")
    for cid, h in health.items():
        obs.gauge(f"health.loss.{cid}", h["loss"])
        obs.gauge(f"health.gnorm.{cid}", h["gnorm"])
        obs.histogram("health.gnorm", h["gnorm"])


def run_coordinate_descent(
    coordinates: Mapping[str, Coordinate],
    update_sequence: Sequence[str],
    num_iterations: int,
    *,
    initial_states: Mapping[str, object] | None = None,
    locked_coordinates: frozenset[str] = frozenset(),
    validation_fn: Callable[[Mapping[str, object]], float] | None = None,
    larger_is_better: bool = True,
    start_iteration: int = 0,
    initial_best: tuple[dict, float] | None = None,
    sweep_callback: Callable | None = None,
    sweep_hook: Callable | None = None,
    tracker_granularity: str = "sweep",
    fused: bool = True,
    on_divergence: str | None = None,
) -> CoordinateDescentResult:
    """Run block coordinate descent.

    ``validation_fn(states) -> metric`` is evaluated after each full sweep;
    the best snapshot is retained (reference CoordinateDescent tracks the
    best model by validation evaluator, :240+). ``validation_fn`` gets the
    LIVE state arrays (no copy — it runs every sweep and the built-in
    scorer only reduces them to a metric): when donation is active it must
    not retain them or ``np.asarray`` views of them beyond the call — the
    next sweep consumes those buffers. A validator that needs a lasting
    snapshot must copy (``jnp.copy`` / ``np.array(x, copy=True)``).

    ``tracker_granularity`` controls where the honest device barrier (a
    read-back; see util/force.py) lands and therefore what the tracker's
    ``seconds`` mean:

    - ``"sweep"`` (default): the steady-state path is sync-free — each
      coordinate's fused step is enqueued back to back and ONE barrier
      closes the sweep. Per-coordinate rows still carry ``seconds``, but
      they are ENQUEUE walls (dispatch latency, not device compute); the
      per-sweep row's ``sweep_seconds`` (barrier-closed) is the honest
      number, with the barrier's own cost split out as
      ``barrier_seconds`` and the compiled-program launch count as
      ``dispatches``.
    - ``"coordinate"``: opt-in profiling mode — every coordinate's step is
      closed with its own read-back, so per-coordinate ``seconds`` are
      honest device walls at the cost of one blocking round trip per
      coordinate per sweep.

    ``fused=False`` forces the unfused reference sequence (one dispatch
    per arrow, no buffer donation) — the parity oracle for the fused
    programs and a profiling A/B lever. Under the fused path, tracker
    ``info`` leaves that alias the live coordinate state (an
    ``OptimizeResult.x``) are CONSUMED by the next sweep's donation; the
    scalar counters (``n_evals``, ``iterations``, …) every consumer reads
    stay valid.

    Checkpoint/resume (SURVEY §5.3 — the TPU-native replacement for Spark
    task retry): ``sweep_callback(iteration, states, best_states,
    best_metric)`` fires after every completed sweep so callers can flush
    recovery state; ``start_iteration``/``initial_best`` restart descent
    from a checkpoint. Descent is deterministic given states, so a resumed
    run is bit-identical to an uninterrupted one. Under ``fused`` the
    callback receives donation-decoupled COPIES of the states (the live
    arrays are consumed in place by the next sweep — a retained
    ``np.asarray`` view of them would silently mutate), so callbacks may
    retain what they receive.

    ``sweep_hook(iteration, row)`` fires right after each per-sweep
    tracker row is appended, with the row itself. Unlike
    ``sweep_callback`` it carries NO states, so installing one adds no
    donation-decoupling copies (zero extra dispatches) — the estimator
    uses it to emit ``sweep_complete`` lifecycle events.

    Telemetry (photon_tpu/obs): each coordinate step, the sweep, the
    read-back barrier, validation, and the checkpoint callback run
    inside tracer spans, and the tracker rows are derived FROM those
    spans (``seconds``/``sweep_seconds`` are span durations) — same
    fields as always, one clock. With telemetry disabled the spans
    reduce to bare monotonic clock reads; nothing extra is dispatched
    or read back in either mode.

    Health monitoring (photon_tpu/obs/health.py): every sweep step
    computes a per-coordinate loss / grad-norm / ``isfinite`` triple
    INSIDE its already-dispatched program, and the scalars ride the
    sweep's ONE read-back barrier home (``util/force.fetch_scalars`` —
    zero extra dispatches, zero extra read-backs; the dispatch-count
    tests pin this). ``on_divergence`` decides what a non-finite
    coordinate does at the sweep boundary: ``"raise"`` (default; a
    :class:`photon_tpu.obs.health.DivergenceError` instead of a silently
    poisoned checkpoint), ``"warn"``, or ``"halt_coordinate"``
    (re-initialize + freeze the offender, keep training the rest —
    recovery dispatches are paid only at the divergence boundary).
    ``None`` resolves via ``PHOTON_ON_DIVERGENCE``. Host health values
    land in the per-sweep tracker rows as ``health``.
    """
    on_divergence = resolve_policy(on_divergence)
    if tracker_granularity not in ("sweep", "coordinate"):
        raise ValueError(
            f"tracker_granularity must be 'sweep' or 'coordinate', got "
            f"{tracker_granularity!r}"
        )
    unknown = [c for c in update_sequence if c not in coordinates]
    if unknown:
        raise ValueError(f"update sequence references unknown coordinates {unknown}")
    for c in locked_coordinates:
        if c not in coordinates:
            raise ValueError(f"locked coordinate {c} not present")

    # donation active ⇒ every structure that must outlive a sweep needs
    # its own buffers (copies below); donation off (XLA:CPU — see
    # util/target.donation_enabled) ⇒ the copies are skipped
    donating = fused and donation_enabled()
    states = {}
    for cid, coord in coordinates.items():
        if initial_states is not None and cid in initial_states:
            # donation safety: the fused step consumes its state buffers,
            # and caller-provided arrays (checkpoint resume, λ-grid warm
            # starts, locked states) must survive this call — one
            # device-side copy decouples them.
            states[cid] = (
                _copy_device_leaves(initial_states[cid])
                if donating
                else initial_states[cid]
            )
        else:
            states[cid] = coord.initial_state()

    # initial scores (locked coordinates contribute through these forever)
    with obs.span("descent.initial_score", coordinates=len(coordinates)):
        scores = {
            cid: coordinates[cid].score(states[cid]) for cid in coordinates
        }
        total = None
        for s in scores.values():
            total = s if total is None else total + s
    if donating and len(scores) == 1:
        # single coordinate: total IS that coordinate's score buffer, and
        # the fused step donates both arguments — donating one buffer
        # twice is an XLA error, so decouple them once here
        total = _copy_device_leaves(total)

    tracker: list = []
    best_states, best_metric = initial_best or (None, None)

    trainable = [c for c in update_sequence if c not in locked_coordinates]
    per_coordinate = tracker_granularity == "coordinate"
    halted: set[str] = set()
    for it in range(start_iteration, num_iterations):
        # chaos hook (no-op without a fault plan): kill/crash/transient
        # mid-fit — the auto-resume path's injection site
        faults.fault_point("descent.sweep")
        d0 = dispatch_count.snapshot()
        c0 = compile_watch.snapshot()
        #: cid → the step's {loss, gnorm, finite} device scalars (None
        #: where the coordinate kind can't fold them collective-free)
        health_dev: dict[str, dict | None] = {}
        # the transfer sanitizer (PHOTON_SANITIZE=transfers, a no-op
        # otherwise) makes any IMPLICIT host transfer inside the
        # steady-state sweep fail loudly; the sanctioned crossings below
        # open explicit, reasoned escapes (util/sanitize.py)
        with obs.span(
            "descent.sweep", iteration=it
        ) as sweep_span, transfer_sanitizer("descent.sweep"):
            for cid in trainable:
                if cid in halted:
                    continue
                coord = coordinates[cid]
                # chaos hook: a matched ``nan`` clause poisons this
                # coordinate's state BEFORE its step, so the in-program
                # health fold sees non-finite loss/gnorm at this very
                # sweep's barrier; raising kinds fire here too
                _cl = faults.fault_point("descent.coordinate")
                if _cl is not None and _cl.kind == "nan":
                    states[cid] = _poison_state_nan(states[cid])
                # flight-recorder tap (host dict only; two global reads
                # when no recorder is installed): the blackbox of a run
                # killed mid-sweep names the coordinate it was enqueuing
                obs.flight.record("coordinate", iteration=it, coordinate=cid)
                with obs.span(
                    "descent.coordinate", iteration=it, coordinate=cid
                ) as coord_span:
                    if fused:
                        # donating decided ONCE at entry and threaded
                        # through, so the copy discipline above cannot
                        # diverge from the donation the programs perform
                        new_state, new_score, total, info, hlth = (
                            coord.sweep_step(
                                total, scores[cid], states[cid],
                                donate=donating,
                            )
                        )
                    else:
                        new_state, new_score, total, info, hlth = (
                            Coordinate.sweep_step(
                                coord, total, scores[cid], states[cid]
                            )
                        )
                    scores[cid] = new_score
                    states[cid] = new_state
                    health_dev[cid] = hlth
                    if per_coordinate:
                        # a read-back is the only honest boundary for per-
                        # coordinate seconds (util/force.py) —
                        # opt-in: it costs a blocking round trip per
                        # coordinate per sweep
                        with sanctioned_transfers(
                            "per-coordinate profiling barrier (opt-in "
                            "tracker_granularity='coordinate' read-back)"
                        ):
                            force(new_score)
                elapsed = coord_span.duration_s
                obs.counter("descent.coordinate_steps")
                tracker.append(
                    {
                        "iteration": it,
                        "coordinate": cid,
                        "seconds": elapsed,
                        "info": info,
                    }
                )
                logger.info(
                    "CD iter %d coordinate %s %s in %.3fs",
                    it,
                    cid,
                    "trained" if per_coordinate else "enqueued",
                    elapsed,
                )
            barrier_s = 0.0
            if not per_coordinate:
                # sync-free steady state: ONE read-back closes the whole
                # sweep (new_total depends on every coordinate's train +
                # rescore), and the health scalars ride home IN that
                # same fetch — still exactly one read-back per sweep
                with obs.span("descent.barrier", iteration=it) as bar_span:
                    with sanctioned_transfers(
                        "THE per-sweep barrier read-back — health scalars "
                        "ride the one sanctioned sync (util/force."
                        "fetch_scalars)"
                    ):
                        health = _read_health(health_dev, barrier=total)
                barrier_s = bar_span.duration_s
            else:
                # profiling mode already paid a round trip per
                # coordinate; the health fetch is one more
                with sanctioned_transfers(
                    "per-coordinate profiling mode health fetch"
                ):
                    health = _read_health(health_dev, barrier=None)
            # phase-boundary live-buffer census (host metadata only — a
            # gated no-op that never dispatches or reads back; see
            # photon_tpu/obs/memory.py)
            obs.memory.census("sweep_barrier")
            cw = compile_watch.delta(c0)
            dispatches = dispatch_count.snapshot() - d0
            # the counters ride on the sweep span so the exported trace
            # carries the dispatch/compile attribution per sweep
            sweep_span.set(
                dispatches=dispatches,
                compiles=cw["backend_compiles"],
                compile_seconds=cw["backend_compile_s"],
                barrier_seconds=barrier_s,
                granularity=tracker_granularity,
            )
        sweep_row = {
            "iteration": it,
            "sweep_seconds": sweep_span.duration_s,
            "barrier_seconds": barrier_s,
            "dispatches": dispatches,
            # compile share of this sweep's wall (compile_watch): the
            # steady state must show ~0 here — a nonzero count past
            # the first sweep means retrace/recompile leaked into the
            # hot loop (the class of regression PERF.md r6 pins)
            "compiles": cw["backend_compiles"],
            "compile_seconds": cw["backend_compile_s"],
            "granularity": tracker_granularity,
            "health": health,
        }
        tracker.append(sweep_row)
        obs.counter("descent.sweeps")
        obs.histogram("descent.sweep_seconds", sweep_span.duration_s)
        obs.histogram("descent.barrier_seconds", barrier_s)
        _record_health_metrics(health)
        # flight-recorder tap at the barrier choke point: every value
        # here is a host scalar the sweep's ONE read-back already
        # fetched — the tap adds zero dispatches and zero syncs
        obs.flight.record(
            "sweep",
            iteration=it,
            sweep_seconds=round(sweep_span.duration_s, 6),
            barrier_seconds=round(barrier_s, 6),
            dispatches=dispatches,
            health=health,
        )
        # fleet tap (obs/fleet.py): this process's barrier-ARRIVAL wall
        # for the sweep — the per-worker skew signal the aggregator
        # joins by iteration. Host file append only; two module-global
        # reads when no fleet publisher is armed (single-process runs)
        obs.fleet.record_sweep(
            it, sweep_span.duration_s, barrier_s
        )
        diverged = [
            cid for cid, h in health.items() if not h["finite"]
        ]
        if sweep_hook is not None:
            sweep_hook(it, sweep_row)
        for cid in diverged:
            obs.counter("health.divergence")
            obs.flight.record(
                "divergence",
                coordinate=cid,
                iteration=it,
                policy=on_divergence,
                health_row=health[cid],
            )
            obs.instant(
                "health.divergence",
                cat="lifecycle",
                coordinate=cid,
                iteration=it,
                policy=on_divergence,
                **health[cid],
            )
            if on_divergence == "raise":
                raise DivergenceError(cid, it, health[cid])
            if on_divergence == "halt_coordinate":
                logger.warning(
                    "coordinate %s diverged at sweep %d (%s); "
                    "re-initializing and halting it for the rest of "
                    "this descent",
                    cid, it, health[cid],
                )
                halted.add(cid)
                # recovery (divergence boundary only, never steady
                # state): fresh state, fresh score, total rebuilt from
                # scratch — the old total carries the NaN
                states[cid] = coordinates[cid].initial_state()
                scores[cid] = coordinates[cid].score(states[cid])
                total = None
                for s in scores.values():
                    total = s if total is None else total + s
                if donating and len(scores) == 1:
                    total = _copy_device_leaves(total)
            else:
                logger.warning(
                    "coordinate %s diverged at sweep %d (%s); policy "
                    "'warn' — training continues on non-finite state",
                    cid, it, health[cid],
                )
        if validation_fn is not None:
            with obs.span("descent.validation", iteration=it):
                # phl-ok: PHL002 validation barrier — the one sanctioned per-iteration read-back
                metric = float(validation_fn(states))
            tracker.append({"iteration": it, "validation": metric})
            logger.info("CD iter %d validation metric %.6f", it, metric)
            if best_metric is None or (
                metric > best_metric if larger_is_better else metric < best_metric
            ):
                best_metric = metric
                # the snapshot must own its buffers under donation — the
                # next sweep consumes the live state arrays
                best_states = (
                    {cid: _copy_device_leaves(s) for cid, s in states.items()}
                    if donating
                    else dict(states)
                )
        if sweep_callback is not None:
            # the callback gets its OWN buffers under donation: the next
            # sweep consumes the live state arrays IN PLACE, and even an
            # np.asarray taken inside the callback is a zero-copy VIEW of
            # the device buffer on CPU — it would silently mutate when
            # XLA reuses the donated storage. One device-side copy per
            # sweep (only when a callback is installed) restores the
            # retain-what-you-received contract.
            with obs.span("descent.checkpoint", iteration=it):
                cb_states = (
                    {
                        cid: _copy_device_leaves(s)
                        for cid, s in states.items()
                    }
                    if donating
                    else states
                )
                sweep_callback(it, cb_states, best_states, best_metric)

    return CoordinateDescentResult(
        states=states,
        tracker=tracker,
        best_states=best_states,
        best_metric=best_metric,
    )
