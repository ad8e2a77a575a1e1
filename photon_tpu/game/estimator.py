"""GameEstimator: the GAME trainer.

Reference parity: photon-api estimators/GameEstimator.scala:304-846 —
GameData → per-coordinate datasets (FixedEffectDataSet / RandomEffectDataSet
+ projection) → CoordinateDescent over a sequence of optimization configs
with warm-start chaining between λ configs; validation evaluators; partial
retraining with locked coordinates; normalization contexts per shard.

The λ grid: each coordinate carries ``regularization_weights``; the
estimator trains the cartesian sweep positionally (grid i uses each
coordinate's ``weights[min(i, len-1)]``) with warm starts — matching the
reference's ``prepareGameOptConfigs`` cartesian expansion for the common
aligned-grid case (GameTrainingDriver.scala:612-623).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Mapping, Sequence

import jax.numpy as jnp
import numpy as np

from photon_tpu import obs
from photon_tpu.evaluation.evaluators import EvaluatorType
from photon_tpu.game.config import (
    CoordinateConfig,
    FixedEffectCoordinateConfig,
    MatrixFactorizationCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.coordinate import (
    FixedEffectCoordinate,
    MatrixFactorizationCoordinate,
    RandomEffectCoordinate,
)
from photon_tpu.game.data import GameData, build_random_effect_dataset
from photon_tpu.game.descent import run_coordinate_descent
from photon_tpu.game.model import (
    GameModel,
    RandomEffectModel,
    merge_random_effect_carryover,
)
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.types import TaskType

logger = logging.getLogger(__name__)


def _carry_over_prior_models(model: GameModel, initial: GameModel) -> GameModel:
    """Warm-start survival of prior per-entity models with no new data
    (reference RandomEffectCoordinate.updateModel leftOuterJoin branch)."""
    merged = dict(model.coordinates)
    for cid, new_cm in model.coordinates.items():
        prior_cm = initial.coordinates.get(cid)
        if isinstance(new_cm, RandomEffectModel) and isinstance(
            prior_cm, RandomEffectModel
        ):
            merged[cid] = merge_random_effect_carryover(new_cm, prior_cm)
    return dataclasses.replace(model, coordinates=merged)


def shard_shape_census(coordinates, mesh) -> dict:
    """Per-coordinate census of the meshed random-effect block layout —
    the shard-uniformity contract behind the PR 3 shape budget on a
    mesh: every bucket's entity axis must divide the entity shard count
    so EVERY shard holds an identical ``(E/shards, rows, d)`` block and
    all shards compile ONE shared bucket/level set (GSPMD partitions one
    program; a shard-divergent block shape would force a repartition or
    a per-shard program — exactly the compile-bill blowup the ShapePool
    exists to prevent). Raises ``ValueError`` on divergence; returns
    ``{cid: {"entity_shards", "per_shard_blocks", "levels"}}`` with the
    shared ``(rows, d)`` level set per coordinate."""
    from photon_tpu.game.coordinate import RandomEffectCoordinate
    from photon_tpu.parallel.mesh import ENTITY_AXIS

    shards = dict(mesh.shape).get(ENTITY_AXIS, 1)
    census = {}
    for cid, coord in coordinates.items():
        if not isinstance(coord, RandomEffectCoordinate):
            continue
        blocks = []
        levels = set()
        for db in coord.device_buckets:
            e, rows, d = (int(s) for s in db.features.shape)
            if e % shards != 0:
                raise ValueError(
                    f"coordinate {cid}: bucket entity axis {e} does not "
                    f"divide {shards} entity shards — shards would "
                    "compile divergent block shapes"
                )
            blocks.append([e // shards, rows, d])
            levels.add((rows, d))
        census[cid] = {
            "entity_shards": shards,
            "per_shard_blocks": blocks,
            "levels": sorted(levels),
        }
    return census


@dataclasses.dataclass
class GameTrainingResult:
    model: GameModel
    evaluation: float | None
    regularization_weights: dict
    tracker: list
    wall_time_s: float
    #: compile telemetry for this grid point (util/compile_watch deltas:
    #: n programs compiled, backend-compile seconds, persistent-cache
    #: hits/misses), plus the parallel-precompile report on grid 0 when
    #: ``GameEstimator.precompile`` is on
    compile_stats: dict | None = None


@dataclasses.dataclass
class BuiltFit:
    """A fit that is built and not yet run: what ``GameEstimator.build``
    hands back and ``GameEstimator.fit`` itself runs on. The coordinates
    hold their placed data (FE batch and window layout, RE bucket blocks)
    and compile their sweep programs at the first sweep, or hold them
    already where the estimator precompiles. Anything that wants sweeps
    without a whole ``fit`` drives them as ``fit`` does::

        built = estimator.build(data)
        run_coordinate_descent(
            built.coordinates, built.update_sequence,
            built.descent_iterations,
            initial_states=built.initial_states(),
            locked_coordinates=built.locked_coordinates,
        )
    """

    coordinates: dict
    #: cid -> the host-side RandomEffectDataset its coordinate was placed
    #: from (vocabulary, buckets, which rows each entity trains on)
    re_datasets: dict
    update_sequence: tuple
    locked_coordinates: frozenset
    descent_iterations: int
    #: placed states of the coordinates an initial model covers; None
    #: without one
    warm_states: dict | None = None
    #: ``precompile_coordinates``' report where the estimator precompiles
    precompile_report: dict | None = None
    #: cid -> {"buckets": s, "place": s}: host seconds of the coordinate's
    #: bucketing (RE only) and of its layout build and placement, the
    #: ``photon.game.prepare.*`` spans
    prepare_seconds: dict = dataclasses.field(default_factory=dict)

    def initial_states(self) -> dict:
        """cid -> the state descent starts from: the warm start where an
        initial model covers the coordinate, else the coordinate's own
        zero state, placed as its sweep program expects it."""
        warm = self.warm_states or {}
        return {
            cid: warm[cid] if cid in warm else coord.initial_state()
            for cid, coord in self.coordinates.items()
        }


@dataclasses.dataclass
class GameEstimator:
    """Train a GAME model by block coordinate descent.

    Parameters mirror the reference GameEstimator Params
    (GameEstimator.scala:70-133): trainingTask, coordinate configurations,
    update sequence, descent iterations, normalization contexts,
    partial-retrain locked coordinates + initial model, validation.
    """

    task: TaskType
    coordinate_configs: Mapping[str, CoordinateConfig]
    update_sequence: Sequence[str]
    descent_iterations: int = 1
    normalization_contexts: Mapping[str, NormalizationContext] | None = None
    locked_coordinates: frozenset = frozenset()
    #: warm-start semantics for the RE lower bound: entities WITHOUT a prior
    #: model bypass ``active_data_lower_bound`` (reference
    #: GameEstimator.ignoreThresholdForNewModels :127-133 →
    #: RandomEffectDataSet.generateActiveData). Requires ``initial_model``.
    ignore_threshold_for_new_models: bool = False
    #: plain EvaluatorType, or a GroupedEvaluatorSpec (per-entity metric
    #: like ``AUC:queryId`` — reference MultiEvaluatorType); per-sweep
    #: evaluation runs on device either way
    validation_evaluator: "EvaluatorType | object | None" = None
    #: (data, entity) device mesh; when set, fixed-effect batches shard
    #: rows over the whole mesh (gradient psums over ICI) and random-effect
    #: buckets shard entities over the entity axis — the reference's
    #: treeAggregate + entity partitioner, SURVEY §2.10/§5.8.
    mesh: object | None = None
    dtype: object = jnp.float32
    seed: int = 0
    #: descent tracker barrier placement — "sweep" (default, sync-free
    #: steady state: one read-back per sweep) or "coordinate" (opt-in
    #: profiling: honest per-coordinate walls at one blocking round trip
    #: per coordinate per sweep); see game/descent.run_coordinate_descent
    tracker_granularity: str = "sweep"
    #: AOT-precompile the fused sweep/score programs on a thread pool
    #: before descent starts (game/descent.precompile_coordinates), so
    #: independent compiles overlap instead of serializing inside the
    #: first sweep. λ rides as a traced scalar, so one precompiled
    #: program set serves the whole regularization grid. Off by default:
    #: it front-loads the compile bill, which only pays when the fit is
    #: compile-bound (cold caches, many
    #: coordinates).
    precompile: bool = False
    #: lifecycle event bus (util/events.EventEmitter). When set, ``fit``
    #: emits ``setup`` / ``sweep_complete`` / ``training_finish`` /
    #: ``training_failure`` events with payloads, so LIBRARY callers get
    #: the same lifecycle stream the CLI drivers always had. Excluded
    #: from the checkpoint fingerprint (listeners don't change numerics).
    events: object | None = None
    #: what a NON-FINITE coordinate (NaN/Inf loss, gradient, or state —
    #: photon_tpu/obs/health.py) does at the sweep boundary where the
    #: health monitor catches it: "raise" (default — fail loudly with
    #: DivergenceError instead of silently poisoning the checkpoint and
    #: every later sweep), "warn" (log + event, keep going), or
    #: "halt_coordinate" (re-initialize + freeze the offender, train the
    #: rest). None resolves via the PHOTON_ON_DIVERGENCE env. The
    #: monitor itself is free: health scalars are computed inside the
    #: already-dispatched sweep programs and ride the existing per-sweep
    #: read-back barrier.
    on_divergence: str | None = None
    #: supervised auto-resume budget (game/recovery.py): when > 0, a
    #: ``fit`` that fails with a TRANSIENT error (UNAVAILABLE-class
    #: transport flake, non-permanent I/O) or a DIVERGENT one
    #: (DivergenceError — the checkpoint predates the poisoned sweep)
    #: restarts itself up to this many times with capped
    #: jittered-exponential backoff, resuming from the newest valid
    #: checkpoint when ``checkpoint_dir`` is set. Fatal errors (shape,
    #: config, OOM) never retry. ``PHOTON_MAX_RESTARTS`` env wins over
    #: this value (the env-over-config precedence every knob here
    #: follows); default 0: supervision off.
    max_restarts: int | None = None
    #: retain the fit's built coordinates on ``last_coordinates`` after
    #: ``fit`` returns — for audit tooling that inspects the fit's OWN
    #: AOT executables and live table placements (the ``--programs``
    #: estimator audit, bench's meshed leg, the northstar drive). OFF
    #: by default: coordinates pin the entire on-device dataset (entity
    #: blocks, the FE batch), and a long-lived estimator must not hold
    #: the prior fit's footprint through its next phase.
    keep_coordinates: bool = False
    #: out-of-core streaming training (game/streaming.py): a
    #: StreamConfig, an int chunk size, or True (env/default chunk
    #: size). When set, datasets stay HOST-resident and every sweep
    #: streams fixed-shape chunks through a two-deep host→device double
    #: buffer — peak device residency bounded at 2 chunks + tables
    #: (ledger-verified when ``assert_residency``), coefficients
    #: BIT-IDENTICAL to the materialized path. Requires mesh=None in
    #: process (multi-PROCESS ``ingest_shard`` slices compose), locked
    #: fixed effects, no device validation scorer, no MF coordinates.
    stream: object | None = None

    def __post_init__(self):
        #: per-fit telemetry deltas (wall, dispatches, compiles) for the
        #: most recent ``fit()`` call — see the fit docstring
        self.last_fit_stats: dict | None = None
        #: built coordinates of the most recent fit (audit tooling)
        self.last_coordinates: dict | None = None
        missing = [c for c in self.update_sequence if c not in self.coordinate_configs]
        if missing:
            raise ValueError(f"update sequence names unknown coordinates: {missing}")
        if self.locked_coordinates and not set(self.locked_coordinates) <= set(
            self.coordinate_configs
        ):
            raise ValueError("locked coordinates must be configured")
        if self.tracker_granularity not in ("sweep", "coordinate"):
            # fail at construction, not minutes later inside fit
            raise ValueError(
                "tracker_granularity must be 'sweep' or 'coordinate', got "
                f"{self.tracker_granularity!r}"
            )
        from photon_tpu.obs.health import resolve_policy

        # validate (and env-resolve) at construction, not mid-fit
        self.on_divergence = resolve_policy(self.on_divergence)
        from photon_tpu.game.recovery import max_restarts_from_env

        self.max_restarts = max_restarts_from_env(self.max_restarts)

    # ------------------------------------------------------------------

    def _existing_model_keys(self, cid, initial_model):
        """Prior-model key set for the RE lower-bound bypass (or None when
        the bypass is off) — needed by both the shape profile and the
        dataset build, so resolved once."""
        if not self.ignore_threshold_for_new_models or initial_model is None:
            return None
        prior = initial_model.coordinates.get(cid)
        return (
            prior.modeled_keys()
            if isinstance(prior, RandomEffectModel)
            else set()
        )

    def _build_shape_pool(self, data: GameData, initial_model=None):
        """One pooled bucket-shape level set across every RE coordinate
        (game/data.ShapePool): the cheap profile pass runs before any
        dataset build so all coordinates snap to shared (rows, d) shapes
        — strictly fewer distinct solve programs for the compile bill.
        Coordinates with the budget disabled (shape_budget=0 /
        PHOTON_RE_SHAPE_BUDGET=0) opt out, as do shards the profile
        cannot price exactly (general sparse index compaction)."""
        from photon_tpu.game.data import (
            ShapePool,
            profile_random_effect_shapes,
            re_shape_budget,
        )

        budgets = []
        profiles = {}
        for cid, cfg in self.coordinate_configs.items():
            if not isinstance(cfg, RandomEffectCoordinateConfig):
                continue
            b = re_shape_budget(cfg.shape_budget)
            if b is None:
                continue  # budget disabled for this coordinate
            prof = profile_random_effect_shapes(
                data,
                cfg,
                existing_model_keys=self._existing_model_keys(
                    cid, initial_model
                ),
            )
            if prof is None:
                continue  # not exactly profilable: per-coordinate DP
            budgets.append(b)
            profiles[cid] = prof
        if not profiles:
            return None
        pool = ShapePool(budget=min(budgets))
        for d_pad, n_trn in profiles.values():
            pool.observe(d_pad, n_trn)
        pool.freeze()
        logger.info("RE shape pool: %s", pool.stats())
        return pool

    def _validate_streaming(self, stream_cfg, validation_data):
        """Everything streaming mode refuses, rejected at fit entry with
        the actionable message — never discovered mid-sweep."""
        from photon_tpu.game.streaming import StreamingModeError

        if self.mesh is not None:
            raise StreamingModeError(
                "streaming fits are per-process (mesh=None): an in-process "
                "device mesh keeps the materialized path; multi-PROCESS "
                "scale-out streams disjoint ingest_shard slices instead"
            )
        if validation_data is not None and self.validation_evaluator is not None:
            raise StreamingModeError(
                "streaming fits do not support the device validation "
                "scorer (it materializes the validation set on device); "
                "evaluate the returned model host-side instead"
            )
        for cid, cfg in self.coordinate_configs.items():
            if isinstance(cfg, MatrixFactorizationCoordinateConfig):
                raise StreamingModeError(
                    f"coordinate {cid!r}: matrix-factorization coordinates "
                    "are not streamable (factor-table training gathers "
                    "arbitrary rows per chunk)"
                )
            if (
                isinstance(cfg, FixedEffectCoordinateConfig)
                and cid not in self.locked_coordinates
            ):
                raise StreamingModeError(
                    f"coordinate {cid!r}: streaming fits require "
                    "fixed-effect coordinates to be LOCKED (the global "
                    "L-BFGS cannot train bit-exactly from chunks); train "
                    "it materialized first, then stream with it locked — "
                    "the daily-retrain shape"
                )
            if cfg.optimization.variance_computation.value != "NONE":
                raise StreamingModeError(
                    f"coordinate {cid!r}: streaming fits do not compute "
                    "coefficient variances; set variance_computation=NONE"
                )

    def _build_coordinates(
        self, data: GameData, initial_model=None, shape_pool=None,
        stream_cfg=None,
    ):
        coords = {}
        re_datasets = {}
        seconds: dict = {}
        norm = self.normalization_contexts or {}
        stream_telemetry = None
        if stream_cfg is not None:
            from photon_tpu.game.streaming import StreamTelemetry

            stream_telemetry = StreamTelemetry()
        if shape_pool is None:
            with obs.span("fit.shape_profile"):
                shape_pool = self._build_shape_pool(data, initial_model)
        for cid, cfg in self.coordinate_configs.items():
            if isinstance(cfg, FixedEffectCoordinateConfig):
                if stream_cfg is not None:
                    from photon_tpu.game.streaming import (
                        StreamingFixedEffectCoordinate,
                    )

                    coords[cid] = StreamingFixedEffectCoordinate.build_streaming(
                        data,
                        cfg,
                        norm.get(cfg.feature_shard, NormalizationContext()),
                        self.dtype,
                        stream=stream_cfg,
                        telemetry=stream_telemetry,
                    )
                    continue
                with obs.span(
                    "game.prepare.place", cat="build", coordinate=cid
                ) as place:
                    coords[cid] = FixedEffectCoordinate.build(
                        data,
                        cfg,
                        norm.get(cfg.feature_shard, NormalizationContext()),
                        self.dtype,
                        seed=self.seed,
                        mesh=self.mesh,
                    )
                    batch = coords[cid].batch
                    block = (
                        batch.features
                        if hasattr(batch, "features")
                        else batch.values
                    )
                    place.set(
                        layout=type(batch).__name__,
                        block_bytes=int(block.nbytes),
                    )
                seconds[cid] = {"place": place.duration_s}
            elif isinstance(cfg, RandomEffectCoordinateConfig):
                entity_shards = 1
                if self.mesh is not None:
                    from photon_tpu.parallel.mesh import ENTITY_AXIS

                    entity_shards = dict(self.mesh.shape).get(ENTITY_AXIS, 1)
                with obs.span(
                    "game.prepare.buckets", cat="build", coordinate=cid
                ) as bucketing:
                    ds = build_random_effect_dataset(
                        data,
                        cfg,
                        seed=self.seed,
                        entity_shards=entity_shards,
                        existing_model_keys=self._existing_model_keys(
                            cid, initial_model
                        ),
                        shape_pool=shape_pool,
                    )
                re_datasets[cid] = ds
                seconds[cid] = {"buckets": bucketing.duration_s}
                if stream_cfg is not None:
                    from photon_tpu.game.streaming import (
                        StreamingRandomEffectCoordinate,
                    )

                    coords[cid] = (
                        StreamingRandomEffectCoordinate.build_streaming(
                            ds, cfg, self.dtype, stream=stream_cfg,
                            telemetry=stream_telemetry,
                        )
                    )
                else:
                    with obs.span(
                        "game.prepare.place", cat="build", coordinate=cid
                    ) as place:
                        coords[cid] = RandomEffectCoordinate.build(
                            data, ds, cfg, self.dtype, mesh=self.mesh
                        )
                        place.set(
                            score_layout=coords[cid].score_layout,
                            width_groups=len(coords[cid].score_blocks),
                            table_fetch=coords[cid].table_fetch,
                            packed_table_bytes=coords[cid].packed_table_bytes,
                        )
                    seconds[cid]["place"] = place.duration_s
                waste = ds.padding_waste()
                logger.info(
                    "coordinate %s: %d entities in %d buckets "
                    "(padded shapes %s, padding waste %.1f%%)",
                    cid,
                    ds.num_entities,
                    len(ds.buckets),
                    [(b.features.shape) for b in ds.buckets],
                    100.0 * waste["total_waste"],
                )
            elif isinstance(cfg, MatrixFactorizationCoordinateConfig):
                coords[cid] = MatrixFactorizationCoordinate.build(
                    data, cfg, self.dtype, mesh=self.mesh, seed=self.seed
                )
            else:
                raise TypeError(f"unknown coordinate config for {cid}")
        return coords, re_datasets, seconds

    def _grid_length(self) -> int:
        return max(
            len(cfg.regularization_weights)
            for cfg in self.coordinate_configs.values()
        )

    # ------------------------------------------------------------------

    def fit(
        self,
        data: GameData,
        *,
        validation_data: GameData | None = None,
        initial_model: GameModel | None = None,
        grid_callback=None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        shape_pool=None,
        mesh=None,
        stream=None,
        warm_start: str | None = None,
        model_checkpoint_dir: str | None = None,
    ) -> list[GameTrainingResult]:
        """Train one GameModel per λ-grid point, warm-starting across the
        grid (reference fit :304-390 + train :746).

        Telemetry: the whole call runs inside a ``fit`` tracer span
        (photon_tpu/obs) with nested ``fit.data_build`` /
        ``fit.precompile`` / ``fit.grid`` → ``descent.sweep`` →
        ``descent.coordinate`` spans, and per-FIT deltas of the
        dispatch/compile counters land on the span and in
        ``self.last_fit_stats`` — deltas, not process totals, so two
        sequential fits in one process each report their own bill.
        Lifecycle events (``setup`` / ``sweep_complete`` /
        ``training_finish`` / ``training_failure``) go to
        ``self.events`` when an emitter is configured.

        ``grid_callback(grid_index, result)`` fires as each grid point
        completes — drivers use it to flush partial progress to disk so a
        crash never loses finished models (SURVEY §5.3: the reference
        delegates recovery to Spark task retry; here checkpointing is the
        recovery story).

        ``checkpoint_dir`` enables mid-descent recovery on top of that:
        coordinate states are flushed after every ``checkpoint_every``
        sweeps, and a rerun with the same arguments resumes from the last
        completed sweep (skipping already-completed grid points, whose
        models the previous run flushed through ``grid_callback``) and
        produces bit-identical models. Entries for skipped grid points are
        ``None`` in the returned list.

        ``shape_pool`` injects a prebuilt RE bucket-shape pool (from
        ``_build_shape_pool`` on the SAME data/initial model) so callers
        that already profiled shapes — e.g. bench's projected-bill pass —
        don't pay the profile + DP twice and are guaranteed the fit
        buckets exactly as they priced.

        ``mesh`` spans this fit over a device mesh (overriding the
        constructor's ``mesh`` field for this call and onward): the
        fixed-effect batch shards rows over EVERY mesh device, packed
        random-effect entity tables shard over the entity axis
        (``parallel/mesh.shard_entities``), and the fused sweep/score
        programs compile against those shardings — PR 2's sync-free
        steady state (one barrier per sweep, zero per-step re-placements)
        survives on-mesh, gated by the transfer sanitizer and the SPMD
        program audit. Checkpoints fingerprint the mesh TOPOLOGY (axis
        names + shape), and a resume re-places loaded states onto each
        coordinate's declared sharding.

        ``stream`` (per-fit override of the constructor field — a
        StreamConfig, an int chunk size, or True) trains OUT-OF-CORE:
        datasets stay host-resident and every sweep streams fixed-shape
        chunks through the double-buffered pipeline (game/streaming.py)
        with ledger-verified bounded residency — bit-identical
        coefficients, zero steady-state compiles, one (host no-op)
        barrier per sweep. ``self.last_fit_stats["stream"]`` then
        carries the chunk/stage-wall/H2D-overlap/residency report.

        ``warm_start`` names a model checkpoint DIRECTORY
        (:class:`photon_tpu.game.checkpoint.ModelCheckpointStore`): the
        newest valid sequence-numbered snapshot loads as the
        ``initial_model`` — the daily-retrain entry point, where
        today's fit updates only entities present in today's data and
        every other entity's model carries over bit-identically. An
        EMPTY directory cold-starts with a warning (day zero);
        combining ``warm_start`` with an explicit ``initial_model`` is
        an error. ``model_checkpoint_dir`` (often the same directory)
        saves the final grid point's model as the next snapshot after
        the fit completes, so tomorrow's run finds it.
        """
        from photon_tpu.util import compile_watch, dispatch_count

        if mesh is not None:
            # per-fit override of the constructor field: the mesh decides
            # every placement the build performs, so it must be settled
            # before the data/coordinate build below
            self.mesh = mesh
        if stream is None:
            stream = self.stream
        stream_cfg = None
        if stream is not None and stream is not False:
            from photon_tpu.game.streaming import StreamConfig

            stream_cfg = StreamConfig.resolve(stream)
            self._validate_streaming(stream_cfg, validation_data)
        if warm_start is not None:
            if initial_model is not None:
                raise ValueError(
                    "pass either warm_start (a model checkpoint directory) "
                    "or initial_model, not both"
                )
            from photon_tpu.game.checkpoint import ModelCheckpointStore

            loaded = ModelCheckpointStore(warm_start).load_latest()
            if loaded is None:
                logger.warning(
                    "warm_start directory %s holds no model snapshot; "
                    "cold-starting (day zero of the retrain loop)",
                    warm_start,
                )
            else:
                initial_model, warm_seq = loaded
                logger.info(
                    "warm-starting from model snapshot seq %d in %s",
                    warm_seq, warm_start,
                )
                obs.counter("fit.warm_starts")

        emitter = self.events
        t_fit = time.perf_counter()
        # per-FIT counter baselines: the process-global compile/dispatch
        # counters are monotonic (their jax.monitoring listeners register
        # once per process, compile_watch.install), so every fit reports
        # its own DELTA — repeated fits never double-count
        fit_d0 = dispatch_count.snapshot()
        fit_c0 = compile_watch.snapshot()
        with obs.span(
            "fit",
            task=self.task.name,
            coordinates=len(self.coordinate_configs),
            grid_length=self._grid_length(),
        ) as fit_span:
            obs.counter("fit.count")
            obs.flight.record(
                "fit",
                task=self.task.name,
                coordinates=len(self.coordinate_configs),
                grid_length=self._grid_length(),
            )
            if emitter is not None:
                emitter.emit(
                    "setup",
                    coordinates=list(self.coordinate_configs),
                    update_sequence=list(self.update_sequence),
                    grid_length=self._grid_length(),
                    descent_iterations=self.descent_iterations,
                    num_samples=int(data.num_samples),
                )
            def attempt():
                return self._fit_impl(
                    data,
                    validation_data=validation_data,
                    initial_model=initial_model,
                    grid_callback=grid_callback,
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every,
                    shape_pool=shape_pool,
                    stream_cfg=stream_cfg,
                )

            try:
                if self.max_restarts:
                    # supervised auto-resume (game/recovery.py): each
                    # restart re-enters _fit_impl, which reloads the
                    # newest VALID checkpoint — transient and divergent
                    # failures resume mid-descent instead of killing the
                    # training worker; fatal ones re-raise immediately
                    from photon_tpu.game.recovery import run_with_recovery

                    if checkpoint_dir is None:
                        logger.warning(
                            "max_restarts=%d without checkpoint_dir: a "
                            "restart retrains from scratch instead of "
                            "resuming mid-descent",
                            self.max_restarts,
                        )
                    results = run_with_recovery(
                        attempt, max_restarts=self.max_restarts
                    )
                else:
                    results = attempt()
            except Exception as e:
                # a failed fit must not leave the PREVIOUS fit's numbers
                # behind as if they described this call
                self.last_fit_stats = None
                if emitter is not None:
                    emitter.emit(
                        "training_failure",
                        error=f"{type(e).__name__}: {e}",
                    )
                raise
            wall_s = time.perf_counter() - t_fit
            cw = compile_watch.delta(fit_c0)
            # ingest provenance: "cache" when the data came from the
            # feature-cache replay (zero avro decode), "host" otherwise —
            # the field that lets a profile reader tell a warm run apart
            prov = getattr(data, "provenance", None) or {}
            #: per-fit telemetry summary (deltas over this call only)
            self.last_fit_stats = {
                "wall_s": round(wall_s, 4),
                "dispatches": dispatch_count.snapshot() - fit_d0,
                "ingest": prov.get("source", "host"),
                **cw,
            }
            if getattr(self, "_stream_telemetry", None) is not None:
                # chunk pipeline report: stage waterfall, H2D overlap
                # split, residency-guard peak — the bench gates read it
                self.last_fit_stats["stream"] = self._stream_telemetry.report()
                self._stream_telemetry = None
            fit_span.set(
                **{
                    k: v
                    for k, v in self.last_fit_stats.items()
                    if not isinstance(v, dict)
                }
            )
            if model_checkpoint_dir is not None:
                from photon_tpu.game.checkpoint import ModelCheckpointStore

                final = [r for r in results if r is not None]
                if final:
                    seq = ModelCheckpointStore(model_checkpoint_dir).save(
                        final[-1].model
                    )
                    logger.info(
                        "saved model snapshot seq %d to %s",
                        seq, model_checkpoint_dir,
                    )
            if emitter is not None:
                evals = [
                    r.evaluation
                    for r in results
                    if r is not None and r.evaluation is not None
                ]
                ev = self.validation_evaluator
                pick = (
                    max if ev is None or ev.larger_is_better else min
                )
                emitter.emit(
                    "training_finish",
                    n_grid_points=len(results),
                    best_evaluation=pick(evals) if evals else None,
                    wall_time_s=round(wall_s, 4),
                    dispatches=self.last_fit_stats["dispatches"],
                )
            return results

    def build(
        self,
        data: GameData,
        *,
        initial_model: GameModel | None = None,
        shape_pool=None,
        stream=None,
    ) -> BuiltFit:
        """Everything of a fit that comes before its first sweep: the data
        padded to the mesh, every coordinate built and its data placed
        (``photon.game.prepare`` and, under it, ``.buckets`` and ``.place``
        spans), the sweep programs precompiled where ``precompile`` is
        set, and the states an initial model gives. ``fit`` runs on what
        this returns; so can anything else that wants to drive sweeps
        (:class:`BuiltFit`). ``shape_pool`` and ``stream`` are ``fit``'s."""
        if stream is None:
            stream = self.stream
        stream_cfg = None
        if stream is not None and stream is not False:
            from photon_tpu.game.streaming import StreamConfig

            stream_cfg = StreamConfig.resolve(stream)
        if self.ignore_threshold_for_new_models and initial_model is None:
            raise ValueError(
                "ignore_threshold_for_new_models requires an initial model "
                "(reference GameEstimator validation :226)"
            )
        with obs.span(
            "fit.data_build", num_samples=int(data.num_samples)
        ), obs.span("game.prepare", cat="build"):
            if self.mesh is not None:
                from photon_tpu.game.data import pad_game_data

                data = pad_game_data(data, int(self.mesh.devices.size))
            coordinates, re_datasets, seconds = self._build_coordinates(
                data, initial_model, shape_pool=shape_pool,
                stream_cfg=stream_cfg,
            )
        self._stream_telemetry = None
        if stream_cfg is not None:
            self._stream_telemetry = self._arm_stream_guard(
                coordinates, stream_cfg
            )
        if self.mesh is not None:
            # shard-uniformity contract (the PR 3 shape budget on a
            # mesh): every shard must compile the SAME bucket/level set
            # — divergence is a build bug, caught before any compile
            census = shard_shape_census(coordinates, self.mesh)
            for cid, row in census.items():
                logger.info(
                    "coordinate %s: %d entity shards × per-shard blocks "
                    "%s (shared level set %s)",
                    cid, row["entity_shards"], row["per_shard_blocks"],
                    row["levels"],
                )
        # built coordinates retained only on request (keep_coordinates):
        # audit tooling reads the fit's own AOT executables and live
        # table placements from here; everyone else gets the device
        # memory back when the BuiltFit drops
        self.last_coordinates = coordinates if self.keep_coordinates else None
        # phase-boundary memory censuses (photon_tpu/obs/memory.py):
        # host-metadata snapshots of every live device buffer — gated
        # no-ops that never dispatch or read back
        obs.memory.census("data_build")

        precompile_report = None
        if self.precompile:
            from photon_tpu.game.descent import precompile_coordinates

            with obs.span("fit.precompile") as pre_span:
                precompile_report = precompile_coordinates(
                    coordinates, locked=self.locked_coordinates
                )
                pre_span.set(
                    n_programs=precompile_report["n_programs"],
                    cache_hits=precompile_report["cache_hits"],
                )
            obs.memory.census("precompile")

        warm_states = None
        if initial_model is not None:
            with obs.span("fit.warm_start"):
                warm_states = self._place_states(
                    self._states_from_model(
                        initial_model, coordinates, re_datasets
                    ),
                    coordinates,
                )
            obs.memory.census("warm_start")
        return BuiltFit(
            coordinates=coordinates,
            re_datasets=re_datasets,
            update_sequence=tuple(self.update_sequence),
            locked_coordinates=frozenset(self.locked_coordinates),
            descent_iterations=self.descent_iterations,
            warm_states=warm_states,
            precompile_report=precompile_report,
            prepare_seconds=seconds,
        )

    def _fit_impl(
        self,
        data: GameData,
        *,
        validation_data,
        initial_model,
        grid_callback,
        checkpoint_dir,
        checkpoint_every,
        shape_pool,
        stream_cfg=None,
    ) -> list[GameTrainingResult]:
        built = self.build(
            data, initial_model=initial_model, shape_pool=shape_pool,
            stream=stream_cfg if stream_cfg is not None else False,
        )
        coordinates = built.coordinates
        precompile_report = built.precompile_report
        init_states = built.warm_states

        from photon_tpu.util import compile_watch

        validation_fn = None
        if validation_data is not None and self.validation_evaluator is not None:
            # built once; per-sweep evaluation is device gathers/einsums over
            # the live optimizer states — no GameModel/transformer rebuild
            # per sweep (r2 weak #6)
            from photon_tpu.game.validation import DeviceValidationScorer

            with obs.span("fit.validation_build"):
                scorer = DeviceValidationScorer.build(
                    validation_data,
                    coordinates,
                    self.validation_evaluator,
                    self.dtype,
                )
            validation_fn = scorer.evaluate

        checkpointer = None
        ckpt = None
        fingerprint = None
        if checkpoint_dir is not None:
            from photon_tpu.game.checkpoint import DescentCheckpointer

            # stale-config guard: resuming state trained under different
            # hyperparameters must be a hard error, not silent reuse
            from photon_tpu.game.data import re_shape_budget

            from photon_tpu.parallel.mesh import mesh_fingerprint

            fingerprint = repr(
                (
                    self.task,
                    sorted(
                        (cid, repr(cfg))
                        for cid, cfg in self.coordinate_configs.items()
                    ),
                    tuple(self.update_sequence),
                    self.descent_iterations,
                    sorted(self.locked_coordinates),
                    self.seed,
                    data.num_samples,
                    # mesh TOPOLOGY (axis names + per-axis device
                    # counts): a checkpoint's saved leaves are laid out
                    # for one topology (entity-sharded tables pad the
                    # entity axis to divide it) — resuming under
                    # another must be the clean stale-config error, not
                    # a silent reshard or an unflatten failure
                    mesh_fingerprint(self.mesh),
                    # layout knob: a different shape budget changes the
                    # per-bucket state SHAPES — resuming across it must be
                    # the clean stale-config error, not a cryptic unflatten
                    # failure. Normalized via the build's own parse site so
                    # equivalent configs never spuriously invalidate (the
                    # env override rides along).
                    sorted(
                        (cid, re_shape_budget(cfg.shape_budget))
                        for cid, cfg in self.coordinate_configs.items()
                        if isinstance(cfg, RandomEffectCoordinateConfig)
                    ),
                )
            )
            checkpointer = DescentCheckpointer(
                checkpoint_dir, every=checkpoint_every
            )
            ckpt = checkpointer.load(expect_fingerprint=fingerprint)
            if ckpt is not None:
                logger.info(
                    "resuming from checkpoint: grid %d, sweep %d",
                    ckpt.grid_index,
                    ckpt.iteration,
                )
                # the snapshot's leaves load as host arrays; the first
                # dispatch must see each coordinate's DECLARED placement
                # — a mesh sharding, or HOST numpy for streaming
                # coordinates — not pay an implicit reshard (which the
                # sanitizer flags and the AOT executables reject).
                # No-op for plain single-device coordinates.
                ckpt.states = self._place_states(ckpt.states, coordinates)
                if ckpt.best_states is not None:
                    ckpt.best_states = self._place_states(
                        ckpt.best_states, coordinates
                    )

        results = []
        states = init_states
        for gi in range(self._grid_length()):
            if ckpt is not None and gi < ckpt.grid_index:
                # completed in a previous run; its model was flushed via
                # grid_callback then. The checkpointed states carry the
                # warm start forward.
                results.append(None)
                states = ckpt.states if gi == ckpt.grid_index - 1 else states
                continue
            t_grid = time.perf_counter()
            coords_gi = {}
            reg_weights = {}
            for cid, coord in coordinates.items():
                ws = self.coordinate_configs[cid].regularization_weights
                w = ws[min(gi, len(ws) - 1)]
                reg_weights[cid] = w
                coords_gi[cid] = (
                    coord.with_regularization_weight(w) if gi > 0 else coord
                )

            start_iteration = 0
            initial_best = None
            if ckpt is not None and gi == ckpt.grid_index and ckpt.iteration >= 0:
                states = ckpt.states
                start_iteration = ckpt.iteration + 1
                if ckpt.best_states is not None:
                    initial_best = (ckpt.best_states, ckpt.best_metric)
            sweep_callback = None
            if checkpointer is not None:
                sweep_callback = (
                    lambda it, st, bs, bm, _gi=gi: checkpointer.on_sweep(
                        _gi, it, st, bs, bm, fingerprint=fingerprint
                    )
                )

            sweep_hook = None
            if self.events is not None:
                # stateless per-sweep notification (no donation copies,
                # game/descent.py): library listeners see sweep progress
                sweep_hook = (
                    lambda it, row, _gi=gi: self.events.emit(
                        "sweep_complete",
                        grid_index=_gi,
                        iteration=it,
                        sweep_seconds=row["sweep_seconds"],
                        dispatches=row["dispatches"],
                        compiles=row["compiles"],
                        health=row.get("health"),
                    )
                )

            obs.flight.record("grid", grid_index=gi)
            with compile_watch.watch() as grid_compiles, obs.span(
                "fit.grid", grid_index=gi
            ):
                cd = run_coordinate_descent(
                    coords_gi,
                    self.update_sequence,
                    self.descent_iterations,
                    initial_states=states,
                    locked_coordinates=self.locked_coordinates,
                    validation_fn=validation_fn,
                    larger_is_better=(
                        self.validation_evaluator.larger_is_better
                        if self.validation_evaluator
                        else True
                    ),
                    start_iteration=start_iteration,
                    initial_best=initial_best,
                    sweep_callback=sweep_callback,
                    sweep_hook=sweep_hook,
                    tracker_granularity=self.tracker_granularity,
                    on_divergence=self.on_divergence,
                )
            final_states = (
                cd.best_states if cd.best_states is not None else cd.states
            )
            model = self._to_model(coords_gi, final_states)
            if initial_model is not None:
                model = _carry_over_prior_models(model, initial_model)
            result = GameTrainingResult(
                model=model,
                evaluation=cd.best_metric,
                regularization_weights=reg_weights,
                tracker=cd.tracker,
                wall_time_s=time.perf_counter() - t_grid,
                compile_stats={
                    **grid_compiles,
                    # the parallel-precompile bill was paid once, before
                    # grid 0 — later grid points reuse its executables
                    "precompile": precompile_report if gi == 0 else None,
                },
            )
            results.append(result)
            if grid_callback is not None:
                grid_callback(gi, result)
            states = cd.states  # warm start the next grid point
            if checkpointer is not None:
                checkpointer.mark_grid_done(gi, states, fingerprint)

        # per-sweep device-time breakdown (obs/fleet.py): join this
        # fit's OWN sweep executables (SPMD comm census + XLA cost
        # flops) with the measured sweep/barrier walls of the LAST
        # trained grid point — published as device.* gauges and the
        # breakdown artifact. Host-side pricing only, after training;
        # guarded so attribution can never fail a fit. Resumed grids
        # hold None placeholders for points completed in a previous
        # life — price the last one THIS call actually swept.
        done = [r for r in results if r is not None]
        if done:
            obs.fleet.publish_device_breakdown(
                coordinates, done[-1].tracker
            )

        return results

    # ------------------------------------------------------------------

    def _arm_stream_guard(self, coordinates, stream_cfg):
        """Arm the bounded-residency assertion for a streaming fit: the
        shared StreamTelemetry gets a ResidencyGuard whose limit is the
        ISSUE's structural bound — ``2 × chunk_bytes + tables`` (tables
        = the FE coefficient/normalization vectors that legitimately
        stay device-resident across a score stream; RE tables are
        host-resident in streaming so they contribute ZERO device
        bytes) plus allocator slack. Every chunk placement samples live
        device bytes against it and raises ResidencyError on breach."""
        from photon_tpu.game.streaming import (
            StreamingFixedEffectCoordinate,
            StreamingRandomEffectCoordinate,
        )
        from photon_tpu.obs import memory as obs_memory

        telemetry = None
        chunk_bytes = 0
        table_bytes = 0
        for coord in coordinates.values():
            if isinstance(
                coord,
                (
                    StreamingFixedEffectCoordinate,
                    StreamingRandomEffectCoordinate,
                ),
            ):
                telemetry = coord.telemetry
                chunk_bytes = max(chunk_bytes, coord.max_chunk_device_bytes())
            if isinstance(coord, StreamingFixedEffectCoordinate):
                # state + factors + shifts ride on device for the whole
                # score stream — the "tables" term of the bound
                itemsize = int(jnp.dtype(coord.dtype).itemsize)
                table_bytes += 3 * coord.num_features * itemsize
        if telemetry is None:
            return None
        if stream_cfg.assert_residency:
            limit = (
                2 * chunk_bytes + table_bytes
                + stream_cfg.residency_slack_bytes
            )
            telemetry.guard = obs_memory.ResidencyGuard(
                limit, label="train.stream"
            )
            logger.info(
                "streaming residency guard armed: limit %d B "
                "(2 x %d chunk + %d tables + %d slack) over a %d B "
                "baseline",
                limit, chunk_bytes, table_bytes,
                stream_cfg.residency_slack_bytes,
                telemetry.guard.baseline_bytes,
            )
        return telemetry

    def _to_model(self, coordinates, states) -> GameModel:
        # Include every coordinate with a state — locked coordinates outside
        # the update sequence still contribute scores during descent and
        # must ship with the model (reference partialRetrainLockedCoordinates).
        ordered = list(self.update_sequence) + [
            cid for cid in coordinates if cid not in self.update_sequence
        ]
        return GameModel(
            coordinates={
                cid: coordinates[cid].to_model(states[cid])
                for cid in ordered
                if cid in states
            },
            task=self.task,
        )

    def _place_states(self, states: dict, coordinates) -> dict:
        """Route every coordinate's loaded state through its declared
        sharding (``Coordinate.place_state`` — explicit device_put, a
        no-op off-mesh). One site for checkpoint resume AND warm starts,
        so neither path can hand the meshed sweep a single-device
        array."""
        return {
            cid: (
                coordinates[cid].place_state(st) if cid in coordinates else st
            )
            for cid, st in states.items()
        }

    def _states_from_model(self, model: GameModel, coordinates, re_datasets):
        """Warm-start / partial-retrain states from a prior GameModel
        (reference initialModel + partialRetrainLockedCoordinates)."""
        states = {}
        for cid, coord in coordinates.items():
            if cid not in model.coordinates:
                continue
            prior = model.coordinates[cid]
            if isinstance(coord, FixedEffectCoordinate):
                w = jnp.asarray(
                    prior.model.coefficients.means, dtype=self.dtype
                )
                states[cid] = coord.normalization.model_to_transformed_space(w)
            elif isinstance(coord, RandomEffectCoordinate):
                lookup = prior.dense_coefficient_lookup()
                prior_idx = {k: i for i, k in enumerate(prior.vocab)}
                bucket_states = []
                # device_buckets carry the authoritative (possibly mesh-
                # padded) shapes; streaming coordinates hold NO device
                # buckets, so their shapes come from the host dataset
                shapes = (
                    [
                        (db.features.shape[0], db.features.shape[2])
                        for db in coord.device_buckets
                    ]
                    if coord.device_buckets
                    else [
                        (b.num_entities, b.projected_dim)
                        for b in coord.dataset.buckets
                    ]
                )
                for (e, d), host_bucket in zip(
                    shapes, coord.dataset.buckets
                ):
                    w0 = np.zeros((e, d), dtype=np.float32)
                    for i, ent in enumerate(host_bucket.entity_ids):
                        pi = prior_idx.get(coord.dataset.vocab[ent])
                        vec = lookup[pi] if pi is not None else None
                        if vec is None:
                            continue
                        cols = host_bucket.col_index[i]
                        valid = cols >= 0
                        w0[i][valid] = vec[cols[valid]]
                    bucket_states.append(jnp.asarray(w0, dtype=self.dtype))
                states[cid] = bucket_states
            elif isinstance(coord, MatrixFactorizationCoordinate):
                u0, v0 = coord.initial_state()
                u0, v0 = np.array(u0), np.array(v0)  # writable copies
                r_prior = {k: i for i, k in enumerate(prior.row_vocab)}
                c_prior = {k: i for i, k in enumerate(prior.col_vocab)}
                k_common = min(u0.shape[1], prior.row_factors.shape[1])
                for i, key in enumerate(coord.row_vocab):
                    pi = r_prior.get(key)
                    if pi is not None:
                        u0[i, :k_common] = prior.row_factors[pi, :k_common]
                for i, key in enumerate(coord.col_vocab):
                    pi = c_prior.get(key)
                    if pi is not None:
                        v0[i, :k_common] = prior.col_factors[pi, :k_common]
                states[cid] = (
                    jnp.asarray(u0, dtype=self.dtype),
                    jnp.asarray(v0, dtype=self.dtype),
                )
        return states
