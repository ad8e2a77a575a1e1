"""GAME training driver (reference cli/game/training/GameTrainingDriver.scala).

Pipeline (reference ``run`` :335-474): read Avro → feature maps → data
validation → per-shard stats + normalization contexts → GameEstimator.fit
over the λ grid (warm-started) → optional hyperparameter tuning → model
selection → save model(s).

Usage:
    python -m photon_tpu.cli.game_training \
      --input-data-directories /data/train \
      --root-output-directory /out \
      --training-task LOGISTIC_REGRESSION \
      --feature-shard-configurations name=global,feature.bags=features \
      --coordinate-configurations name=global,feature.shard=global,optimizer=LBFGS,regularization=L2,reg.weights=1|10 \
      --coordinate-update-sequence global \
      --coordinate-descent-iterations 1
"""
from __future__ import annotations

import argparse
import dataclasses
import enum
import json
import os
import sys

import numpy as np

from photon_tpu.cli import game_base
from photon_tpu.cli.parsing import parse_coordinate_config
from photon_tpu.data.stats import BasicStatisticalSummary
from photon_tpu.data.validators import DataValidationType, validate_game_data
from photon_tpu.evaluation.evaluators import EvaluatorType
from photon_tpu.game.estimator import GameEstimator, GameTrainingResult
from photon_tpu.game.tuning import run_hyperparameter_tuning
from photon_tpu.io.model_io import save_game_model
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.types import NormalizationType, TaskType
from photon_tpu.util import EventEmitter, PhotonLogger, Timed, prepare_output_dir
from photon_tpu.util.compile_cache import enable_persistent_cache

MODELS_DIR = "models"
BEST_MODEL_DIR = "best"
SUMMARY_FILE = "training-summary.json"


class ModelOutputMode(enum.Enum):
    """Which trained models to persist (reference ModelOutputMode.scala)."""

    NONE = "NONE"
    BEST = "BEST"
    ALL = "ALL"


class HyperparameterTuningMode(enum.Enum):
    NONE = "NONE"
    RANDOM = "RANDOM"
    BAYESIAN = "BAYESIAN"


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="game-training",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    game_base.add_common_arguments(p)
    p.add_argument(
        "--training-task",
        required=True,
        choices=[t.name for t in TaskType],
    )
    p.add_argument("--validation-data-directories", default=None)
    p.add_argument("--validation-data-date-range", default=None)
    p.add_argument(
        "--coordinate-configurations",
        action="append",
        required=True,
        metavar="name=<id>,feature.shard=<shard>,...",
        help="repeatable; one coordinate per instance (see cli/parsing.py)",
    )
    p.add_argument(
        "--coordinate-update-sequence",
        required=True,
        help="comma-separated coordinate ids, trained in order",
    )
    p.add_argument("--coordinate-descent-iterations", type=int, default=1)
    p.add_argument(
        "--normalization",
        default="NONE",
        choices=[t.name for t in NormalizationType],
    )
    p.add_argument("--data-summary-directory", default=None)
    p.add_argument(
        "--partial-retrain-locked-coordinates",
        default=None,
        help="comma-separated coordinate ids to keep fixed (requires --model-input-directory)",
    )
    p.add_argument("--model-input-directory", default=None)
    p.add_argument(
        "--ignore-threshold-for-new-models",
        action="store_true",
        help="warm start: entities WITHOUT a prior random-effect model "
        "bypass the active-data lower bound (requires "
        "--model-input-directory; reference GameEstimator.scala:127-133)",
    )
    p.add_argument(
        "--output-mode",
        default="BEST",
        choices=[m.name for m in ModelOutputMode],
    )
    p.add_argument(
        "--hyper-parameter-tuning",
        default="NONE",
        choices=[m.name for m in HyperparameterTuningMode],
    )
    p.add_argument("--hyper-parameter-tuning-iter", type=int, default=10)
    p.add_argument(
        "--hyper-parameter-prior-json",
        default=None,
        help="path to serialized prior observations from earlier jobs "
        "(reference HyperparameterSerialization format: {'records': [...]})",
    )
    p.add_argument(
        "--hyper-parameter-shrink-radius",
        type=float,
        default=None,
        help="contract the search box to ±radius (in [0,1] space) around "
        "the GP-predicted best prior point (reference ShrinkSearchRange)",
    )
    p.add_argument(
        "--hyper-parameter-save-observations",
        default=None,
        help="write this run's (weights, evaluation) observations as prior "
        "JSON for future jobs",
    )
    p.add_argument(
        "--mesh",
        default=None,
        metavar="DxE|N|auto",
        help="span the fit over a device mesh: 'DxE' (data x entity "
        "device factorization, e.g. 1x8), 'N' (N devices on the data "
        "axis), or 'auto' (every device on the data axis). Fixed-effect "
        "batches shard rows over the whole mesh, random-effect entity "
        "tables shard over the entity axis; checkpoints fingerprint the "
        "topology. env PHOTON_MESH overrides; default off "
        "(single-device)",
    )
    p.add_argument(
        "--precompile",
        action="store_true",
        help="AOT-compile the fused sweep/score programs on a thread pool "
        "before descent (independent compiles overlap instead of "
        "serializing inside the first sweep; pays off when the fit is "
        "compile-bound — cold caches)",
    )
    p.add_argument("--compute-variance", action="store_true")
    p.add_argument("--model-sparsity-threshold", type=float, default=1e-4)
    p.add_argument(
        "--data-validation",
        default="VALIDATE_FULL",
        choices=[t.name for t in DataValidationType],
    )
    p.add_argument(
        "--max-restarts",
        type=int,
        default=None,
        help="supervised auto-resume budget (game/recovery.py): restart "
        "a fit that fails with a transient (UNAVAILABLE-class) or "
        "divergent error up to this many times, resuming from the "
        "newest valid checkpoint when --checkpoint-sweeps is set; fatal "
        "errors never retry (default 0; env PHOTON_MAX_RESTARTS "
        "overrides)",
    )
    p.add_argument(
        "--stream-chunk-rows",
        type=int,
        default=None,
        metavar="ROWS",
        help="train OUT-OF-CORE: keep datasets host-resident and stream "
        "fixed-shape chunks of ~ROWS sample rows through the "
        "double-buffered sweep pipeline (game/streaming.py) — bounded "
        "device residency, bit-identical coefficients, zero steady-state "
        "compiles. Fixed-effect coordinates must be locked "
        "(--partial-retrain-locked-coordinates) or absent. env "
        "PHOTON_STREAM_CHUNK_ROWS overrides the value",
    )
    p.add_argument(
        "--warm-start-input-directory",
        default=None,
        help="model checkpoint directory (sequence-numbered snapshots, "
        "game/checkpoint.ModelCheckpointStore): warm-start the fit from "
        "the newest valid snapshot — the daily-retrain entry point. An "
        "empty or missing directory cold-starts with a warning (day "
        "zero). Mutually exclusive with --model-input-directory",
    )
    p.add_argument(
        "--model-checkpoint-directory",
        default=None,
        help="save the final trained model as the next sequence-numbered "
        "snapshot here after the fit completes (often the same directory "
        "as --warm-start-input-directory, closing the retrain loop)",
    )
    p.add_argument(
        "--checkpoint-sweeps",
        action="store_true",
        help="flush coordinate-descent state to <output>/checkpoints after "
        "every sweep; a rerun of the same command resumes from the last "
        "completed sweep with bit-identical results (requires "
        "--override-output-directory NOT set on the rerun; output mode ALL "
        "recommended so completed grid models are already on disk)",
    )
    return p


def _normalization_contexts(
    norm_type: NormalizationType, data, shard_configs, index_maps
) -> tuple[dict[str, NormalizationContext], dict[str, BasicStatisticalSummary]]:
    """Per-shard stats + normalization contexts (reference
    prepareNormalizationContextWrappers, GameEstimator.scala:698)."""
    from photon_tpu.data.index_map import INTERCEPT_KEY

    contexts: dict[str, NormalizationContext] = {}
    summaries: dict[str, BasicStatisticalSummary] = {}
    for shard in shard_configs:
        summary = BasicStatisticalSummary.of(data.shard_dataset(shard))
        summaries[shard] = summary
        icpt = index_maps[shard].get_index(INTERCEPT_KEY)
        contexts[shard] = NormalizationContext.build(
            norm_type,
            mean=summary.mean,
            variance=summary.variance,
            max_magnitude=np.maximum(np.abs(summary.max), np.abs(summary.min)),
            intercept_index=None if icpt < 0 else icpt,
        )
    return contexts, summaries


def _save_summary_stats(path, summaries, index_maps) -> None:
    """Feature stats output as FeatureSummarizationResultAvro records
    (reference ModelProcessingUtils.writeBasicStatistics:515-585: one
    record per feature with the (name, term) split and a metrics map keyed
    max/min/mean/normL1/normL2/numNonzeros/variance), one
    ``<shard>/part-00000.avro`` per feature shard."""
    from photon_tpu.data.index_map import INTERSECT
    from photon_tpu.io.avro import write_avro_file
    from photon_tpu.io.schemas import FEATURE_SUMMARIZATION_RESULT_AVRO

    for shard, s in summaries.items():
        imap = index_maps[shard]

        def records(imap=imap, s=s):  # bind: consumed inside this iteration
            for j in range(len(imap)):
                key = imap.get_feature_name(j)
                name, _, term = key.partition(INTERSECT)
                yield {
                    "featureName": name,
                    "featureTerm": term,
                    "metrics": {
                        "max": float(s.max[j]),
                        "min": float(s.min[j]),
                        "mean": float(s.mean[j]),
                        "normL1": float(s.norm_l1[j]),
                        "normL2": float(s.norm_l2[j]),
                        "numNonzeros": float(s.num_nonzeros[j]),
                        "variance": float(s.variance[j]),
                    },
                }

        shard_dir = os.path.join(path, shard)
        os.makedirs(shard_dir, exist_ok=True)
        write_avro_file(
            os.path.join(shard_dir, "part-00000.avro"),
            FEATURE_SUMMARIZATION_RESULT_AVRO,
            records(),
        )


def _restore_skipped_grid_results(
    results, grid_results_path, out_root, index_maps, log
):
    """Fill ``None`` placeholders left by a checkpoint resume for grid
    points completed in a previous (killed) run: evaluations come from the
    checkpoint's grid-results.jsonl sidecar, models reload from the ALL-
    mode flush directory when present."""
    from photon_tpu.io.model_io import load_game_model

    recorded = {}
    if grid_results_path and os.path.exists(grid_results_path):
        with open(grid_results_path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    # a line truncated by the very crash being recovered
                    # from must not kill the recovery path
                    continue
                recorded[row["grid_index"]] = row
    out = []
    for gi, r in enumerate(results):
        if r is not None:
            out.append(r)
            continue
        row = recorded.get(gi, {})
        model_dir = os.path.join(out_root, MODELS_DIR, str(gi))
        model = None
        if os.path.isdir(model_dir):
            model = load_game_model(model_dir, index_maps)
        else:
            log.warning(
                "resume: grid %d model not on disk (run with output mode "
                "ALL to keep completed models reloadable)",
                gi,
            )
        out.append(
            GameTrainingResult(
                model=model,
                evaluation=row.get("evaluation"),
                regularization_weights=row.get(
                    "regularization_weights", {}
                ),
                tracker=[],
                wall_time_s=row.get("wall_time_s", 0.0),
            )
        )
    return out


def _select_best(
    results: list[GameTrainingResult], evaluator: EvaluatorType | None
) -> int:
    """Index of the best model (reference selectBestModel :677-720): by
    validation metric when present, else the most-regularized (first)."""
    if evaluator is None or all(r.evaluation is None for r in results):
        return 0
    vals = [
        (r.evaluation if r.evaluation is not None else -np.inf)
        if evaluator.larger_is_better
        else (r.evaluation if r.evaluation is not None else np.inf)
        for r in results
    ]
    return int(np.argmax(vals) if evaluator.larger_is_better else np.argmin(vals))


def run(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    enable_persistent_cache()
    # chaos: (re)install the PHOTON_FAULTS plan per driver run — the
    # chaos drive (scripts/chaos_drive.py) controls faults through the
    # child environment; unset env clears any leftover plan
    from photon_tpu.util import faults

    faults.install_from_env()

    task = TaskType[args.training_task]
    shard_configs = game_base.parse_shard_configs(args)
    coordinate_configs = {}
    for s in args.coordinate_configurations:
        name, cfg = parse_coordinate_config(s, task)
        if name in coordinate_configs:
            raise ValueError(f"duplicate coordinate {name!r}")
        if args.compute_variance:
            from photon_tpu.optimize.problem import VarianceComputationType

            cfg = dataclasses.replace(
                cfg,
                optimization=dataclasses.replace(
                    cfg.optimization,
                    variance_computation=VarianceComputationType.FULL,
                ),
            )
        coordinate_configs[name] = cfg
    update_sequence = [
        c.strip() for c in args.coordinate_update_sequence.split(",") if c.strip()
    ]
    missing_shards = {
        c.feature_shard
        for c in coordinate_configs.values()
        if getattr(c, "feature_shard", None) is not None
    } - set(shard_configs)
    if missing_shards:
        raise ValueError(f"coordinates reference unknown shards {missing_shards}")
    locked = frozenset(
        c.strip()
        for c in (args.partial_retrain_locked_coordinates or "").split(",")
        if c.strip()
    )
    if locked and not args.model_input_directory:
        raise ValueError(
            "--partial-retrain-locked-coordinates requires --model-input-directory"
        )
    if args.ignore_threshold_for_new_models and not args.model_input_directory:
        raise ValueError(
            "--ignore-threshold-for-new-models requires --model-input-directory"
        )
    if args.warm_start_input_directory and args.model_input_directory:
        raise ValueError(
            "--warm-start-input-directory and --model-input-directory are "
            "mutually exclusive (both supply the initial model)"
        )
    from photon_tpu.evaluation.multi import GroupedEvaluatorSpec
    from photon_tpu.game.config import required_id_tags

    evaluators = game_base.evaluators_from_args(args)
    validation_evaluator = evaluators[0] if evaluators else None
    evaluator_tags = {
        ev.id_tag for ev in evaluators if isinstance(ev, GroupedEvaluatorSpec)
    }
    # the training read needs only coordinate tags; evaluator-only tags are
    # materialized on the (smaller) validation read alone
    id_tags = sorted(required_id_tags(coordinate_configs.values()))
    validation_id_tags = sorted(set(id_tags) | evaluator_tags)

    ckpt_dir = (
        os.path.join(args.root_output_directory, "checkpoints")
        if args.checkpoint_sweeps
        else None
    )
    if ckpt_dir is not None and ModelOutputMode[args.output_mode] != (
        ModelOutputMode.ALL
    ):
        # without the per-grid ALL-mode flush, a resume cannot reload
        # models completed before the kill — a dead end, so refuse early
        raise ValueError("--checkpoint-sweeps requires --output-mode ALL")
    from photon_tpu.game.checkpoint import MANIFEST as CKPT_MANIFEST

    resuming = (
        ckpt_dir is not None
        and os.path.exists(os.path.join(ckpt_dir, CKPT_MANIFEST))
        and not args.override_output_directory  # override = wipe + fresh run
    )
    if resuming:
        # a resume rerun reuses the existing output tree by definition
        out_root = args.root_output_directory
    else:
        out_root = prepare_output_dir(
            args.root_output_directory, override=args.override_output_directory
        )
    emitter = EventEmitter()
    with game_base.run_profile(out_root), PhotonLogger(
        os.path.join(out_root, "driver.log"), level=args.log_level
    ) as log:
        # driver-level boundary (fires even when the run fails before
        # fit); the estimator adds the PER-FIT lifecycle events on this
        # same bus (events=emitter below) — ``setup`` with coordinate
        # payloads, ``sweep_complete``, ``training_finish``. A run's
        # overall completion signal (post-tuning, models on disk) is
        # ``driver_finish``.
        emitter.emit("setup", application=args.application_name)

        with Timed("read training data"):
            paths = game_base.resolve_input_paths(args)
            index_maps = game_base.prepare_feature_maps(args, shard_configs)
            data, index_maps = game_base.read_game_data(
                paths, shard_configs, index_maps, id_tags,
                cache=args.feature_cache,
            )
        log.info(
            "read %d samples, shards %s",
            data.num_samples,
            {s: m.num_cols for s, m in data.feature_shards.items()},
        )

        validation_data = None
        if args.validation_data_directories:
            with Timed("read validation data"):
                v_args = argparse.Namespace(
                    input_data_directories=args.validation_data_directories,
                    input_data_date_range=args.validation_data_date_range,
                    input_data_days_range=None,
                )
                v_paths = game_base.resolve_input_paths(v_args)
                validation_data, _ = game_base.read_game_data(
                    v_paths, shard_configs, index_maps, validation_id_tags,
                    cache=args.feature_cache,
                )

        with Timed("data validation"):
            mode = DataValidationType[args.data_validation]
            validate_game_data(data, task, mode)
            if validation_data is not None:
                validate_game_data(validation_data, task, mode)

        norm_type = NormalizationType[args.normalization]
        contexts = None
        if norm_type != NormalizationType.NONE or args.data_summary_directory:
            with Timed("feature statistics"):
                contexts, summaries = _normalization_contexts(
                    norm_type, data, shard_configs, index_maps
                )
            if args.data_summary_directory:
                _save_summary_stats(
                    args.data_summary_directory, summaries, index_maps
                )
            if norm_type == NormalizationType.NONE:
                contexts = None

        initial_model = None
        if args.model_input_directory:
            from photon_tpu.io.model_io import load_game_model

            with Timed("load initial model"):
                initial_model = load_game_model(
                    args.model_input_directory, index_maps
                )

        from photon_tpu.parallel.mesh import resolve_mesh

        mesh = resolve_mesh(args.mesh)
        if mesh is not None:
            log.info(
                "training spans a %s device mesh (axes %s)",
                "x".join(str(s) for s in mesh.devices.shape),
                tuple(mesh.axis_names),
            )
        estimator = GameEstimator(
            task=task,
            coordinate_configs=coordinate_configs,
            update_sequence=update_sequence,
            descent_iterations=args.coordinate_descent_iterations,
            mesh=mesh,
            normalization_contexts=contexts,
            ignore_threshold_for_new_models=args.ignore_threshold_for_new_models,
            locked_coordinates=locked,
            validation_evaluator=validation_evaluator,
            precompile=args.precompile,
            # library-level lifecycle events (setup / sweep_complete /
            # training_finish / training_failure) ride the driver's bus
            events=emitter,
            # supervised auto-resume: transient/divergent failures
            # restart from the newest valid checkpoint (recovery.*
            # events on the same bus/obs spine)
            max_restarts=args.max_restarts,
        )

        emitter.emit("training_start", task=task.name)
        # flush each grid point's model as it completes (output mode ALL):
        # a crash mid-grid keeps every finished model on disk — the
        # checkpoint-based recovery story replacing Spark task retry
        grid_results_path = (
            os.path.join(ckpt_dir, "grid-results.jsonl") if ckpt_dir else None
        )
        flushed = set()
        save_all = ModelOutputMode[args.output_mode] == ModelOutputMode.ALL

        def grid_callback(gi, result):
            if save_all:
                save_game_model(
                    os.path.join(out_root, MODELS_DIR, str(gi)),
                    result.model,
                    index_maps,
                    optimization_configurations=result.regularization_weights,
                    sparsity_threshold=args.model_sparsity_threshold,
                )
                flushed.add(gi)
            if grid_results_path is not None:
                with open(grid_results_path, "a") as f:
                    f.write(
                        json.dumps(
                            {
                                "grid_index": gi,
                                "regularization_weights": result.regularization_weights,
                                "evaluation": result.evaluation,
                                "wall_time_s": result.wall_time_s,
                            }
                        )
                        + "\n"
                    )

        with Timed("train"):
            results = estimator.fit(
                data,
                validation_data=validation_data,
                initial_model=initial_model,
                grid_callback=grid_callback,
                checkpoint_dir=ckpt_dir,
                stream=args.stream_chunk_rows,
                warm_start=args.warm_start_input_directory,
                model_checkpoint_dir=args.model_checkpoint_directory,
            )
        # None placeholders appear on a cross-process resume AND after an
        # in-process supervised restart that re-entered the grid loop
        # past checkpointed grid points — restore from disk either way
        if any(r is None for r in results):
            results = _restore_skipped_grid_results(
                results, grid_results_path, out_root, index_maps, log
            )

        tuning_mode = HyperparameterTuningMode[args.hyper_parameter_tuning]
        if tuning_mode != HyperparameterTuningMode.NONE:
            if validation_data is None or validation_evaluator is None:
                raise ValueError(
                    "hyperparameter tuning requires validation data + an evaluator"
                )
            prior_json = None
            if args.hyper_parameter_prior_json:
                with open(args.hyper_parameter_prior_json) as f:
                    prior_json = f.read()
            with Timed("hyperparameter tuning"):
                tuned = run_hyperparameter_tuning(
                    estimator,
                    data,
                    validation_data,
                    num_iterations=args.hyper_parameter_tuning_iter,
                    mode=tuning_mode.name,
                    prior_json=prior_json,
                    shrink_radius=args.hyper_parameter_shrink_radius,
                )
            results = results + tuned
        if args.hyper_parameter_save_observations:
            # written for the plain λ-sweep too (mode NONE) — every model
            # with a validation evaluation is a usable prior
            from photon_tpu.hyperparameter.serialization import priors_to_json

            observations = [
                (r.regularization_weights, float(r.evaluation))
                for r in results
                if r.evaluation is not None
            ]
            with open(args.hyper_parameter_save_observations, "w") as f:
                f.write(priors_to_json(observations))

        best = _select_best(results, validation_evaluator)
        log.info(
            "trained %d models; best #%d (metric=%s)",
            len(results),
            best,
            results[best].evaluation,
        )

        output_mode = ModelOutputMode[args.output_mode]
        opt_summary = [
            {
                "regularizationWeights": r.regularization_weights,
                "evaluation": r.evaluation,
                "wallTimeS": r.wall_time_s,
            }
            for r in results
        ]
        if output_mode != ModelOutputMode.NONE:
            with Timed("save models"):
                if output_mode == ModelOutputMode.ALL:
                    for i, r in enumerate(results):
                        if i in flushed:  # already written by grid_callback
                            continue
                        if r.model is None or os.path.isdir(
                            os.path.join(out_root, MODELS_DIR, str(i))
                        ):
                            continue  # restored entry, written by prior run
                        save_game_model(
                            os.path.join(out_root, MODELS_DIR, str(i)),
                            r.model,
                            index_maps,
                            optimization_configurations=r.regularization_weights,
                            sparsity_threshold=args.model_sparsity_threshold,
                        )
                if results[best].model is None:
                    raise RuntimeError(
                        f"best model (grid {best}) was trained by a previous "
                        "killed run but is not on disk; rerun checkpointed "
                        "jobs with --output-mode ALL"
                    )
                save_game_model(
                    os.path.join(out_root, BEST_MODEL_DIR),
                    results[best].model,
                    index_maps,
                    optimization_configurations=results[best].regularization_weights,
                    sparsity_threshold=args.model_sparsity_threshold,
                )
        with open(os.path.join(out_root, SUMMARY_FILE), "w") as f:
            json.dump(
                {"models": opt_summary, "best": best, "task": task.name}, f, indent=2
            )
        game_base.export_run_profile(
            out_root, log, meta={"driver": "game_training"}
        )
        # overall run completion: includes tuned models, unlike the
        # estimator's per-fit training_finish
        emitter.emit("driver_finish", num_models=len(results))
    emitter.close()
    return {"results": results, "best": best, "output": out_root}


def main() -> None:
    run(sys.argv[1:])


if __name__ == "__main__":
    main()
