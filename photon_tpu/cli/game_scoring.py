"""GAME scoring driver (reference cli/game/scoring/GameScoringDriver.scala:
load a saved GAME model, score a dataset, optionally evaluate, write
ScoringResultAvro part files).

Scoring streams by default: avro part files decode in bounded chunks on
a producer thread, each chunk runs through the fused device scorer
(``game/scoring.GameScorer`` — one program per batch shape, zero
steady-state retraces), and finished batches land round-robin in
sharded ``part-NNNNN.avro`` outputs (score columns buffered, each shard
flushed through the C++ block writer at close). Host memory holds a
constant number of decoded feature chunks (two staged on the producer
side + two in flight in the consumer), never the dataset. Knobs:
``--score-batch-rows`` / ``PHOTON_SCORE_BATCH_ROWS``,
``--num-output-partitions`` / ``PHOTON_SCORE_PARTITIONS``,
``--monolithic-scoring`` forces the legacy materialize-everything path
(also the automatic fallback for model layouts the fused program cannot
express).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from photon_tpu.cli import game_base
from photon_tpu.game.transformer import GameTransformer
from photon_tpu.io.model_io import (
    ShardedScoringWriter,
    load_game_model,
    save_scoring_results,
)
from photon_tpu.util import EventEmitter, PhotonLogger, Timed, prepare_output_dir
from photon_tpu.util.compile_cache import enable_persistent_cache

SCORES_DIR = "scores"


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="game-scoring", description=__doc__)
    game_base.add_common_arguments(p)
    p.add_argument(
        "--model-input-directory",
        required=True,
        help="directory written by the training driver (best/ or models/<i>/)",
    )
    p.add_argument("--model-id", default="", help="tag written to every record")
    p.add_argument(
        "--log-data-and-model-stats",
        action="store_true",
        help="log per-coordinate model summaries before scoring",
    )
    p.add_argument(
        "--score-batch-rows",
        type=int,
        default=None,
        help="rows per streaming score batch (default 8192; env "
        "PHOTON_SCORE_BATCH_ROWS overrides)",
    )
    p.add_argument(
        "--num-output-partitions",
        type=int,
        default=None,
        help="score output part files, filled round-robin per batch "
        "(default 1; env PHOTON_SCORE_PARTITIONS overrides)",
    )
    p.add_argument(
        "--monolithic-scoring",
        action="store_true",
        help="materialize the full dataset and score it in one host pass "
        "(the pre-streaming path; also the automatic fallback for model "
        "layouts the fused scorer cannot express)",
    )
    p.add_argument(
        "--degrade-on-stream-failure",
        action="store_true",
        help="opt-in resilience escape: when the streaming pipeline "
        "fails (repeated chunk decode failures past their retries, a "
        "dead/hung producer), fall back to the monolithic path instead "
        "of failing the run (env PHOTON_SCORE_DEGRADE=1). Off by "
        "default: degrading trades bounded host memory for completion, "
        "which must be an operator decision",
    )
    return p


def _degrade_enabled(args) -> bool:
    env = os.environ.get("PHOTON_SCORE_DEGRADE", "").strip()
    if env and env not in ("0", "1"):
        # fail loudly: an operator who set =true believing the escape
        # was armed must not discover otherwise via a dead run
        raise ValueError(
            f"PHOTON_SCORE_DEGRADE must be 0 or 1, got {env!r}"
        )
    if env:
        return env == "1"
    return bool(args.degrade_on_stream_failure)


def _stream_degradable(exc: BaseException) -> bool:
    """Which streaming failures the opt-in escape may absorb: pipeline
    errors the monolithic path does not share (watchdog/producer death,
    exhausted chunk retries — I/O and transient-transport classes).
    Programming errors (shape/type/config) always propagate."""
    from photon_tpu.game.scoring import StreamError
    from photon_tpu.util.retry import is_transient, is_transient_io

    return (
        isinstance(exc, StreamError)
        or is_transient_io(exc)
        or is_transient(exc)
    )


def _run_evaluators(log, requested, scores, labels, weights, tag_cols) -> dict:
    """Evaluate on the finite-labeled subset. Scoring data may be
    partially labeled (the reference scores labeled and unlabeled rows
    alike); rows without a finite label are excluded from every metric —
    the same masking convention as weight-0 rows — and the exclusion is
    logged, instead of one missing label silently skipping ALL
    evaluators (the old ``np.all(isfinite)`` gate)."""
    from photon_tpu.evaluation.multi import GroupedEvaluatorSpec

    evaluations: dict = {}
    if not requested:
        return evaluations
    finite = np.isfinite(labels)
    n_excluded = int(len(labels) - finite.sum())
    if not finite.any():
        log.warning("scoring data has no finite labels; skipping evaluators")
        return evaluations
    if n_excluded:
        log.info(
            "evaluating on %d of %d rows (%d excluded for non-finite labels)",
            int(finite.sum()), len(labels), n_excluded,
        )
    import jax.numpy as jnp

    from photon_tpu.evaluation.evaluators import evaluate

    s_f, lab_f, w_f = scores[finite], labels[finite], weights[finite]
    s, lab, w = jnp.asarray(s_f), jnp.asarray(lab_f), jnp.asarray(w_f)
    # weight-0 rows are padding/masked by convention and excluded from
    # grouped metrics (plain evaluators mask via the weights)
    keep = w_f > 0
    for ev in requested:
        if isinstance(ev, GroupedEvaluatorSpec):
            ids = np.asarray(tag_cols[ev.id_tag])[finite]
            evaluations[ev.name] = float(
                ev.build()(s_f[keep], lab_f[keep], ids[keep])
            )
        else:
            evaluations[ev.name] = float(evaluate(ev, s, lab, w))
        log.info("%s = %.6f", ev.name, evaluations[ev.name])
    return evaluations


def _score_streaming(
    args, log, model, index_maps, shard_configs, id_tags, out_root,
    requested,
):
    """Streamed scoring: chunked decode → fused device scorer → sharded
    avro writers, with the label/weight/id-tag columns (cheap, O(N))
    accumulated only when evaluators will consume them. Returns None
    when the model layout needs the monolithic fallback."""
    from photon_tpu.cache import resolve_reader
    from photon_tpu.game.scoring import (
        UnsupportedModelLayout,
        score_batch_rows,
        score_output_partitions,
    )

    # knob validation happens BEFORE the layout fallback: a bad
    # --score-batch-rows / env value must raise, not silently demote the
    # run to the materialize-everything path
    batch_rows = score_batch_rows(args.score_batch_rows)
    partitions = score_output_partitions(args.num_output_partitions)
    try:
        scorer = GameTransformer(model=model, task=model.task).streaming_scorer(
            batch_rows=batch_rows
        )
    except UnsupportedModelLayout as e:
        log.warning("streaming scorer unavailable (%s); falling back to "
                    "the monolithic path", e)
        return None

    paths = game_base.resolve_input_paths(args)
    # the ingest front door: a fresh feature cache turns the producer
    # thread into mmap slice + H2D copy (zero avro decode); a miss in
    # 'use' mode streams avro and builds the cache through the same
    # single decode (photon_tpu/cache)
    resolved = resolve_reader(
        paths,
        shard_configs,
        index_maps=index_maps,
        id_tags=tuple(id_tags),
        mode=args.feature_cache,
    )
    if resolved.mode != "off":
        log.info("feature cache: %s", resolved.describe())
    chunks = resolved.iter_chunks(chunk_rows=batch_rows)
    writer = ShardedScoringWriter(
        os.path.join(out_root, SCORES_DIR),
        num_partitions=partitions,
        model_id=args.model_id,
    )
    accumulate = bool(requested)
    labels_acc, weights_acc = [], []
    tag_acc: dict[str, list] = {t: [] for t in id_tags}

    def on_batch(chunk, scores):
        writer.write_chunk(
            scores,
            labels=chunk.labels,
            weights=chunk.weights,
            uids=chunk.uids,
        )
        # evaluator columns are O(N) host memory (id tags are Python
        # object arrays); with no evaluators requested, keep the
        # bounded-memory promise and accumulate nothing
        if accumulate:
            labels_acc.append(chunk.labels)
            weights_acc.append(chunk.weights)
            for t in id_tags:
                tag_acc[t].append(np.asarray(chunk.id_tags[t]))

    with Timed("stream scores"):
        result = scorer.stream(chunks, on_batch=on_batch)
        n = writer.close()
    log.info(
        "streamed %d samples in %d batches of %d rows -> %d partition(s)",
        result.stats.samples, result.stats.batches, batch_rows, partitions,
    )
    columns = {
        "labels": (
            np.concatenate(labels_acc) if labels_acc else np.zeros(0)
        ),
        "weights": (
            np.concatenate(weights_acc) if weights_acc else np.zeros(0)
        ),
        "tags": {
            t: (np.concatenate(v) if v else np.zeros(0, dtype=object))
            for t, v in tag_acc.items()
        },
    }
    from photon_tpu.obs import slo

    tracker = slo.active()
    detail = {
        "mode": "streaming",
        "batchRows": batch_rows,
        "numOutputPartitions": partitions,
        "batches": result.stats.batches,
        "maxStagedChunks": result.stats.max_staged_chunks,
        "batchLatency": result.stats.latency_percentiles(),
        # the per-stage latency waterfall (p50/p90/p99 per pipeline
        # stage) + end-to-end percentiles incl. p99.9 — a slow run's
        # summary names decode-vs-H2D-vs-write, not a bare aggregate
        "stageLatency": result.stats.stage_percentiles(),
        "e2eLatency": result.stats.e2e_percentiles(),
        "slo": (
            None
            if tracker is None
            else {
                "spec": tracker.spec.render(),
                "violations": result.stats.deadline_violations,
                "violationsByStage": dict(
                    result.stats.violations_by_stage
                ),
            }
        ),
        "outputFiles": writer.paths(),
        "featureCache": resolved.describe(),
    }
    return result.scores, n, columns, detail


def run(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    enable_persistent_cache()
    # chaos: (re)install the PHOTON_FAULTS plan per driver run
    from photon_tpu.util import faults

    faults.install_from_env()

    shard_configs = game_base.parse_shard_configs(args)
    out_root = prepare_output_dir(
        args.root_output_directory, override=args.override_output_directory
    )
    emitter = EventEmitter()
    with game_base.run_profile(out_root), PhotonLogger(
        os.path.join(out_root, "driver.log"), level=args.log_level
    ) as log:
        emitter.emit("setup", application=args.application_name)

        # Feature maps must come from the stores / the model's own vocabulary,
        # not the scoring data — otherwise indices won't line up.
        index_maps = game_base.prepare_feature_maps(args, shard_configs)
        with Timed("load model"):
            if index_maps is None:
                from photon_tpu.io.model_io import read_model_feature_keys
                index_maps = read_model_feature_keys(
                    args.model_input_directory, shard_configs
                )
            model = load_game_model(args.model_input_directory, index_maps)
        if args.log_data_and_model_stats:
            for cid, cm in model.coordinates.items():
                log.info("coordinate %s: %s", cid, type(cm).__name__)

        from photon_tpu.evaluation.multi import GroupedEvaluatorSpec

        requested = game_base.evaluators_from_args(args)
        evaluator_tags = {
            ev.id_tag
            for ev in requested
            if isinstance(ev, GroupedEvaluatorSpec)
        }
        id_tags = sorted(model.required_id_tags() | evaluator_tags)

        if args.monolithic_scoring:
            streamed = None
        else:
            # knob validated BEFORE streaming: a bad PHOTON_SCORE_DEGRADE
            # value must raise up front, not only on the failure path
            degrade = _degrade_enabled(args)
            try:
                streamed = _score_streaming(
                    args, log, model, index_maps, shard_configs, id_tags,
                    out_root, requested,
                )
            except Exception as e:
                # opt-in degrade-to-monolithic escape: a stream-only
                # failure (dead producer, exhausted chunk retries) falls
                # back to the materialize-everything path instead of
                # failing the run — logged loudly, never silent
                if not (degrade and _stream_degradable(e)):
                    raise
                from photon_tpu import obs

                obs.counter("score.stream_degraded")
                obs.instant(
                    "score.stream_degraded",
                    cat="lifecycle",
                    error=f"{type(e).__name__}: {e}",
                )
                log.warning(
                    "streaming scoring failed (%s: %s); degrading to the "
                    "monolithic path (--degrade-on-stream-failure)",
                    type(e).__name__, e,
                )
                # drop any partial streamed shards: the monolithic
                # fallback writes part-00000.avro into the same
                # directory, and a stale streamed part-0000N.avro
                # holding a subset of rows would double-count for any
                # consumer globbing part-*.avro
                import shutil

                shutil.rmtree(
                    os.path.join(out_root, SCORES_DIR), ignore_errors=True
                )
                streamed = None
        if streamed is not None:
            scores, n, columns, score_detail = streamed
            log.info("scored %d samples (streaming)", n)
        else:
            with Timed("read scoring data"):
                paths = game_base.resolve_input_paths(args)
                data, _ = game_base.read_game_data(
                    paths, shard_configs, index_maps, id_tags,
                    cache=args.feature_cache,
                )
            log.info("scoring %d samples (monolithic)", data.num_samples)
            transformer = GameTransformer(model=model, task=model.task)
            with Timed("score"):
                scores = np.asarray(transformer.score(data))
            with Timed("save scores"):
                n = save_scoring_results(
                    os.path.join(out_root, SCORES_DIR, "part-00000.avro"),
                    scores,
                    model_id=args.model_id,
                    labels=data.labels,
                    weights=data.weights,
                    uids=data.uids,
                )
            columns = {
                "labels": data.labels,
                "weights": data.weights,
                "tags": {t: data.id_tags[t] for t in id_tags},
            }
            score_detail = {"mode": "monolithic"}

        evaluations = _run_evaluators(
            log, requested, scores,
            np.asarray(columns["labels"], dtype=np.float64),
            np.asarray(columns["weights"], dtype=np.float64),
            columns["tags"],
        )
        with open(os.path.join(out_root, "scoring-summary.json"), "w") as f:
            json.dump(
                {
                    "numScored": n,
                    "evaluations": evaluations,
                    "scoring": score_detail,
                },
                f,
                indent=2,
            )
        game_base.export_run_profile(
            out_root, log, meta={"driver": "game_scoring"}
        )
        emitter.emit("scoring_finish", num_scored=n)
    emitter.close()
    return {"scores": scores, "evaluations": evaluations, "output": out_root}


def main() -> None:
    run(sys.argv[1:])


if __name__ == "__main__":
    main()
