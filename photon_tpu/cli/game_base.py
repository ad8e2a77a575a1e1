"""Shared GAME driver plumbing (reference cli/game/GameDriver.scala):
common CLI parameters, feature-map preparation (off-heap store vs generated),
and date-ranged input resolution."""
from __future__ import annotations

import argparse
import contextlib
import os

from photon_tpu.cli.parsing import (
    parse_evaluators,
    parse_feature_shard_config,
)
from photon_tpu.data.index_map import IndexMap
from photon_tpu.data.native_index import load_partitioned_store
from photon_tpu.game.data import GameData
from photon_tpu.io.data_reader import AvroDataReader, FeatureShardConfig
from photon_tpu.util import DateRange, DaysRange, resolve_date_range_paths


def add_common_arguments(p: argparse.ArgumentParser) -> None:
    """Arguments shared by the training and scoring drivers
    (reference GameDriver.scala:56-130)."""
    p.add_argument(
        "--input-data-directories",
        required=True,
        help="comma-separated input dirs of Avro part files",
    )
    p.add_argument(
        "--input-data-date-range",
        default=None,
        help="yyyyMMdd-yyyyMMdd window of daily partitions under each input dir",
    )
    p.add_argument(
        "--input-data-days-range",
        default=None,
        help="start-end in days ago, resolved against today",
    )
    p.add_argument(
        "--feature-shard-configurations",
        action="append",
        required=True,
        metavar="name=<shard>,feature.bags=<bag1|bag2>[,intercept=<bool>]",
        help="repeatable; one feature shard definition per instance",
    )
    p.add_argument(
        "--off-heap-index-map-dir",
        default=None,
        help="directory of native index stores built by feature_indexing",
    )
    p.add_argument("--evaluators", default=None, help="comma-separated evaluator types")
    p.add_argument(
        "--feature-cache",
        default=None,
        choices=["off", "use", "require", "rebuild"],
        help="packed columnar feature cache (photon_tpu/cache): 'use' "
        "replays a fresh cache (and builds one on a miss), 'require' "
        "refuses to decode avro (scripts/cache_tool.py builds/verifies "
        "caches), 'rebuild' forces a fresh build; env "
        "PHOTON_FEATURE_CACHE overrides (default off)",
    )
    p.add_argument(
        "--root-output-directory", required=True, help="driver output root"
    )
    p.add_argument(
        "--override-output-directory",
        action="store_true",
        help="replace an existing output directory",
    )
    p.add_argument("--log-level", default="info")
    p.add_argument("--application-name", default="photon-tpu")


def parse_shard_configs(args) -> dict[str, FeatureShardConfig]:
    configs = {}
    for s in args.feature_shard_configurations:
        name, cfg = parse_feature_shard_config(s)
        if name in configs:
            raise ValueError(f"duplicate feature shard {name!r}")
        configs[name] = cfg
    return configs


def resolve_input_paths(args) -> list[str]:
    """Input dirs, optionally expanded to daily partitions in a date range."""
    roots = [p.strip() for p in args.input_data_directories.split(",") if p.strip()]
    date_range = None
    if args.input_data_date_range:
        date_range = DateRange.parse(args.input_data_date_range)
    elif args.input_data_days_range:
        date_range = DaysRange.parse(args.input_data_days_range).to_date_range()
    if date_range is None:
        return roots
    paths: list[str] = []
    for root in roots:
        paths.extend(resolve_date_range_paths(root, date_range))
    return paths


def prepare_feature_maps(
    args, shard_configs: dict[str, FeatureShardConfig]
) -> dict[str, IndexMap] | None:
    """Off-heap native stores when configured, else None (the reader
    generates in-memory maps from the data — reference prepareFeatureMaps'
    PalDB vs DefaultIndexMap split)."""
    if not args.off_heap_index_map_dir:
        return None
    return {
        shard: load_partitioned_store(args.off_heap_index_map_dir, shard)
        for shard in shard_configs
    }


def read_game_data(
    paths,
    shard_configs: dict[str, FeatureShardConfig],
    index_maps: dict[str, IndexMap] | None,
    id_tags=(),
    cache: str | None = None,
) -> tuple[GameData, dict[str, IndexMap]]:
    """One materialized GameData through the ingest front door
    (photon_tpu/cache): ``cache`` is the ``--feature-cache`` mode (env
    ``PHOTON_FEATURE_CACHE`` wins; default off = the plain avro read)."""
    from photon_tpu.cache import resolve_reader

    resolved = resolve_reader(
        paths,
        shard_configs,
        index_maps=index_maps,
        id_tags=tuple(id_tags),
        mode=cache,
    )
    data = resolved.read()
    return data, resolved.index_maps


def evaluators_from_args(args):
    return parse_evaluators(args.evaluators) if args.evaluators else []


@contextlib.contextmanager
def run_profile(out_root=None):
    """Telemetry session for one driver run: enable the spine
    (photon_tpu/obs) from a clean slate on entry, and ALWAYS disable and
    drop the recorded spans on exit — success or failure — so a
    long-lived process embedding a driver never keeps profiling (and
    accumulating spans for) unrelated work after the run. Drivers
    profile by default — the measured overhead is <2% of a steady sweep
    (PERF.md r7) and the artifacts are what make a slow run debuggable
    after the fact. Artifacts must be exported inside the session
    (``export_run_profile``).

    ``out_root`` additionally arms the LIVE telemetry plane under
    ``<out_root>/obs/``: first, any stale flight ring a DEAD previous
    run left behind (a real SIGKILL mid-fit) is reconstructed into a
    ``blackbox-<seq>.json`` so the relaunch reports what the dead
    process was doing; then the mmap flight recorder + crash handlers,
    the series flusher (``PHOTON_OBS_FLUSH_S``), and the opt-in HTTP
    endpoints (``PHOTON_OBS_HTTP_PORT``) run for the session, all torn
    down in the ``finally``. A run that FAILS exports best-effort
    partial artifacts (``partial.metrics.json`` + summary + manifest)
    and a blackbox dump before the exception propagates — a crashed run
    is no longer telemetry-free.

    ``PHOTON_OBS=0`` opts the driver out of MANAGING the pipeline
    entirely: nothing is enabled on entry and — just as important —
    nothing is disabled or dropped on exit, so an embedding process
    that runs its own library-level telemetry (``obs.enable()``) keeps
    its state and its accumulated spans across a driver call."""
    from photon_tpu import obs

    if os.environ.get("PHOTON_OBS", "").strip() == "0":
        yield
        return
    obs.enable()
    obs.reset()
    plane = None
    try:
        if out_root is not None:
            # fleet-aware namespacing (photon_tpu/obs/fleet.py):
            # <out_root>/obs for a single process (historical layout,
            # unchanged), <out_root>/obs/p<k> for process k of a
            # jax.distributed run — N workers sharing one output root
            # no longer clobber each other's ring/series/artifacts
            plane = obs.live_plane(obs.fleet.obs_dir(out_root))
        try:
            yield
        except BaseException as e:
            _export_failure_artifacts(out_root, e)
            raise
    finally:
        if plane is not None:
            plane.close()
        obs.disable()
        obs.reset()


def _export_failure_artifacts(out_root, exc: BaseException) -> None:
    """The failed-run telemetry flush: blackbox dump + best-effort
    partial metrics/summary/manifest under ``<out_root>/obs/``. Every
    step is guarded — telemetry must never mask the real failure."""
    from photon_tpu import obs

    if out_root is None or not obs.enabled():
        return
    reason = f"{type(exc).__name__}: {exc}"
    try:
        obs.flight.dump_blackbox(reason=reason)
    except Exception:  # pragma: no cover - dump_blackbox already guards
        pass
    try:
        obs.export_partial_artifacts(
            obs.fleet.obs_dir(out_root),
            meta={"failed": True, "error": reason},
        )
    except Exception:  # pragma: no cover - exporter already guards
        pass


def export_run_profile(out_root, log=None, meta=None) -> dict | None:
    """Write this run's telemetry artifacts under ``<out_root>/obs/``:
    Chrome trace-event JSON (open at https://ui.perfetto.dev or
    chrome://tracing), the metrics snapshot, the JSONL run manifest, and
    the human-readable per-phase summary. No-op (returns None) when
    telemetry is disabled.

    Call inside a :func:`run_profile` session (which owns the
    enable/disable lifecycle — including the failure path, where no
    artifacts are written but telemetry still shuts off)."""
    from photon_tpu import obs

    if not obs.enabled():
        return None
    paths = obs.export_artifacts(
        obs.fleet.obs_dir(out_root), meta=meta
    )
    if log is not None:
        log.info("run profile:\n%s", obs.summary_table())
        log.info("telemetry artifacts: %s", paths)
    fleet_path = export_fleet_report(log)
    if fleet_path is not None:
        paths["fleet_report"] = fleet_path
    return paths


def export_fleet_report(log=None) -> str | None:
    """Process 0 of a fleet run writes the offline fleet document
    (worker heartbeat table, merged registry, per-sweep skew rows,
    stragglers — photon_tpu/obs/fleet.py) as ``fleet_report.json`` at
    the shared obs root. No-op (None) single-process, on workers k>0,
    or when no publisher is armed; guarded — the report must never fail
    the run it describes."""
    import json

    from photon_tpu import obs

    pub = obs.fleet.get_publisher()
    if pub is None or pub.info.index != 0:
        return None
    try:
        doc = obs.fleet.fleet_report(pub.fleet_root)
        path = os.path.join(pub.fleet_root, "fleet_report.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=2, default=str, sort_keys=True)
    except Exception as e:  # pragma: no cover - defensive
        import logging

        logging.getLogger(__name__).warning(
            "fleet report export failed: %s: %s", type(e).__name__, e
        )
        return None
    if log is not None:
        workers = doc.get("workers", [])
        bad = [w for w in workers if w.get("status") != "ok"]
        log.info(
            "fleet report: %d workers (%d not ok), %d skew rows, "
            "%d straggler flags -> %s",
            len(workers), len(bad), len(doc.get("skew", [])),
            len(doc.get("stragglers", [])), path,
        )
    return path
