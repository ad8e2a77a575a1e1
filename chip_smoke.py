#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that photon-tpu still starts on the chip.

One process, one TPU chip, through the drivers a user calls:

    generate  a CTR-shaped GLMix deployment from ``--seed`` at the widths of
              bench config ``game_ctr_scale`` (logistic loss; sparse fixed
              effect d = 2^17 at 24 nnz/row incl. intercept; per-user and
              per-item random effects of d = 16; Zipf-skewed entities),
              written as avro part files by the pure-Python writer
    train     photon_tpu.cli.game_training.run — FE + per-user + per-item,
              two descent sweeps, --precompile
    score     photon_tpu.cli.game_scoring.run — streaming over the held-out
              part files, compared with a plain NumPy float64 scoring of the
              SAVED model files on the generated rows
    serve     photon_tpu.cli.game_serving.run — a spool of request envelopes
              staged by a thread, answers compared with the score phase

Exits non-zero (and prints no result line) when JAX finds no TPU, when a
phase raises, or when an assertion fails. Each phase prints one JSON line;
the LAST line of stdout is the contract line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips 4`` runs ONLY the four-chip phase: the same data at a quarter of
the rows and entities, trained twice through ``game_training.run``
(``--mesh 1x4`` and unmeshed) and compared; its last line reports
``"count": 4``.

The compile cache is the one ``photon_tpu.util.compile_cache`` picks:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import functools
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

# -- the deployment's widths (bench.py config_game_ctr_scale) ---------------
FE_DIM = 1 << 17  # fixed-effect columns, intercept included
FE_NNZ = 24  # non-zeros per row, intercept included
RE_DIM = 16  # per-user and per-item random-effect width
# -- its scale: what the pure-Python avro writer allows (ISSUE 26) ----------
ROWS = 1 << 18
USERS = 1 << 16
ITEMS = 1 << 13
#: meshed and unmeshed fits agree to this (max abs difference of saved
#: coefficients and of held-out scores; f32, reductions in another order)
MESH_TOL = 2e-3
#: per-entity training caps (bench.py "medium" rung of the same config)
USER_CAP = 128
ITEM_CAP = 512
HELDOUT_DIV = 8  # held-out rows = rows / 8
PARTS = 8  # avro part files per split
WRITE_BUDGET_S = 120.0
SERVE_REQUESTS = 32
SERVE_ROWS_PER_REQ = 64
SCORE_BATCH_ROWS = 8192

SHARD_ARGS = (
    "name=global,feature.bags=features",
    "name=per_user,feature.bags=userFeatures,intercept=false",
    "name=per_item,feature.bags=itemFeatures,intercept=false",
)


def emit(**row) -> None:
    print(json.dumps(row, sort_keys=True, default=str), flush=True)


# ---------------------------------------------------------------------------
# data: seeded structure + values, no JAX
# ---------------------------------------------------------------------------


def zipf_ids(rng, n: int, num_entities: int, a: float = 1.3) -> np.ndarray:
    """Zipf-skewed entity ids with every entity covered at least once when
    the row budget allows (bench._zipf_ids' shape)."""
    ids = ((rng.zipf(a, size=n) - 1) % num_entities).astype(np.int64)
    if n >= num_entities:
        ids[:num_entities] = rng.permutation(num_entities)
    return ids


def generate(seed: int, rows: int, users: int, items: int) -> dict:
    """The whole deployment as NumPy arrays (train rows first, held-out
    rows after): FE columns/values WITHOUT the intercept (the reader
    appends it), entity ids, dense RE features, labels drawn from a true
    FE + per-user + per-item logistic model."""
    n = rows + rows // HELDOUT_DIV
    rng = np.random.default_rng(seed)
    k = FE_NNZ - 1
    # column j of the shard is feature "f<j>"; the first FE_DIM-1 rows walk
    # every column once so the index map has exactly FE_DIM-1 features
    cols = rng.integers(0, FE_DIM - 1, size=(n, k), dtype=np.int64)
    if n >= FE_DIM - 1:
        cols[: FE_DIM - 1, 0] = rng.permutation(FE_DIM - 1)
    # no duplicate column inside a row (a duplicate is legal avro but makes
    # the per-row nnz data-dependent): re-draw the few collisions
    cols.sort(axis=1)
    for _ in range(8):
        dup = np.zeros_like(cols, dtype=bool)
        dup[:, 1:] = cols[:, 1:] == cols[:, :-1]
        if not dup.any():
            break
        cols[dup] = rng.integers(0, FE_DIM - 1, size=int(dup.sum()))
        cols.sort(axis=1)
    else:
        raise RuntimeError("could not de-duplicate FE columns")
    vals = rng.normal(size=(n, k)) / np.sqrt(FE_NNZ)
    user = zipf_ids(rng, n, users)
    item = zipf_ids(rng, n, items)
    # every entity must appear in the TRAIN rows (held-out rows of an
    # unseen entity would score FE-only and tell nothing about the tables)
    user[rows:] = user[rng.integers(0, rows, size=n - rows)]
    item[rows:] = item[rng.integers(0, rows, size=n - rows)]
    xu = rng.normal(size=(n, RE_DIM))
    xi = rng.normal(size=(n, RE_DIM))
    w_fe = rng.normal(size=FE_DIM - 1) * 0.5
    b_fe = -1.0  # base rate well under one half, as CTR data has
    w_user = rng.normal(size=(users, RE_DIM)) * 0.4
    w_item = rng.normal(size=(items, RE_DIM)) * 0.4
    margin = (
        b_fe
        + np.einsum("nk,nk->n", vals, w_fe[cols])
        + np.einsum("nk,nk->n", xu, w_user[user])
        + np.einsum("nk,nk->n", xi, w_item[item])
    )
    labels = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(
        np.float64
    )
    return {
        "rows": rows,
        "n": n,
        "cols": cols,
        "vals": vals,
        "user": user,
        "item": item,
        "xu": xu,
        "xi": xi,
        "labels": labels,
    }


def smoke_schema() -> dict:
    """TrainingExampleAvro plus the two random-effect feature bags."""
    from photon_tpu.io.schemas import TRAINING_EXAMPLE_AVRO

    schema = copy.deepcopy(TRAINING_EXAMPLE_AVRO)
    at = [f["name"] for f in schema["fields"]].index("features") + 1
    for bag in ("itemFeatures", "userFeatures"):
        schema["fields"].insert(
            at,
            {
                "name": bag,
                "type": {
                    "type": "array",
                    "items": "com.linkedin.photon.avro.generated.FeatureAvro",
                },
            },
        )
    return schema


def _records(data: dict, lo: int, hi: int):
    re_names = [f"x{j}" for j in range(RE_DIM)]
    for i in range(lo, hi):
        yield {
            "uid": f"r{i}",
            "label": float(data["labels"][i]),
            "features": [
                {"name": f"f{c}", "term": "", "value": float(v)}
                for c, v in zip(data["cols"][i].tolist(), data["vals"][i].tolist())
            ],
            "userFeatures": [
                {"name": nm, "term": "", "value": v}
                for nm, v in zip(re_names, data["xu"][i].tolist())
            ],
            "itemFeatures": [
                {"name": nm, "term": "", "value": v}
                for nm, v in zip(re_names, data["xi"][i].tolist())
            ],
            "metadataMap": {
                "userId": f"u{data['user'][i]}",
                "itemId": f"i{data['item'][i]}",
            },
            "weight": None,
            "offset": None,
        }


#: one deployment per process: a pool worker regenerates it from the seed
#: once (cheaper than pickling it) and writes its part files from it
_deployment = functools.lru_cache(maxsize=1)(generate)


def _write_part(job) -> float:
    """Pool worker: write one part file with the pure-Python writer."""
    seed, rows, users, items, lo, hi, path = job
    from photon_tpu.io.avro import write_avro_file

    data = _deployment(seed, rows, users, items)
    t0 = time.perf_counter()
    write_avro_file(path, smoke_schema(), _records(data, lo, hi))
    return time.perf_counter() - t0


def write_splits(seed, rows, users, items, workdir, workers) -> dict:
    """Write train/ and heldout/ part files through the pure-Python avro
    writer on a process pool (the children never touch a device). A short
    probe projects the wall first; rows and entities are halved — never the
    widths — until the projection fits ``WRITE_BUDGET_S``."""
    import multiprocessing as mp

    reduced = []
    probe_rows = 512
    probe_path = os.path.join(workdir, "probe.avro")
    per_row = _write_part(
        (seed, probe_rows, 64, 16, 0, probe_rows, probe_path)
    ) / probe_rows
    os.remove(probe_path)
    while (
        per_row * (rows + rows // HELDOUT_DIV) / workers > WRITE_BUDGET_S
        and rows > 1 << 12
    ):
        reduced.append(
            f"rows {rows}->{rows // 2}, users {users}->{users // 2}, "
            f"items {items}->{items // 2} (avro write projected over "
            f"{WRITE_BUDGET_S:.0f}s at {per_row * 1e6:.0f}us/row)"
        )
        rows, users, items = rows // 2, users // 2, items // 2

    n = rows + rows // HELDOUT_DIV
    jobs = []
    for split, lo, hi in (("train", 0, rows), ("heldout", rows, n)):
        d = os.path.join(workdir, split)
        os.makedirs(d, exist_ok=True)
        edges = np.linspace(lo, hi, PARTS + 1).astype(int)
        for p in range(PARTS):
            jobs.append(
                (seed, rows, users, items, int(edges[p]), int(edges[p + 1]),
                 os.path.join(d, f"part-{p:05d}.avro"))
            )
    t0 = time.perf_counter()
    # spawn, not fork: the parent already holds the chip's runtime threads
    with mp.get_context("spawn").Pool(workers) as pool:
        pool.map(_write_part, jobs, chunksize=1)
    return {
        "rows": rows,
        "users": users,
        "items": items,
        "write_s": time.perf_counter() - t0,
        "probe_us_per_row": per_row * 1e6,
        "reduced": reduced,
    }


# ---------------------------------------------------------------------------
# driver argument lists
# ---------------------------------------------------------------------------


def _shard_args() -> list[str]:
    out = []
    for s in SHARD_ARGS:
        out += ["--feature-shard-configurations", s]
    return out


def training_args(workdir, out_root, *, mesh=None) -> list[str]:
    args = [
        "--input-data-directories", os.path.join(workdir, "train"),
        "--validation-data-directories", os.path.join(workdir, "heldout"),
        "--root-output-directory", out_root,
        "--training-task", "LOGISTIC_REGRESSION",
        *_shard_args(),
        "--coordinate-configurations",
        "name=global,feature.shard=global,optimizer=LBFGS,max.iter=10,"
        "regularization=L2,reg.weights=1",
        "--coordinate-configurations",
        "name=per-user,random.effect.type=userId,feature.shard=per_user,"
        f"max.iter=5,regularization=L2,reg.weights=1,"
        f"active.data.upper.bound={USER_CAP}",
        "--coordinate-configurations",
        "name=per-item,random.effect.type=itemId,feature.shard=per_item,"
        f"max.iter=5,regularization=L2,reg.weights=1,"
        f"active.data.upper.bound={ITEM_CAP}",
        "--coordinate-update-sequence", "global,per-user,per-item",
        "--coordinate-descent-iterations", "2",
        "--evaluators", "AUC",
        "--precompile",
    ]
    if mesh is not None:
        args += ["--mesh", str(mesh)]
    return args


# ---------------------------------------------------------------------------
# checks shared by the phases
# ---------------------------------------------------------------------------


def assert_live_arrays_on(devices) -> int:
    """Every array still alive sits on the accelerator(s) this run owns —
    a CPU-resident leftover means some step quietly ran off the chip."""
    import jax

    allowed = set(devices)
    live = jax.live_arrays()
    for a in live:
        where = set(a.devices())
        if not where <= allowed:
            raise AssertionError(
                f"live array {a.shape} {a.dtype} sits on {where}, "
                f"not on {sorted(allowed, key=str)}"
            )
    return len(live)


def compiled_since(t: float | None) -> str:
    """The programs ``compile_watch`` saw compile after ``perf_counter``
    instant ``t`` (the whole process where ``t`` is None), latest first."""
    from photon_tpu.util import compile_watch

    rows = compile_watch.programs() if t is None else compile_watch.programs_since(t)
    return compile_watch.describe(rows, since=t)


def sweep_rows(result) -> list[dict]:
    return [r for r in result.tracker if "sweep_seconds" in r]


def assert_training_result(
    result, base_rate_auc=0.5, margin=0.1, since: float | None = None
) -> dict:
    """Finite per-coordinate health in every sweep, zero compiles in the
    second sweep, held-out AUC clearly above the base rate. ``since`` is
    the ``perf_counter`` at which the fit began: a compile in the second
    sweep is then reported with the names of what compiled after it, the
    latest (the second sweep's) first."""
    sweeps = sweep_rows(result)
    assert len(sweeps) == 2, f"expected 2 sweep rows, got {len(sweeps)}"
    for row in sweeps:
        for cid, h in (row["health"] or {}).items():
            assert h["finite"] and np.isfinite(h["loss"]), (
                f"sweep {row['iteration']} coordinate {cid}: {h}"
            )
    assert sweeps[1]["compiles"] == 0, (
        f"second sweep compiled {sweeps[1]['compiles']} program(s); "
        f"compiled since the fit began, latest first: {compiled_since(since)}"
    )
    auc = result.evaluation
    assert auc is not None and auc > base_rate_auc + margin, (
        f"held-out AUC {auc} not clearly above {base_rate_auc}"
    )
    return {
        "auc": auc,
        "sweep_seconds": [r["sweep_seconds"] for r in sweeps],
        "sweep_compiles": [r["compiles"] for r in sweeps],
        "losses": {
            cid: h["loss"] for cid, h in (sweeps[-1]["health"] or {}).items()
        },
    }


@contextlib.contextmanager
def coordinates_built():
    """The coordinates a ``game_training.run`` builds, for as long as the
    ``with`` lasts: the two ``build`` constructors hand each coordinate
    they make to the yielded list as well. The driver returns models, not
    coordinates, and where the arrays sit and what the compiled sweep
    holds can only be read off the coordinates themselves."""
    from photon_tpu.game.coordinate import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )

    built: list = []
    classes = (FixedEffectCoordinate, RandomEffectCoordinate)
    originals = [cls.build for cls in classes]

    def noting(build):
        def build_and_note(*args, **kwargs):
            coordinate = build(*args, **kwargs)
            built.append(coordinate)
            return coordinate

        return staticmethod(build_and_note)

    for cls, build in zip(classes, originals):
        cls.build = noting(build)
    try:
        yield built
    finally:
        for cls, build in zip(classes, originals):
            cls.build = staticmethod(build)
        built.clear()


def placement(arr) -> dict:
    shards = arr.addressable_shards
    return {
        "shape": list(arr.shape),
        "devices": sorted({s.device.id for s in shards}),
        "platforms": sorted({s.device.platform for s in shards}),
        "distinct_slices": len({str(s.index) for s in shards}),
    }


def coordinate_facts(coordinates) -> dict:
    from photon_tpu.game.coordinate import FixedEffectCoordinate

    facts: dict = {"fe": [], "re": []}
    for obj in coordinates:
        if isinstance(obj, FixedEffectCoordinate):
            exes = {
                k: v for k, v in obj.aot_executables().items()
                if k[0] == "sweep"
            }
            windows = getattr(obj.batch, "windows", None)
            assert len(exes) == 1, (
                "the FE coordinate holds no precompiled sweep (dropped "
                f"after rejecting its inputs?): {list(exes)}"
            )
            (sweep,) = exes.values()
            facts["fe"].append({
                "num_features": int(obj.num_features),
                "meshed": obj.mesh is not None,
                "donating_sweep": [k[1] for k in exes],
                "batch": placement(obj.batch.indices),
                "windows": None if windows is None else {
                    "rows": placement(windows.rows),
                    "window": int(windows.window),
                    "instance_len": int(windows.instance_len),
                },
                # the compiled HLO is read only where it is asked about
                "sweep_all_reduces": (
                    sweep.as_text().count("all-reduce")
                    if obj.mesh is not None else 0
                ),
            })
        else:
            facts["re"].append({
                "type": obj.config.random_effect_type,
                "tables": [placement(b.features) for b in obj.device_buckets],
            })
    return facts


def fe_columns(data: dict) -> int:
    """Columns of the FE shard as the reader will index them: every
    feature seen in the training rows, plus the intercept."""
    return len(np.unique(data["cols"][: data["rows"]])) + 1


def training_phase(name, argv, *, devices, spread: int, fe_dim: int) -> dict:
    """One ``game_training.run`` with its checks; ``spread`` is how many
    devices every coordinate's arrays must be split over."""
    from photon_tpu.cli import game_training
    from photon_tpu.util import compile_watch

    t0 = time.perf_counter()
    with coordinates_built() as built, compile_watch.watch() as cw:
        out = game_training.run(argv)
        wall = time.perf_counter() - t0
        facts = coordinate_facts(built)
    result = out["results"][out["best"]]
    checks = assert_training_result(result, since=t0)
    assert len(facts["fe"]) == 1 and len(facts["re"]) == 2, (
        f"expected one FE and two RE coordinates under the driver: {facts}"
    )
    (fe,) = facts["fe"]
    assert fe["num_features"] == fe_dim, (fe, fe_dim)
    assert fe["windows"] is not None, (
        "the FE batch carries no column-window layout: the ELL path ran, "
        f"not the TPU branch ({fe})"
    )
    placed = [fe["batch"], fe["windows"]["rows"]] + [
        t for re_ in facts["re"] for t in re_["tables"]
    ]
    ids = {d.id for d in devices}
    for p in placed:
        assert set(p["devices"]) <= ids and len(p["devices"]) == spread, p
        assert p["distinct_slices"] == spread, p
    if spread > 1:
        assert fe["sweep_all_reduces"] > 0, (
            "no all-reduce in the meshed FE sweep program"
        )
    pre = (result.compile_stats or {}).get("precompile") or {}
    live = assert_live_arrays_on(devices)
    emit(
        phase=name,
        wall_s=round(wall, 3),
        compiles=cw["backend_compiles"],
        compile_s=cw["backend_compile_s"],
        cache_hits=cw["cache_hits"],
        cache_misses=cw["cache_misses"],
        precompile={
            k: pre.get(k)
            for k in ("n_programs", "wall_s", "sum_program_walls_s")
        },
        programs={
            p["program"]: p["backend_compile_s"]
            for p in pre.get("programs", ())
        },
        fe=fe,
        re_tables={r["type"]: len(r["tables"]) for r in facts["re"]},
        live_arrays=live,
        **checks,
    )
    return out


# ---------------------------------------------------------------------------
# the plain reference: saved model files -> NumPy float64 scores
# ---------------------------------------------------------------------------


def read_saved_model(model_dir, users: int, items: int) -> dict:
    """The saved model as dense float64 arrays in the GENERATOR's
    numbering (feature ``f<j>`` -> column j, entity ``u<k>`` -> row k),
    read straight from the avro files the training driver wrote."""
    from photon_tpu.io.avro import read_avro_file

    def means(rec, width, prefix):
        w = np.zeros(width)
        for m in rec["means"]:
            if m["name"] != "(INTERCEPT)":
                w[int(m["name"][len(prefix):])] = m["value"]
        return w

    def parts(*rel):
        d = os.path.join(model_dir, *rel, "coefficients")
        return [os.path.join(d, f) for f in sorted(os.listdir(d))]

    (fe_path,) = parts("fixed-effect", "global")
    (fe_rec,) = read_avro_file(fe_path)
    model = {
        "w_fe": means(fe_rec, FE_DIM - 1, "f"),
        "b_fe": sum(
            m["value"] for m in fe_rec["means"] if m["name"] == "(INTERCEPT)"
        ),
    }
    for key, cid, count in (
        ("w_user", "per-user", users), ("w_item", "per-item", items)
    ):
        table = np.zeros((count, RE_DIM))
        for path in parts("random-effect", cid):
            for rec in read_avro_file(path):
                table[int(rec["modelId"][1:])] = means(rec, RE_DIM, "x")
        model[key] = table
    return model


def reference_scores(model: dict, data: dict, lo: int, hi: int) -> np.ndarray:
    rows = slice(lo, hi)
    return (
        model["b_fe"]
        + np.einsum(
            "nk,nk->n", data["vals"][rows], model["w_fe"][data["cols"][rows]]
        )
        + np.einsum(
            "nk,nk->n", data["xu"][rows], model["w_user"][data["user"][rows]]
        )
        + np.einsum(
            "nk,nk->n", data["xi"][rows], model["w_item"][data["item"][rows]]
        )
    )


# ---------------------------------------------------------------------------
# score and serve
# ---------------------------------------------------------------------------


def scoring_phase(workdir, model_dir, data, sizes, *, devices) -> dict:
    """Streaming ``game_scoring.run`` over the held-out part files against
    the NumPy reference. Returns uid -> score for the serve phase."""
    from photon_tpu.cli import game_scoring
    from photon_tpu.io.avro import read_avro_file
    from photon_tpu.util import compile_watch

    out_root = os.path.join(workdir, "score_out")
    t0 = time.perf_counter()
    with compile_watch.watch() as cw:
        out = game_scoring.run([
            "--input-data-directories", os.path.join(workdir, "heldout"),
            "--model-input-directory", model_dir,
            "--root-output-directory", out_root,
            *_shard_args(),
            "--score-batch-rows", str(SCORE_BATCH_ROWS),
            "--num-output-partitions", "2",
            "--evaluators", "AUC",
        ])
    wall = time.perf_counter() - t0
    with open(os.path.join(out_root, "scoring-summary.json")) as f:
        summary = json.load(f)
    assert summary["scoring"]["mode"] == "streaming", summary["scoring"]
    rows = sizes["rows"]
    held = rows // HELDOUT_DIV
    assert summary["numScored"] == held, (summary["numScored"], held)
    by_uid: dict = {}
    for path in summary["scoring"]["outputFiles"]:
        for rec in read_avro_file(path):
            by_uid[rec["uid"]] = rec["predictionScore"]
    assert len(by_uid) == held, (len(by_uid), held)
    got = np.array([by_uid[f"r{i}"] for i in range(rows, rows + held)])
    assert np.all(np.isfinite(got))
    want = reference_scores(
        read_saved_model(model_dir, sizes["users"], sizes["items"]),
        data, rows, rows + held,
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    live = assert_live_arrays_on(devices)
    emit(
        phase="score",
        wall_s=round(wall, 3),
        compiles=cw["backend_compiles"],
        compile_s=cw["backend_compile_s"],
        cache_hits=cw["cache_hits"],
        rows=held,
        batches=summary["scoring"]["batches"],
        auc=out["evaluations"].get("AUC"),
        max_abs_err_vs_numpy=float(np.max(np.abs(got - want))),
        live_arrays=live,
    )
    return by_uid


def heldout_chunks(workdir, model_dir) -> list:
    """The first held-out rows as request-sized GameData chunks, indexed
    by the model's own vocabulary (what a serving client would send)."""
    from photon_tpu.cli.game_base import read_game_data
    from photon_tpu.cli.parsing import parse_feature_shard_config
    from photon_tpu.game.data import slice_game_data
    from photon_tpu.io.model_io import read_model_feature_keys

    shard_configs = dict(parse_feature_shard_config(s) for s in SHARD_ARGS)
    maps = read_model_feature_keys(model_dir, shard_configs)
    data, _ = read_game_data(
        [os.path.join(workdir, "heldout", "part-00000.avro")],
        shard_configs, maps, ("itemId", "userId"),
    )
    n = SERVE_REQUESTS * SERVE_ROWS_PER_REQ
    assert data.num_samples >= n, (data.num_samples, n)
    return [
        slice_game_data(
            data, i * SERVE_ROWS_PER_REQ, (i + 1) * SERVE_ROWS_PER_REQ
        )
        for i in range(SERVE_REQUESTS)
    ]


def stage_requests(chunks, spool_dir, manifest_path, failure: list) -> None:
    """Stager thread: once the server has published its registry, put one
    request envelope after another into the spool. If staging fails the
    server is told to stop, so the failure ends the run, not a hang."""
    from photon_tpu.serve import spool

    try:
        deadline = time.monotonic() + 600
        while not os.path.exists(manifest_path):
            if time.monotonic() > deadline:
                raise TimeoutError("the server never published its registry")
            time.sleep(0.1)
        for seq, chunk in enumerate(chunks, start=1):
            spool.write_request(spool_dir, seq, chunk, deadline_s=120.0)
            time.sleep(0.02)
    except BaseException as e:  # noqa: BLE001 — handed to the main thread
        failure.append(e)
        spool.request_stop(spool_dir)


def serving_phase(workdir, model_dir, by_uid, *, devices) -> None:
    from photon_tpu.cli import game_serving
    from photon_tpu.serve import spool
    from photon_tpu.util import compile_watch

    chunks = heldout_chunks(workdir, model_dir)
    # the ELL width the traffic will carry (FE_NNZ at full scale, where
    # every column is in the model's vocabulary): the server compiles for
    # it before it opens, and for nothing after
    nnz = max(
        int(np.diff(c.feature_shards["global"].indptr).max()) for c in chunks
    )
    out_root = os.path.join(workdir, "serve_out")
    spool_dir = os.path.join(workdir, "spool")
    os.makedirs(spool_dir)
    failure: list = []
    stager = threading.Thread(
        target=stage_requests,
        args=(chunks, spool_dir, os.path.join(out_root, "registry.json"),
              failure),
        name="request-stager",
    )
    t0 = time.perf_counter()
    stager.start()
    try:
        with compile_watch.watch() as cw:
            out = game_serving.run([
                "--root-output-directory", out_root,
                "--spool-directory", spool_dir,
                *_shard_args(),
                "--model", f"default={model_dir}",
                "--score-batch-rows", str(SCORE_BATCH_ROWS),
                "--precompile-nnz", f"global={nnz}",
                "--max-requests", str(len(chunks)),
            ])
    finally:
        stager.join(timeout=60)
    wall = time.perf_counter() - t0
    assert not stager.is_alive(), "the request stager did not finish"
    if failure:
        raise failure[0]
    summary = out["summary"]
    assert out["answered"] == len(chunks), (out["answered"], len(chunks))
    assert summary["shed"] == 0 and summary["dispatch_failures"] == 0, summary
    traffic_compiles = summary["compiles"]["backend_compiles"]
    assert traffic_compiles == 0, (
        f"{traffic_compiles} program(s) compiled while serving; compiled "
        f"since the server was started, latest first: {compiled_since(t0)}"
    )
    worst = 0.0
    for seq, chunk in enumerate(chunks, start=1):
        res = spool.read_result(spool.result_path(spool_dir, seq))
        assert "scores" in res, res
        want = np.array([by_uid[u] for u in chunk.uids])
        np.testing.assert_allclose(res["scores"], want, rtol=1e-6, atol=1e-6)
        worst = max(worst, float(np.max(np.abs(res["scores"] - want))))
    live = assert_live_arrays_on(devices)
    emit(
        phase="serve",
        wall_s=round(wall, 3),
        compiles=cw["backend_compiles"],
        compile_s=cw["backend_compile_s"],
        cache_hits=cw["cache_hits"],
        compiles_while_serving=traffic_compiles,
        requests=len(chunks),
        rows=len(chunks) * SERVE_ROWS_PER_REQ,
        precompiled_nnz=nnz,
        batches=summary["batches"],
        shed=summary["shed"],
        e2e=out["summary"]["e2e"],
        max_abs_diff_vs_score_phase=worst,
        live_arrays=live,
    )


# ---------------------------------------------------------------------------
# four chips: the meshed fit against the unmeshed one
# ---------------------------------------------------------------------------


def four_chip_phase(workdir, data, sizes, *, devices) -> None:
    """``--mesh 1x4``: FE rows over all four chips, RE entities over the
    entity axis (``--mesh 4`` is 4x1, which REPLICATES the RE tables).
    Compared with the same fit on one chip: saved coefficients and the
    held-out scores they give."""
    rows, held = sizes["rows"], sizes["rows"] // HELDOUT_DIV
    models = {}
    for name, mesh, spread in (
        ("train_meshed", f"1x{len(devices)}", len(devices)),
        ("train_one_chip", None, 1),
    ):
        out = training_phase(
            name,
            training_args(workdir, os.path.join(workdir, name), mesh=mesh),
            devices=devices, spread=spread, fe_dim=fe_columns(data),
        )
        models[name] = read_saved_model(
            os.path.join(out["output"], "best"), sizes["users"],
            sizes["items"],
        )
    a, b = models["train_meshed"], models["train_one_chip"]
    diffs = {
        k: float(np.max(np.abs(np.asarray(a[k]) - np.asarray(b[k]))))
        for k in a
    }
    sa = reference_scores(a, data, rows, rows + held)
    sb = reference_scores(b, data, rows, rows + held)
    diffs["heldout_scores"] = float(np.max(np.abs(sa - sb)))
    emit(phase="parity_mesh_vs_one_chip", max_abs_diff=diffs, tol=MESH_TOL)
    for k, v in diffs.items():
        assert v <= MESH_TOL, f"{k}: meshed and unmeshed differ by {v}"


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def tanh_logistic_probe() -> dict:
    """ops/losses.py:36 says this backend's tanh/logistic give NaN for
    |z| >~ 100. Ask the chip."""
    import jax
    import jax.numpy as jnp

    z = jnp.asarray(
        [0.0, 20.0, 88.0, 100.0, 200.0, 1e3, 1e4, 1e30, float("inf")],
        jnp.float32,
    )
    z = jnp.concatenate([z, -z])
    out = {
        "tanh": np.asarray(jnp.tanh(z)),
        "logistic": np.asarray(jax.nn.sigmoid(z)),
        "jit_tanh": np.asarray(jax.jit(jnp.tanh)(z)),
        "jit_logistic": np.asarray(jax.jit(jax.nn.sigmoid)(z)),
    }
    nan_at = {
        k: [float(x) for x in np.asarray(z)[np.isnan(v)]]
        for k, v in out.items()
    }
    return {
        "nan_for_large_z": any(nan_at.values()),
        "nan_at": {k: v for k, v in nan_at.items() if v},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs ONLY the meshed-vs-unmeshed fit on four chips",
    )
    args = ap.parse_args(argv)
    if not __debug__:
        print("chip_smoke: its checks are asserts; run it without -O",
              file=sys.stderr)
        return 2

    import jax

    from photon_tpu.util.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform "
            f"{devices[0].platform!r}); nothing was run", file=sys.stderr,
        )
        return 2
    if len(devices) != args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs exactly {args.chips} "
            f"device(s), JAX found {len(devices)}", file=sys.stderr,
        )
        return 2

    from photon_tpu.data.native_index import _load_native_lib

    t_all = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        div = 4 if args.chips == 4 else 1
        sizes = write_splits(
            args.seed, ROWS // div, USERS // div, ITEMS // div, workdir,
            workers=max(1, min(PARTS, (os.cpu_count() or 2) - 1)),
        )
        data = generate(
            args.seed, sizes["rows"], sizes["users"], sizes["items"]
        )
        assert fe_columns(data) == FE_DIM, "the rows do not cover every column"
        emit(
            phase="generate",
            seed=args.seed,
            fe_dim=FE_DIM, fe_nnz=FE_NNZ, re_dim=RE_DIM,
            heldout_rows=sizes["rows"] // HELDOUT_DIV,
            base_rate=float(data["labels"].mean()),
            reader="native" if _load_native_lib() is not None else "python",
            compile_cache_dir=cache_dir,
            tanh_logistic=tanh_logistic_probe(),
            **sizes,
        )
        if args.chips == 4:
            four_chip_phase(workdir, data, sizes, devices=devices)
        else:
            out = training_phase(
                "train",
                training_args(workdir, os.path.join(workdir, "train_out")),
                devices=devices, spread=1, fe_dim=FE_DIM,
            )
            model_dir = os.path.join(out["output"], "best")
            by_uid = scoring_phase(
                workdir, model_dir, data, sizes, devices=devices
            )
            serving_phase(workdir, model_dir, by_uid, devices=devices)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    emit(phase="total", wall_s=round(time.perf_counter() - t_all, 3))
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
