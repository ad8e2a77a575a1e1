"""Runner for GLMix (GAME) descent sweeps over a DENSE fixed effect and
random effects whose entities hold tens to thousands of rows: the
ratings-shaped deployment (``glmix_movielens``), fitted through
``GameData`` -> ``GameEstimator.build`` -> ``run_coordinate_descent`` like
``runners/game.py``'s click-shaped one. Everything a GAME runner does is
written there (what a step is, what ``correct`` holds, how ``observe``
reads the sweeps); this module is what differs:

- every feature shard is handed to ``GameData`` as its ``[n, d]`` ARRAY, the
  fixed effect's 4.29 GB block too: the program's dense-shard input
  (``photon_tpu.game.data.DenseMatrix``). A tree without it fails here at
  once, before any data is made: through the CSR of full rows the block
  would cost the host an int32 index and an int64 row index beside the
  values, over 21 GB on a machine whose TPU runtime holds 14 of 40.
- the data come from ``lib/datagen_movielens.py`` (every user at least 20
  rows, a heavy-tailed movie popularity), the reference is
  ``lib/reference_game_dense.py`` (the dense products block by block).
- ``block`` is the dense ``[n, d]`` block, so ``fe_pass_roofline`` and
  ``step_mfu_pct`` read the dense pass; ``programs["re_rows"]`` names the
  random effects' sweep program for ``re_rows_solve_ms`` and
  ``re_rows_roofline`` (``block["re_step_bytes"]``, ``work_game.py``).
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib import datagen_movielens, reference_game_dense
from benchmarks.runners import game as base

FIXED = base.FIXED

step = base.step
observe = base.observe
release = base.release
stopping_rule = base.stopping_rule


def game_data(config: dict, struct: dict, vals: dict):
    """The deployment as the program's ``GameData``: every shard a dense
    array, one id column per random effect (as short strings: an id is a
    key to the program, and 8 M keys of numpy's default 21 characters are
    0.7 GB a column)."""
    from photon_tpu.game.data import GameData

    res = config["random_effects"]
    return GameData.build(
        labels=vals["labels"],
        feature_shards={"global": vals["fe_x"], **{name: vals[name] for name in res}},
        id_tags={name: struct[name].astype(f"U{len(str(re['entities']))}")
                 for name, re in res.items()},
    )


def setup(config: dict, seed: int, spans, control: bool = False) -> base.State:
    import jax

    from photon_tpu.game import data as program_data

    if not hasattr(program_data, "DenseMatrix"):
        raise RuntimeError(
            "this tree's GameData takes no dense feature shard "
            "(photon_tpu.game.data.DenseMatrix): the cell's [n, 128] fixed-effect "
            "block cannot be handed in as an array, and is not made"
        )
    feat, res = config["features"], config["random_effects"]
    n = feat["n"]
    with spans.span("build"):
        with spans.span("build.generate"):
            struct = datagen_movielens.structure(config)
            vals = datagen_movielens.values(config, struct, seed)
        data = game_data(config, struct, vals)
        with spans.span("build.prepare"):
            built = base.estimator(config).build(data)
            zero = built.initial_states()
            # placement is asynchronous; this compiles nothing
            jax.block_until_ready((
                [(db.features, db.score_feats) for name in res
                 for db in built.coordinates[name].device_buckets],
                built.coordinates[FIXED].batch, zero))
        del data
    # the program's own ``photon.game.prepare.*`` spans, under the
    # benchmark's span names: the random effects' bucketing and placement,
    # and the dense block's placement
    for name in res:
        spans.rows.append(("re_build", 0.0, sum(built.prepare_seconds[name].values())))
    spans.rows.append(("fe_place", 0.0, built.prepare_seconds[FIXED]["place"]))

    inputs = {"fe_x": vals["fe_x"], "labels": vals["labels"], "_shared": {}}
    block = {"kind": "dense", "n": n, "d": feat["d"], "itemsize": 4,
             "re": {}, "re_step_bytes": []}
    size = feat["d"]
    for name, re in res.items():
        ds = built.re_datasets[name]
        active = np.zeros(n, bool)
        for b in ds.buckets:
            active[b.sample_pos[b.sample_pos < n]] = True
        inputs[name + ".ids"] = struct[name]
        inputs[name + ".features"] = vals[name]
        inputs[name + ".active"] = active
        block["re"][name] = {
            "buckets": [{"entities": b.num_entities, "rows": b.features.shape[1],
                         "d": b.features.shape[2]} for b in ds.buckets],
            "kept_rows": int(sum(len(b.score_pos) for b in ds.buckets)),
            "d": re["d"],
        }
        size += re["entities"] * re["d"]
    inputs["w0"] = np.zeros(size, np.float32)
    state = base.State(
        config=config, built=built, zero=zero, inputs=inputs, first=[], block=block,
        programs={"fe_solve": ["jit_fe_sweep"], "re_rows": ["jit_re_sweep"]},
    )
    state.evaluate = base._evaluator(config, built)
    return state


def _model(config: dict, inputs: dict, precision):
    kept = inputs["_shared"].setdefault("models", {})  # the entities are grouped once
    key = precision or "f64"
    if key not in kept:
        kept[key] = reference_game_dense.GlmixDense(config, inputs, precision=key)
    return kept[key]


def reference_record(config: dict, inputs: dict, steps: int, precision=None) -> dict:
    """``runners/game.reference_record`` over the dense reference: its OWN
    fixed-effect solve from zero for the first K iterations, then the fit
    held to the fixed effects of whatever last stood in the program's place;
    a stand-in (the bf16 control, ``inputs["fault"]``) makes one fit of its
    own and leaves its fixed effects for the reference that judges it."""
    shared, sweeps = inputs["_shared"], config["solver"]["descent_sweeps"]
    k = config["follow_fe_iterations"]
    model = _model(config, inputs, precision)
    fault = inputs.get("fault")
    stand_in = precision is not None or fault is not None
    if stand_in or "fe_path" not in shared:
        fit = model.descend(sweeps, fault=fault)
        if stand_in:
            shared["fe_path"] = fit["fe_path"]
        return base._record(k, fit["fe_first"], fit)
    if "fe_first" not in shared:
        shared["fe_first"] = model.fixed_from_zero(k)
    return base._record(k, shared["fe_first"], model.descend(sweeps, fe_path=shared["fe_path"]))


def reference_at(config: dict, inputs: dict, x, gradient: bool = True) -> dict:
    model = _model(config, inputs, None)
    return model.evaluate(model.unpack(x), gradient=gradient)
