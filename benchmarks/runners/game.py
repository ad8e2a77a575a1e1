"""Runner for GLMix (GAME) descent sweeps: one sparse fixed effect and
per-entity random effects, fitted by block coordinate descent through
``GameEstimator`` and ``run_coordinate_descent``.

What a GAME runner needs to know beyond ``benchmarks/README.md``:

- The fit is BUILT once in set-up through the estimator's public handle
  (``GameEstimator.build`` -> ``BuiltFit``: coordinates with their placed
  data, the update sequence, the zero states) and every step drives sweeps
  over it with ``run_coordinate_descent``, as ``GameEstimator.fit`` itself
  does. A tree without that handle fails here at once, before any data is
  made or any program compiled.
- **A step is one whole fit**: ``solver.descent_sweeps`` sweeps from the
  zero states, every step ``fresh``, as in ``linear_tron.solve``; the
  configuration's ``solver.fe_tolerance`` is negative (a stated departure
  from photon-ml's 1e-7, under ``reduced``), so no tolerance ends the fixed
  effect's solve (at 1e-7 a float32 loss over 4 M rows stops it by rounding
  on some seeds) and a step is the same work on every seed on which the
  line search finds its points; the program's search goes by the
  derivative where the value cannot resolve a gain
  (``optimize/linesearch.py``), so that is all but every one. What may end
  a fixed-effect solve is held in ``observe``, for every sweep of every
  first step: its stated iterations, or a line search that failed (the
  program's ``OBJECTIVE_NOT_IMPROVING``: the solve stays where it was),
  and, where the configuration states a tolerance, that tolerance's own
  two reasons. A solve that ended otherwise, as one cut short does, is not
  the step the cell defines, and the step counts as failed (``ok``); so
  does any step with a non-finite coordinate. A sweep is closed by the
  descent loop's own barrier, the one read-back that brings the health
  scalars and the solves' counters home; the timed step reads nothing
  else.
- For ``lib/check.py`` a step's units are the first ``follow_fe_iterations``
  (K) optimizer iterations of the FIRST sweep's fixed-effect solve, then the
  sweeps: ``iterations`` = K + sweeps (``stopping_rule``: no segment length,
  ``max_iterations`` the same, so every step ends on ``max_iterations`` and
  ``iters_off`` 0, ``stop_excess`` 1 hold the count). ``loss[i]`` /
  ``gnorm[i]`` for i <= K are that solve's own histories (from the zero
  point the random effects score nothing: its objective IS the whole
  objective), read from the step's tracker row (``info.loss_history``);
  the entries after them are the whole regularised objective and the norm
  of its gradient by coordinate after each sweep. ``x`` is the fixed
  effect's coefficients, then each random effect's table in entity order.
- ``passes`` are the FIXED effect's needed passes (``work_game.lbfgs_passes``
  of each sweep's iterations) and ``block`` its ELL block, so that
  ``fe_solve_ms`` and ``fe_pass_roofline`` mean what they mean in
  ``sparse_poisson.solve`` (``programs["fe_solve"]`` is the fixed effect's
  sweep program, by HLO module name) and ``train_rows_per_s`` is rows x
  fixed-effect passes over the WHOLE wall, random-effect solves and
  rescoring included. ``step_mfu_pct`` therefore leaves the random effects'
  bytes out and under-reads (PERF.md section 7). The random effects' own
  work is in ``block["re_step_bytes"]``, one entry a step, for
  ``re_solve_roofline``; ``programs["re_solve"]`` names their sweep program.
- ``observe`` (set-up only) evaluates the objective and its gradient with
  the program's own objective ops at the step's last point. The states after
  the earlier sweeps come from driving the same fit once more with a
  ``sweep_callback``: a step keeps nothing but its last states. That second
  drive is made for the first of the first steps; the others reuse its
  per-sweep readings where their last point is the same to the bit (a fit
  from zero states on the same data is deterministic), and drive again
  where it is not.
- **What the program is held to.** After its third iteration this fixed
  effect's solve moves its loss by 4e-4 of itself in seven iterations (by
  1e-6 in one), the chip evaluates that loss 5e-5 below float64 at the same
  point, and the float32 path ends 2e-4 to 1e-3 of the coefficients' norm
  from the float64 one (PERF.md, PR 31; further on the CPU backend): the
  END of a solve cannot be held by its value, and the fixed effect is 2e-3
  of ``x``'s norm, so ``x_diff`` cannot see it either. The record therefore
  has three parts. (1) Entries 0..K are the reference's OWN float64 solve
  from zero, nothing of the program's in it: ``loss_gap`` holds the
  program's solver to it iteration by iteration while the loss still moves
  (an L-BFGS that lost its history reads 4e-4 to 2e-3 at the fourth
  iteration). (2) What ended every fixed-effect solve is held to the stated
  rule, by ``ok`` above. (3) The random effects are sixteen-wide ridge problems
  that five iterations all but solve, so the rest of the record is the fit
  the reference makes when every sweep's fixed effect is the one the fit in
  the program's place reached (``fe_path``: ``observe`` hands over the
  program's, a stand-in record its own): the objective after each sweep
  (``loss_gap`` again) and ``x`` (``x_diff``, ``dx_gap``) hold the TABLES
  tightly, and it is they that a descent which skips entities or trains on
  stale scores gets wrong; ``fe_path`` serves nothing else. AT the
  program's point nothing is amplified: ``loss_at_x_gap`` and
  ``grad_at_x_*`` hold the arithmetic. Not held: a fixed-effect direction
  that goes wrong after iteration K and still lowers the loss.
- The reference (``lib/reference_game.py``) is given the rows the program
  trains on (``<coordinate>.active``): which rows a capped entity keeps is
  the program's draw, and another draw would be another problem.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.lib import datagen_game, reference_game, work_game

FIXED = "fixed"


@dataclasses.dataclass
class State:
    config: dict
    built: object  # the program's BuiltFit
    zero: dict  # cid -> the zero state every step starts from
    inputs: dict  # what the reference is given: the benchmark's own arrays
    first: list  # records of the first steps, for ``correct``
    block: dict  # the fixed effect's block and the random effects' work
    programs: dict  # layer -> HLO module names
    last: dict = None  # cid -> the states the last step left, on the device
    fe_infos: list = None  # the last step's fixed-effect solves, one a sweep (on the device)
    sweeps: dict = None  # the per-sweep readings and last point of a second drive
    evaluate: object = None  # (states) -> loss, gradient norm, gradient


def _coordinate_configs(config: dict) -> dict:
    from photon_tpu.game.config import (
        FixedEffectCoordinateConfig,
        RandomEffectCoordinateConfig,
    )
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import (
        GLMProblemConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import TaskType

    solver = config["solver"]

    def problem(max_iterations, trials, tolerance):
        return GLMProblemConfig(
            task=TaskType[config["task"]],
            optimizer_config=OptimizerConfig(
                max_iterations=max_iterations, ls_max_iterations=trials,
                tolerance=tolerance, num_corrections=solver["history"],
            ),
            regularization=RegularizationContext(RegularizationType.L2),
        )

    out = {
        FIXED: FixedEffectCoordinateConfig(
            feature_shard="global",
            optimization=problem(solver["fe_max_iterations"], solver["fe_ls_max_iterations"],
                                 solver["fe_tolerance"]),
            regularization_weights=(solver["l2_weight"],),
        )
    }
    for name, re in config["random_effects"].items():
        out[name] = RandomEffectCoordinateConfig(
            random_effect_type=name,
            feature_shard=name,
            optimization=problem(solver["re_max_iterations"], solver["re_ls_max_iterations"],
                                 solver["re_tolerance"]),
            regularization_weights=(solver["l2_weight"],),
            active_data_upper_bound=re["cap"],
        )
    return out


def _dense_shard(x: np.ndarray):
    """A dense [n, d] block as the CSR shard the readers would hand over:
    every row stores all d columns in order."""
    from photon_tpu.game.data import CSRMatrix

    n, d = x.shape
    return CSRMatrix(indptr=np.arange(n + 1, dtype=np.int64) * d,
                     indices=np.tile(np.arange(d, dtype=np.int32), n),
                     values=x.reshape(-1), num_cols=d)


def game_data(config: dict, struct: dict, vals: dict):
    """The deployment as the program's ``GameData``: the fixed effect's CSR
    shard, one dense shard and one id column per random effect."""
    from photon_tpu.game.data import CSRMatrix, GameData

    feat, res = config["features"], config["random_effects"]
    n, k = feat["n"], feat["nnz_per_row"]
    return GameData.build(
        labels=vals["labels"],
        feature_shards={
            "global": CSRMatrix(
                indptr=np.arange(n + 1, dtype=np.int64) * k,
                indices=struct["fe_cols"].reshape(-1),
                values=vals["fe_vals"].reshape(-1), num_cols=feat["d"]),
            **{name: _dense_shard(vals[name]) for name in res},
        },
        id_tags={name: struct[name] for name in res},
    )


def estimator(config: dict):
    """The ``GameEstimator`` of the configuration: fixed effect first, then
    the random effects in the order the file gives."""
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.types import TaskType

    return GameEstimator(
        task=TaskType[config["task"]],
        coordinate_configs=_coordinate_configs(config),
        update_sequence=[FIXED, *config["random_effects"]],
        descent_iterations=config["solver"]["descent_sweeps"],
    )


def setup(config: dict, seed: int, spans, control: bool = False) -> State:
    import jax

    from photon_tpu.game.estimator import GameEstimator

    if not hasattr(GameEstimator, "build"):
        raise RuntimeError(
            "this tree's GameEstimator has no public handle to a built fit "
            "(GameEstimator.build): the cell cannot drive sweeps on it"
        )
    feat, res = config["features"], config["random_effects"]
    n, k = feat["n"], feat["nnz_per_row"]
    with spans.span("build"):
        with spans.span("build.generate"):
            struct = datagen_game.structure(config)
            vals = datagen_game.values(config, struct, seed)
        data = game_data(config, struct, vals)
        with spans.span("build.prepare"):
            built = estimator(config).build(data)
            zero = built.initial_states()
            # placement is asynchronous; this compiles nothing
            jax.block_until_ready((
                [(db.features, db.score_feats) for name in res
                 for db in built.coordinates[name].device_buckets],
                built.coordinates[FIXED].batch, zero))
        del data
    # the random effects' bucketing and placement, from the program's own
    # ``photon.game.prepare.*`` spans, under the benchmark's span name
    for name in res:
        took = sum(built.prepare_seconds[name].values())
        spans.rows.append(("re_build", 0.0, took))

    inputs = {"fe_cols": struct["fe_cols"], "fe_vals": vals["fe_vals"], "labels": vals["labels"],
              # what the reference's calls share: its models, its own fit, and
              # the fixed effects of whatever fit stands in the program's place
              "_shared": {}}
    block = {"kind": "sparse", "nnz": n * k, "re": {}, "re_step_bytes": []}
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
    state = State(
        config=config, built=built, zero=zero, inputs=inputs, first=[], block=block,
        programs={"fe_solve": ["jit_fe_sweep"], "re_solve": ["jit_re_sweep"]},
    )
    state.evaluate = _evaluator(config, built)
    return state


def _descend(state: State, **kwargs):
    from photon_tpu.game.descent import run_coordinate_descent

    b = state.built
    return run_coordinate_descent(
        b.coordinates, b.update_sequence, b.descent_iterations,
        initial_states=state.zero, locked_coordinates=b.locked_coordinates, **kwargs)


def step(state: State) -> dict:
    """One timed step: a whole fit from the zero states, closed by its last
    sweep's barrier. The counters are the ones that barrier read back."""
    cd = _descend(state)
    sweeps = [row["health"] for row in cd.tracker if "health" in row]
    state.last = cd.states
    state.fe_infos = [row["info"] for row in cd.tracker if row.get("coordinate") == FIXED]
    passes, re_bytes, ok = 0, 0.0, True
    for i, health in enumerate(sweeps):
        passes += work_game.lbfgs_passes(health[FIXED]["iterations"][0], from_zero=i == 0)
        for name, shape in state.block["re"].items():
            re_bytes += work_game.re_sweep_bytes(shape, health[name]["iterations"], from_zero=i == 0)
        ok = ok and all(h["finite"] for h in health.values())
    state.block["re_step_bytes"].append(re_bytes)
    return {"units": len(sweeps), "passes": passes, "fresh": True,
            "reason": "max_iterations", "ok": bool(ok)}


def _ended_as_stated(solver: dict, info) -> bool:
    """Whether a fixed-effect solve ended for a reason the configuration
    allows: its stated iterations, a failed line search, or, where a
    tolerance is stated, that tolerance."""
    from photon_tpu.optimize.common import ConvergenceReason as Reason

    reason, iterations = int(info.reason), int(info.iterations)
    if reason == Reason.MAX_ITERATIONS:
        return iterations == solver["fe_max_iterations"]
    if reason in (Reason.FUNCTION_VALUES_CONVERGED, Reason.GRADIENT_CONVERGED):
        return solver["fe_tolerance"] >= 0
    return reason == Reason.OBJECTIVE_NOT_IMPROVING


def _evaluator(config: dict, built):
    """(states) -> (whole objective, norm of its gradient by coordinate, the
    gradient) computed by the program's own objective ops on the placed
    data, in the order ``x`` has."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.optimize.problem import GLMProblem
    from photon_tpu.types import LabeledBatch

    coords = built.coordinates
    fe = coords[FIXED]
    res = [coords[name] for name in config["random_effects"]]
    l2 = config["solver"]["l2_weight"]
    y = fe.batch.labels

    @jax.jit
    def at(fe_batch, re_buckets, scores, states):
        total = sum(scores)
        loss = jnp.sum(fe.problem.objective.loss.loss(total, y))
        reg = sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree_util.tree_leaves(states))
        _, g_fe = fe.problem.objective.value_and_gradient(
            states[0], fe_batch._replace(offsets=fe_batch.offsets + total - scores[0]))
        grads = [g_fe]
        for coord, buckets, score, tables in zip(res, re_buckets, scores[1:], states[1:]):
            objective = GLMProblem.build(coord.problem_config).objective
            rest = jnp.concatenate([total - score, jnp.zeros((1,), total.dtype)])
            per_bucket = []
            for (f, lab, off, w, pos), table in zip(buckets, tables):
                extra = rest[jnp.minimum(pos, total.shape[0])]
                per_bucket.append(jax.vmap(
                    lambda f, lab, off, w, t: objective.value_and_gradient(
                        t, LabeledBatch(features=f, labels=lab, offsets=off, weights=w))[1]
                )(f, lab, off + extra, w, table))
            grads.append(per_bucket)
        gsq = sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree_util.tree_leaves(grads))
        return loss + 0.5 * l2 * reg, jnp.sqrt(gsq), grads

    def evaluate(states: dict):
        order = [FIXED, *config["random_effects"]]
        scores = [coords[cid].score(states[cid]) for cid in order]
        buckets = [[(db.features, db.labels, db.offsets, db.train_weights, db.sample_pos)
                    for db in c.device_buckets] for c in res]
        loss, gnorm, grads = at(fe.batch, buckets, scores, [states[cid] for cid in order])
        return float(loss), float(gnorm), dict(zip(order, grads))

    return evaluate


def _packed(state: State, per_coordinate: dict) -> np.ndarray:
    """``x``'s layout from a per-coordinate tree (states or gradients): the
    fixed effect's vector, then each random effect's table in entity order
    (a bucket's rows belong to the entities its host bucket lists). float32,
    as the program holds it: 38 M numbers a record at the cell's size."""
    parts = [np.asarray(per_coordinate[FIXED], np.float32)]
    for name, re in state.config["random_effects"].items():
        ds = state.built.re_datasets[name]
        entity = ds.vocab.astype(np.int64)  # the ids went in as decimal strings
        table = np.zeros((re["entities"], re["d"]), np.float32)
        for bucket, leaf in zip(ds.buckets, per_coordinate[name]):
            e = len(bucket.entity_ids)
            table[entity[bucket.entity_ids]] = np.asarray(leaf)[:e, : re["d"]]
        parts.append(table.reshape(-1))
    return np.concatenate(parts)


def _drive_again(state: State) -> dict:
    """The same fit once more, keeping the states after every sweep: the
    per-sweep objective and gradient norm, and the last point for the
    bit-for-bit comparison with the step's own."""
    kept = []
    # a copy of the dict: without donation the loop hands over its own
    cd = _descend(state, sweep_callback=lambda it, states, *_: kept.append(dict(states)))
    readings = [state.evaluate(state.zero)[:2]] + [state.evaluate(s)[:2] for s in kept]
    return {"loss": [r[0] for r in readings], "gnorm": [r[1] for r in readings],
            "x": _packed(state, cd.states),
            "fe_path": [np.asarray(s[FIXED], np.float64) for s in kept]}


def observe(state: State, out: dict) -> None:
    """Keep what ``correct`` compares of one of the first steps (host
    copies: the program's state is freed before the reference runs), and
    hold what ended each of its fixed-effect solves (``out["ok"]``, which
    the harness reads after this call)."""
    k = state.config["follow_fe_iterations"]
    out["ok"] = out["ok"] and all(
        _ended_as_stated(state.config["solver"], info) for info in state.fe_infos)
    x = _packed(state, state.last)
    loss, gnorm, grads = state.evaluate(state.last)
    if state.sweeps is None or not np.array_equal(state.sweeps["x"], x):
        state.sweeps = _drive_again(state)
    else:
        x = state.sweeps["x"]  # the same numbers: one copy on the host
    state.inputs["_shared"]["fe_path"] = state.sweeps["fe_path"]
    # the step's own first fixed-effect solve, iteration by iteration
    fe_loss = np.asarray(state.fe_infos[0].loss_history, np.float64)[: k + 1]
    fe_gnorm = np.asarray(state.fe_infos[0].grad_norm_history, np.float64)[: k + 1]
    state.first.append({
        # the earlier sweeps from the second drive, the last from this step
        "loss": np.concatenate([fe_loss, state.sweeps["loss"][1:-1], [loss]]),
        "gnorm": np.concatenate([fe_gnorm, state.sweeps["gnorm"][1:-1], [gnorm]]),
        "x": x,
        "gradient": _packed(state, grads),
        "iterations": k + out["units"],
        "fresh": out["fresh"],
        "reason": out["reason"],
    })


def release(state: State) -> None:
    """Free the program's state; the reference keeps only the benchmark's
    own inputs. The sweep programs are keyed on their coordinates (a static
    argument), so JAX's caches keep every coordinate alive with its placed
    blocks and its host-side buckets (ROADMAP C9) until they are cleared: on
    the one-chip machine the TPU runtime holds 14 of the 40 GB of host
    memory before the first array is made, and the reference needs the
    rest."""
    import gc

    import jax

    state.built = state.zero = state.last = state.fe_infos = state.evaluate = state.sweeps = None
    jax.clear_caches()
    gc.collect()


def _model(config: dict, inputs: dict, precision):
    kept = inputs["_shared"].setdefault("models", {})  # the entities are grouped once
    key = precision or "f64"
    if key not in kept:
        kept[key] = reference_game.Glmix(config, inputs, precision=key)
    return kept[key]


def _record(k: int, fe_first: dict, fit: dict) -> dict:
    """The record ``check.py`` reads: the first sweep's fixed-effect solve
    for K iterations, then ``fit``'s sweeps."""
    return {**fit, "reason": "max_iterations", "iterations": k + fit["iterations"],
            "loss": np.concatenate([fe_first["loss"][: k + 1], fit["loss"][1:]]),
            "gnorm": np.concatenate([fe_first["gnorm"][: k + 1], fit["gnorm"][1:]])}


def reference_record(config: dict, inputs: dict, steps: int, precision=None) -> dict:
    """The plain reference over the same inputs: every step is the same fit
    from zero, so one record stands for them all. The sound float64
    reference's record (the module's docstring): its OWN fixed-effect solve
    from zero for the first K iterations, then the fit held to the fixed
    effects of whatever last stood in the program's place (its own, where
    nothing has). ``precision="bf16"`` (the control) and ``inputs["fault"]``
    (a broken descent, ``reference_game``) are such stand-ins: one fit of
    their own, whose fixed effects they leave for the reference that judges
    them."""
    shared, sweeps = inputs["_shared"], config["solver"]["descent_sweeps"]
    k = config["follow_fe_iterations"]
    model = _model(config, inputs, precision)
    fault = inputs.get("fault")
    stand_in = precision is not None or fault is not None
    if stand_in or "fe_path" not in shared:
        fit = model.descend(sweeps, fault=fault)
        if stand_in:
            shared["fe_path"] = fit["fe_path"]
        return _record(k, fit["fe_first"], fit)
    if "fe_first" not in shared:
        shared["fe_first"] = model.fixed_from_zero(k)
    return _record(k, shared["fe_first"], model.descend(sweeps, fe_path=shared["fe_path"]))


def reference_at(config: dict, inputs: dict, x, gradient: bool = True) -> dict:
    """The reference's whole objective (and its gradient by coordinate) at
    the point ``x`` where the program stands after one of its first steps."""
    model = _model(config, inputs, None)
    return model.evaluate(model.unpack(x), gradient=gradient)


def stopping_rule(config: dict) -> dict:
    """A step is a whole fit and ends on its count of units: the followed
    iterations of the first fixed-effect solve, then the sweeps."""
    return {"segment_iters": None,
            "max_iterations": config["follow_fe_iterations"] + config["solver"]["descent_sweeps"]}
