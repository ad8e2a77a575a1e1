"""Runner for a single GLM solve: ``GLMProblem.solve`` (TRON, one whole
solve per step) or ``SegmentedOWLQN`` (one bounded segment per step, the
program's own path for long solves on a TPU).

The configuration's file says which: ``solver.kind`` is ``tron`` or
``owlqn_segmented``; ``features.kind`` is ``dense`` or ``sparse``. The
program picks its own kernels (window layout, gather, rmatvec): no
``PHOTON_*`` variable is set here.

A runner exposes ``setup(config, seed, spans, control)``, ``step(state)``,
``observe(state, out)``, ``release(state)``, ``reference_record(config,
inputs, steps, precision)``, ``reference_at(config, inputs, x, gradient)``
and ``stopping_rule(config)``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.lib import datagen, reference, work


@dataclasses.dataclass
class State:
    config: dict
    batch: object  # the program's batch pytree, on the device
    w0: object
    inputs: dict  # what the reference is given: the benchmark's own arrays
    first: list  # read-backs of the first steps, for ``correct``
    # the timed entry, in the pieces the program's own driver loop has:
    start: object = None  # () -> a solve at its start point (None: a step is a whole solve)
    advance: object = None  # (solve in flight) -> the same solve one step on, on the device
    counters: object = None  # (solve) -> device scalars: iterations, trials, hvp, reason, value
    result: object = None  # (solve) -> the program's OptimizeResult, on the device
    in_flight: object = None  # the solve the next step continues; None: it starts one
    last: object = None  # what the last step left, for ``observe``
    before: tuple = (0, 0)  # iterations and trials read back so far in this solve
    trials_at_start: int | None = None  # what the program's counter holds before any trial
    block: dict = None  # the feature block's shape, for work.py
    programs: dict = None  # layer -> HLO module names


#: the program's ConvergenceReason codes under the benchmark's own names
REASONS = {0: None, 1: "max_iterations", 2: "function_values", 3: "gradient", 4: "not_improving"}


def _host(x):
    return np.asarray(x)


def setup(config: dict, seed: int, spans, control: bool = False) -> State:
    import jax
    import jax.numpy as jnp

    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.types import LabeledBatch, SparseBatch, TaskType

    feat, solver = config["features"], config["solver"]
    n, d = feat["n"], feat["d"]
    loss = loss_for_task(TaskType[config["task"]])
    with spans.span("build"):
        if feat["kind"] == "dense":
            x, labels, w0 = datagen.dense_linear(n, d, seed)
            inputs = {"features": x, "labels": labels}  # the reference keeps float32
            if control:  # the program's own lower-precision path
                x = x.astype(jnp.bfloat16)
            batch = LabeledBatch(
                features=x,
                labels=labels,
                offsets=jnp.zeros((n,), jnp.float32),
                weights=jnp.ones((n,), jnp.float32),
            )
            block = {"kind": "dense", "n": n, "d": d, "itemsize": x.dtype.itemsize}
        else:
            from photon_tpu.ops.sparse_windows import maybe_build_windows

            k = feat["nnz_per_row"]
            with spans.span("build.generate"):
                idx = datagen.sparse_structure(n, d, k, config["structure_seed"])
                vals = datagen.sparse_poisson_values(idx, d, seed)
            with spans.span("build.windows"):
                windows = maybe_build_windows(idx, vals["values"], d)
            batch = SparseBatch(
                indices=jnp.asarray(idx),
                values=jnp.asarray(vals["values"]),
                labels=jnp.asarray(vals["labels"]),
                offsets=jnp.zeros((n,), jnp.float32),
                weights=jnp.ones((n,), jnp.float32),
                windows=windows,
            )
            w0 = jnp.asarray(vals["w0"])
            inputs = {"indices": idx, "values": vals["values"], "labels": vals["labels"]}
            block = {"kind": "sparse", "nnz": n * k}
        # placement is asynchronous; on this chip block_until_ready and a
        # read-back agree (PERF.md, PR 28), and this compiles nothing
        jax.block_until_ready((batch, w0))
    inputs["w0"] = _host(w0)

    state = State(config=config, batch=batch, w0=w0, inputs=inputs, first=[], block=block)
    if solver["kind"] == "tron":
        from photon_tpu.optimize.problem import (
            GLMProblem,
            GLMProblemConfig,
            RegularizationContext,
            RegularizationType,
        )
        from photon_tpu.types import OptimizerType

        problem = GLMProblem.build(
            GLMProblemConfig(
                task=TaskType[config["task"]],
                optimizer=OptimizerType.TRON,
                optimizer_config=OptimizerConfig(
                    max_iterations=solver["max_iterations"],
                    tolerance=solver["tolerance"],
                    max_cg_iterations=solver["max_cg_iterations"],
                    cg_tolerance=solver["cg_tolerance"],
                ),
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=solver["l2_weight"],
            )
        )

        @jax.jit
        def tron_solve(batch, w0):
            return problem.solve(batch, w0)

        # a step is a whole solve from the seed's start point
        state.advance = lambda _: tron_solve(state.batch, state.w0)
        state.counters = lambda r: (r.iterations, r.n_evals, r.n_hvp, r.reason, r.value)
        state.result = lambda r: r
        state.programs = {"fe_solve": ["jit_tron_solve"]}
    elif solver["kind"] == "owlqn_segmented":
        from photon_tpu.optimize.owlqn import SegmentedOWLQN

        lam, alpha = solver["regularization_weight"], solver["elastic_net_alpha"]
        obj = GLMObjective(loss=loss, l2_weight=(1 - alpha) * lam, l1_weight=alpha * lam)
        seg = SegmentedOWLQN(
            None,
            alpha * lam,
            OptimizerConfig(
                max_iterations=solver["max_iterations"], tolerance=solver["tolerance"]
            ),
            oracle_factory=obj.smooth_margin_oracle,
            segment_iters=solver["segment_iters"],
        )
        # SegmentedOWLQN.__call__'s own loop, a segment to a step: it syncs a
        # scalar per segment and finalizes once, when the solve has ended
        state.start = lambda: seg._init_f(state.w0, state.batch)
        state.advance = lambda s: seg._segment_f(s, state.batch)
        no_hvp = np.int32(0)
        state.counters = lambda s: (s.it, s.n_evals, no_hvp, s.reason, s.f)
        state.result = lambda s: seg._final_f(s, state.batch)
        state.programs = {"fe_solve": ["jit_segment_f", "jit_init_f", "jit_final_f"]}
    else:
        raise ValueError(f"unknown solver kind {solver['kind']!r}")
    return state


def step(state: State) -> dict:
    """One timed step, as the program's own driver loop runs it: a solve is
    started where none is in flight, taken one step on, and its counters are
    read back in one transfer (that closes the step); where the solve has
    ended, its result is finalized and waited for, and the next step starts
    again from the seed's start point. The feature passes are counted here,
    in ``work.py``, from iterations, line-search trials and Hessian-vector
    products."""
    import jax

    fresh = state.in_flight is None
    solve = state.in_flight
    if fresh and state.start is not None:
        solve = state.start()
        if state.trials_at_start is None:  # once, in the first step of set-up
            state.trials_at_start = int(state.counters(solve)[1])
    solve = state.advance(solve)
    it, trials, hvp, reason, value = (v.item() for v in jax.device_get(state.counters(solve)))
    it0, trials0 = (0, state.trials_at_start or 0) if fresh else state.before
    state.before = (it, trials)
    ended = state.start is None or reason != 0
    if ended:
        jax.block_until_ready(state.result(solve).x)
    state.in_flight, state.last = (None if ended else solve), solve
    if state.config["solver"]["kind"] == "tron":
        passes = work.tron_passes(it, hvp)
    else:
        passes = work.owlqn_passes(it - it0, trials - trials0, fresh)
    return {"units": it - it0, "passes": passes, "fresh": fresh, "reason": REASONS[reason],
            "ok": bool(np.isfinite(value))}


def observe(state: State, out: dict) -> None:
    """Keep what ``correct`` compares of one of the first steps: the whole
    result as the program finalizes it at that point, read back (host copies:
    the program's state is freed before the reference runs). Set-up only: the
    window reads nothing but the counters."""
    res = state.result(state.last)
    state.first.append(
        {
            "loss": _host(res.loss_history).astype(np.float64),
            "gnorm": _host(res.grad_norm_history).astype(np.float64),
            "x": _host(res.x).astype(np.float64),
            "gradient": _host(res.gradient).astype(np.float64),
            "iterations": int(res.iterations),
            "fresh": out["fresh"],
            "reason": out["reason"],
        }
    )


def release(state: State) -> None:
    """Free the program's state; the reference keeps only the benchmark's
    own inputs."""
    state.batch = state.w0 = state.in_flight = state.last = None
    state.start = state.advance = state.counters = state.result = None


def _objective(config: dict, inputs: dict, precision):
    feat, solver = config["features"], config["solver"]
    loss = {"LINEAR_REGRESSION": "squared", "POISSON_REGRESSION": "poisson"}[config["task"]]
    kept = inputs.setdefault("_ops", {})  # a block is read back and widened once
    key = precision or "f64"
    if key not in kept:
        if feat["kind"] == "dense":
            kept[key] = reference.DenseOps(inputs["features"], precision=key)
        else:
            kept[key] = reference.SparseOps(
                inputs["indices"], inputs["values"], feat["d"], precision=key
            )
    ops = kept[key]
    labels = np.asarray(inputs["labels"], np.float64)
    weights = inputs.get("weights")  # only a planted fault sets them
    if solver["kind"] == "tron":
        return reference.Objective(ops, loss, labels, solver["l2_weight"], weights=weights), 0.0
    lam, alpha = solver["regularization_weight"], solver["elastic_net_alpha"]
    return reference.Objective(ops, loss, labels, (1 - alpha) * lam, weights=weights), alpha * lam


def reference_record(config: dict, inputs: dict, steps: int, precision=None) -> dict:
    """The plain reference over the same inputs: a TRON solve to its own
    stopping rule, or as many OWL-QN iterations as ``steps`` segments hold.
    ``precision="bf16"`` computes it as the control, and adds what the
    control itself holds at its last point (``loss_at_x``)."""
    solver = config["solver"]
    obj, l1 = _objective(config, inputs, precision)
    if solver["kind"] == "tron":
        rec = reference.tron(
            obj, inputs["w0"], max_iterations=solver["max_iterations"],
            tolerance=solver["tolerance"], max_cg=solver["max_cg_iterations"],
            cg_tol=solver["cg_tolerance"],
        )
    else:
        rec = reference.owlqn(
            obj, inputs["w0"], l1,
            max_iterations=solver["max_iterations"], tolerance=solver["tolerance"],
            stop_after=steps * solver["segment_iters"],
        )
    return rec


def reference_at(config: dict, inputs: dict, x, gradient: bool = True) -> dict:
    """The reference's objective (and gradient) at the point ``x`` where the
    program stands after one of its first steps."""
    obj, l1 = _objective(config, inputs, None)
    return reference.evaluate_at(obj, x, l1, gradient=gradient)


def stopping_rule(config: dict) -> dict:
    """What the configuration states of a step's length and a solve's end,
    for ``check.py``: a step is ``segment_iters`` iterations (None: a whole
    solve), a solve ends by ``max_iterations`` at the latest."""
    solver = config["solver"]
    return {"segment_iters": solver.get("segment_iters"),
            "max_iterations": solver["max_iterations"]}
