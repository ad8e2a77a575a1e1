"""Shared optimizer machinery: configs, results, convergence accounting.

Reference parity: photon-lib optimization/Optimizer.scala (convergence logic
:135-156 — absolute tolerances derived from the zero-coefficient state
:67-70,181), OptimizerState.scala:35, OptimizationStatesTracker.scala:33-99,
util/ConvergenceReason.scala:21-37, optimization/OptimizerConfig.scala.

All optimizers here are *functions* compiled into a single XLA while-loop
(no host round-trips per iteration), returning an ``OptimizeResult`` whose
history arrays replace the reference's mutable ``OptimizationStatesTracker``.
Because results are pytrees of fixed shape, the optimizers compose with
``jax.vmap`` (batched per-entity random-effect solves) and ``pjit``
(data-sharded fixed-effect solves) unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.types import Array


class ConvergenceReason(enum.IntEnum):
    """Why the optimizer stopped (reference ConvergenceReason.scala)."""

    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    FUNCTION_VALUES_CONVERGED = 2
    GRADIENT_CONVERGED = 3
    OBJECTIVE_NOT_IMPROVING = 4


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Optimizer hyperparameters.

    Defaults mirror the reference: LBFGS maxIter=100 tol=1e-7 m=10
    (LBFGS.scala:154-156); TRON maxIter=15 tol=1e-5 CG<=20
    (TRON.scala:256-276).
    """

    max_iterations: int = 100
    tolerance: float = 1e-7
    num_corrections: int = 10
    # Box constraints: (lower, upper) arrays broadcastable to the coefficient
    # shape, or None. Reference constraintMap →
    # OptimizationUtils.projectCoefficientsToSubspace.
    lower_bounds: Array | None = None
    upper_bounds: Array | None = None
    # Line search
    ls_max_iterations: int = 25
    ls_c1: float = 1e-4
    ls_c2: float = 0.9
    # TRON specifics
    max_cg_iterations: int = 20
    cg_tolerance: float = 0.1

    def tron_defaults(self) -> "OptimizerConfig":
        return dataclasses.replace(self, max_iterations=15, tolerance=1e-5)


_TRACING = threading.local()  # per thread: programs are traced in a pool too


@contextlib.contextmanager
def one_solve_a_lane():
    """Entered, while tracing, around a ``vmap`` of solves (a random
    effect's bucket: one entity a lane). A solve traced inside does not ask
    whether ITS start point is the zero point: under ``vmap`` that ``cond``
    is a select which runs both branches on every lane, and it picks a
    zero lane's start values from another subgraph than a warm lane's,
    which two programs over the same lanes need not round alike (the
    streamed fit's chunk programs follow the whole-bucket program bit for
    bit). A ``vmap`` without it is still right, through the select."""
    was = getattr(_TRACING, "lanes", False)
    _TRACING.lanes = True
    try:
        yield
    finally:
        _TRACING.lanes = was


def solving_a_lane() -> bool:
    return getattr(_TRACING, "lanes", False)


class DirectionalOracle(NamedTuple):
    """Objective interface for margin-space line searches (minimize_lbfgs).

    ``full(x) -> (f, g, carry)`` — complete evaluation plus an opaque carry
    (a GLM's margins) threaded through iterations.
    ``dir_setup(carry, x, d) -> (phi, accept)`` — pay the per-direction
    cost once; ``phi(alpha) -> (f, dphi, aux)`` is the cheap scalar oracle
    for the Wolfe search, ``accept(alpha) -> (g, carry')`` produces the
    accepted point's gradient and next carry.
    ``at_zero(x) -> (f, g, carry)`` — what ``full(zeros_like(x))`` gives,
    from a model that knows its product with zero is zero: no forward
    pass over the feature block. ``None``: the solve calls ``full``.
    ``full_product(x) -> (f, g, carry, product)`` — ``full`` handing back
    the feature product it made on the way, before anything was added to
    it, for a caller that scores the point next
    (``minimize_lbfgs(keep_product=True)``). ``None``: there is none.
    """

    full: object
    dir_setup: object
    at_zero: object = None
    full_product: object = None


class SmoothMarginOracle(NamedTuple):
    """Objective interface for value-only line-search trials (OWLQN).

    Orthant projection makes the trial point non-affine in the step, so
    OWLQN cannot reuse the DirectionalOracle's cached-margins trick — but
    its Armijo test needs only the VALUE. ``value_margins(x) -> (f, z)``
    is one forward pass; ``grad_from_margins(x, z) -> g`` turns the
    accepted trial's margins into the gradient with one backward pass —
    trials drop from 2 feature passes to 1, and the gradient is paid once
    per iteration. ``full(x) -> (f, g, z)`` for init/box re-evaluations;
    ``at_zero(x) -> (f, g, z)`` is ``full(zeros_like(x))`` without the
    forward pass (see ``DirectionalOracle``), ``None`` where the model has
    no such shortcut.
    """

    full: object
    value_margins: object
    grad_from_margins: object
    at_zero: object = None


class OptimizeResult(NamedTuple):
    """Terminal optimizer state + per-iteration history (fixed shapes).

    ``loss_history[i]`` / ``grad_norm_history[i]`` hold the state after
    iteration i (index 0 = initial state); entries past ``iterations`` are
    padded with the final value.
    """

    x: Array
    value: Array
    gradient: Array
    iterations: Array  # int32 scalar
    reason: Array  # int32 scalar, ConvergenceReason code
    loss_history: Array  # [max_iterations + 1]
    grad_norm_history: Array  # [max_iterations + 1]
    # Exact work counters (for honest FLOP/MFU accounting in benchmarks):
    # objective (value+gradient) evaluations and Hessian-vector products.
    n_evals: Array | int = 0  # int32 scalar
    n_hvp: Array | int = 0  # int32 scalar
    # Feature-block passes actually executed. With a margin-space line
    # search (GLM directional oracle) trials are O(N) elementwise, so
    # n_evals (trial count, reference-comparable) no longer implies
    # 2 passes each; benches must use this for bytes/FLOP accounting.
    # 0 ⇒ not tracked (older paths): assume 2·n_evals + 2·n_hvp.
    # L-BFGS and OWL-QN count what ran: 1 at a start from zero (the
    # backward pass there), 3 at any other start, then every pass of the
    # iterations and 2 for a last exact re-evaluation.
    n_feature_passes: Array | int = 0  # int32 scalar
    # X·x̃ at ``x`` (x̃ the coefficients as the feature block meets them;
    # offsets and margin shift NOT added), as the last exact evaluation
    # made it. Only where the caller asked the solve to keep it.
    product: Array | None = None

    @property
    def converged(self) -> Array:
        return self.reason != ConvergenceReason.NOT_CONVERGED

    def summary(self) -> str:
        it = int(self.iterations)
        reason = ConvergenceReason(int(self.reason)).name
        lines = [
            f"Optimization finished: iterations={it} reason={reason} "
            # phl-ok: PHL002 post-solve convergence report, once per solve behind its barrier
            f"loss={float(self.value):.8g} |grad|={float(jnp.linalg.norm(self.gradient)):.4g}",
            f"{'iter':>5} {'loss':>16} {'|grad|':>12}",
        ]
        lh = np.asarray(self.loss_history)  # phl-ok: PHL002 post-solve report read-back
        gh = np.asarray(self.grad_norm_history)  # phl-ok: PHL002 post-solve report read-back
        for i in range(min(it + 1, lh.shape[0])):
            lines.append(f"{i:>5} {lh[i]:>16.8g} {gh[i]:>12.4g}")
        return "\n".join(lines)


def record_optimize_metrics(
    result: OptimizeResult, prefix: str = "optimize"
) -> None:
    """Feed an OptimizeResult's exact work counters into the telemetry
    registry (``optimize.iterations`` / ``.n_evals`` / ``.n_hvp`` /
    ``.n_feature_passes`` — the line-search/inner-loop accounting the
    spans cannot see because the loops run inside one XLA program).
    No-op while telemetry is disabled, and safe on traced results: a
    counter that is not concrete (called under jit) records nothing
    rather than tracing a read-back into the program."""
    from photon_tpu import obs

    if not obs.enabled():
        return
    for name in ("iterations", "n_evals", "n_hvp", "n_feature_passes"):
        v = getattr(result, name)
        try:
            obs.counter(f"{prefix}.{name}", int(v))
        except (TypeError, jax.errors.TracerArrayConversionError):
            return  # traced → whole result is traced; nothing to record


def project_to_box(
    x: Array, lower: Array | None, upper: Array | None
) -> Array:
    """Clamp coefficients into box constraints (reference
    OptimizationUtils.projectCoefficientsToSubspace, applied after every
    optimizer step, LBFGS.scala:72). Bounds are cast to the coefficient
    dtype so float64 bound arrays never promote a float32 solve."""
    if lower is not None:
        x = jnp.maximum(x, jnp.asarray(lower, dtype=x.dtype))
    if upper is not None:
        x = jnp.minimum(x, jnp.asarray(upper, dtype=x.dtype))
    return x


def convergence_check(
    *,
    it: Array,
    value: Array,
    prev_value: Array,
    grad_norm: Array,
    loss_abs_tol: Array,
    grad_abs_tol: Array,
    max_iterations: int,
    step_failed: Array,
) -> Array:
    """Reference Optimizer.getConvergenceReason:135-156 as one expression.

    Order matters: max-iter > not-improving > function-values > gradient.
    Returns an int32 ConvergenceReason code (0 = keep going).
    """
    reason = jnp.where(
        it >= max_iterations,
        ConvergenceReason.MAX_ITERATIONS,
        jnp.where(
            step_failed,
            ConvergenceReason.OBJECTIVE_NOT_IMPROVING,
            jnp.where(
                jnp.abs(value - prev_value) <= loss_abs_tol,
                ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                jnp.where(
                    grad_norm <= grad_abs_tol,
                    ConvergenceReason.GRADIENT_CONVERGED,
                    ConvergenceReason.NOT_CONVERGED,
                ),
            ),
        ),
    )
    return reason.astype(jnp.int32)
