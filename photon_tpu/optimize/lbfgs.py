"""L-BFGS as one jit-compiled XLA while-loop.

TPU-native replacement for the reference's Breeze-backed LBFGS
(optimization/LBFGS.scala:59-156): two-loop recursion over a fixed-size
(S, Y) history kept newest pair first, strong-Wolfe line search
(optimize/linesearch.py), optional box-constraint projection after every
step (reference OptimizationUtils.projectCoefficientsToSubspace via
LBFGS.scala:72 — this also serves as the LBFGSB variant), and the reference
Optimizer's convergence accounting (Optimizer.scala:135-156: absolute
tolerances scaled off the zero-coefficient state).

The whole optimize runs on device with no host round-trips, so it can be
``vmap``-ped over thousands of per-entity random-effect problems (each lane
converges independently; finished lanes no-op via the shared while-loop
condition) and ``pjit``-ed over a sharded batch for the fixed-effect solve,
where XLA turns the gradient reductions into psum over ICI.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp
from jax import lax

from photon_tpu.obs.scopes import scope
from photon_tpu.optimize.common import (
    ConvergenceReason,
    DirectionalOracle,
    OptimizeResult,
    OptimizerConfig,
    convergence_check,
    project_to_box,
    solving_a_lane,
)
from photon_tpu.optimize.linesearch import (
    wolfe_line_search,
    wolfe_search_phi,
)
from photon_tpu.types import Array

_CURVATURE_EPS = 1e-10


class _LBFGSState(NamedTuple):
    it: Array
    x: Array
    f: Array
    g: Array
    prev_f: Array
    s_hist: Array  # [m, D], newest pair first
    y_hist: Array  # [m, D]
    rho: Array  # [m]
    num_pairs: Array
    reason: Array
    loss_hist: Array
    gnorm_hist: Array
    n_evals: Array
    n_passes: Array
    carry: object  # DirectionalOracle state (GLM margins), () otherwise


def two_loop_direction(
    g: Array,
    s_hist: Array,
    y_hist: Array,
    rho: Array,
    num_pairs: Array,
    pos: Array,
) -> Array:
    """Two-loop recursion: approximates -H·g from the (s, y) history.

    Fixed m iterations with validity masks so the shapes are static; the
    initial Hessian scale is γ = s·y / y·y of the newest pair (Nocedal 7.20).
    The history is circular and ``pos`` its write index (OWL-QN's; L-BFGS
    keeps its own newest first, ``_two_loop_newest_first``).
    """
    m = s_hist.shape[0]
    n_valid = jnp.minimum(num_pairs, m)

    def newest_to_oldest(j):
        return (pos - 1 - j) % m

    def first_loop(j, carry):
        q, alphas = carry
        idx = newest_to_oldest(j)
        valid = j < n_valid
        alpha = jnp.where(valid, rho[idx] * jnp.dot(s_hist[idx], q), 0.0)
        q = q - alpha * y_hist[idx]
        return q, alphas.at[j].set(alpha)

    q, alphas = lax.fori_loop(
        0, m, first_loop, (g, jnp.zeros((m,), dtype=g.dtype))
    )

    newest = (pos - 1) % m
    sy = jnp.dot(s_hist[newest], y_hist[newest])
    yy = jnp.dot(y_hist[newest], y_hist[newest])
    gamma = jnp.where((n_valid > 0) & (yy > 0), sy / jnp.where(yy > 0, yy, 1.0), 1.0)
    r = gamma * q

    def second_loop(jj, r):
        j = m - 1 - jj
        idx = newest_to_oldest(j)
        valid = j < n_valid
        beta = jnp.where(valid, rho[idx] * jnp.dot(y_hist[idx], r), 0.0)
        return r + s_hist[idx] * (alphas[j] - beta)

    r = lax.fori_loop(0, m, second_loop, r)
    return -r


def _two_loop_newest_first(
    g: Array, s_hist: Array, y_hist: Array, rho: Array, num_pairs: Array
) -> Array:
    """The same recursion over a history kept newest pair first: slot j is
    read by the loop counter alone. Under ``vmap`` a per-lane write index
    turns every read of a circular history into a gather (the 59 KB an
    entity of PERF.md, PR 31); a shifted one has none."""
    m = s_hist.shape[0]

    def first_loop(j, carry):
        q, alphas = carry
        alpha = jnp.where(j < num_pairs, rho[j] * jnp.dot(s_hist[j], q), 0.0)
        return q - alpha * y_hist[j], alphas.at[j].set(alpha)

    q, alphas = lax.fori_loop(
        0, m, first_loop, (g, jnp.zeros((m,), dtype=g.dtype))
    )
    sy = jnp.dot(s_hist[0], y_hist[0])
    yy = jnp.dot(y_hist[0], y_hist[0])
    gamma = jnp.where((num_pairs > 0) & (yy > 0), sy / jnp.where(yy > 0, yy, 1.0), 1.0)

    def second_loop(jj, r):
        j = m - 1 - jj
        beta = jnp.where(j < num_pairs, rho[j] * jnp.dot(y_hist[j], r), 0.0)
        return r + s_hist[j] * (alphas[j] - beta)

    return -lax.fori_loop(0, m, second_loop, gamma * q)


def evaluate_start(eval_at, at_zero, x_init: Array, dtype):
    """A solve's two boundary evaluations, L-BFGS's and OWL-QN's alike:
    ``(f, g, carry)`` at ``x_init``, ``(f, g)`` at zero, and the feature
    passes that ran, an int32 scalar.

    ``at_zero`` (an oracle's; ``None`` for a black box) evaluates the zero
    point with one backward pass, and where every value of ``x_init`` is
    zero that evaluation is the start point's as well: 1 pass; 3 from any
    other start. The test is made in the program, on the values. One solve
    a lane (``common.one_solve_a_lane``) evaluates the start outright, as
    the select would on every lane: 3 passes. Without ``at_zero`` both
    points go through ``eval_at``: 4 passes."""
    if at_zero is None:
        f_zero, g_zero, _ = eval_at(jnp.zeros_like(x_init))
        f0, g0, carry0 = eval_at(x_init)
        return f0, g0, carry0, f_zero, g_zero, jnp.asarray(4, jnp.int32)
    f_zero, g_zero, carry_zero = at_zero(x_init)
    f_zero, g_zero = f_zero.astype(dtype), g_zero.astype(dtype)
    if solving_a_lane():
        f0, g0, carry0 = eval_at(x_init)
        return f0, g0, carry0, f_zero, g_zero, jnp.asarray(3, jnp.int32)
    moved = jnp.any(x_init != 0)
    f0, g0, carry0 = lax.cond(
        moved, lambda: eval_at(x_init), lambda: (f_zero, g_zero, carry_zero)
    )
    passes = jnp.where(moved, 3, 1).astype(jnp.int32)
    return f0, g0, carry0, f_zero, g_zero, passes


def minimize_lbfgs(
    value_and_grad: Callable[[Array], tuple[Array, Array]] | None,
    x0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    *,
    oracle: DirectionalOracle | None = None,
    keep_product: bool = False,
) -> OptimizeResult:
    """Minimize a smooth objective with L-BFGS.

    ``value_and_grad(x) -> (f, g)`` must be a pure jnp function. Returns an
    ``OptimizeResult`` pytree with fixed shapes (jit/vmap-stable).

    ``oracle`` (a DirectionalOracle) switches the line search to the
    margin-space form: trials cost O(N) elementwise on carried state
    instead of full objective evaluations, and each iteration pays exactly
    one forward (direction margins) + one backward (accepted gradient)
    feature pass. ``n_evals`` still counts line-search trials (the
    reference-comparable number); ``n_feature_passes`` counts real passes.

    The boundary evaluations reuse what the solve holds. An oracle with
    ``at_zero`` gives the zero point's value and gradient (the absolute
    tolerances' scale) from one backward pass, and a start point whose
    every value is zero IS that point: it is not evaluated again. That is
    decided in the program from ``x0``'s values, so zeros handed to a
    jitted caller as an argument take it too; under ``vmap`` both branches
    run (``evaluate_start``). ``keep_product=True`` hands out the feature
    product of the last exact re-evaluation (``OptimizeResult.product``)
    where the oracle has ``full_product`` and the re-evaluation is made.
    """
    dtype = x0.dtype
    d = x0.shape[-1]
    t = config.max_iterations
    # a solve of t iterations stores at most t pairs: slots past that are
    # never valid, and each costs every lane of a vmapped solve its bytes
    m = max(1, min(config.num_corrections, t))
    has_box = config.lower_bounds is not None or config.upper_bounds is not None

    if oracle is None:
        if value_and_grad is None:
            raise ValueError("need value_and_grad or oracle")

        def _full(x):
            f, g = value_and_grad(x)
            return f, g, ()

        oracle = DirectionalOracle(full=_full, dir_setup=None)
    elif value_and_grad is not None:
        # a silent winner would mask an objective mismatch between the two
        raise ValueError("pass value_and_grad=None when oracle is given")

    def eval_at(x):
        f, g, carry = oracle.full(x)
        return f.astype(dtype), g.astype(dtype), carry

    # Absolute tolerances from the zero-coefficient state (Optimizer.scala:181).
    x_init = project_to_box(x0, config.lower_bounds, config.upper_bounds)
    f0, g0, carry0, f_zero, g_zero, start_passes = evaluate_start(
        eval_at, oracle.at_zero, x_init, dtype
    )
    loss_abs_tol = jnp.abs(f_zero) * config.tolerance
    grad_abs_tol = jnp.linalg.norm(g_zero) * config.tolerance

    init = _LBFGSState(
        it=jnp.zeros((), jnp.int32),
        x=x_init,
        f=f0,
        g=g0,
        prev_f=jnp.asarray(jnp.inf, dtype),
        s_hist=jnp.zeros((m, d), dtype),
        y_hist=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        num_pairs=jnp.zeros((), jnp.int32),
        reason=jnp.zeros((), jnp.int32),
        loss_hist=jnp.full((t + 1,), f0, dtype),
        gnorm_hist=jnp.full((t + 1,), jnp.linalg.norm(g0), dtype),
        n_evals=jnp.asarray(2, jnp.int32),  # zero-state + initial point
        n_passes=start_passes,
        carry=carry0,
    )

    def cond(s: _LBFGSState):
        return s.reason == ConvergenceReason.NOT_CONVERGED

    def body(s: _LBFGSState) -> _LBFGSState:
        with scope("photon.lbfgs.direction"):
            direction = _two_loop_newest_first(
                s.g, s.s_hist, s.y_hist, s.rho, s.num_pairs
            )
            # Guard: if the direction is not a descent direction (numerics), fall
            # back to steepest descent.
            descent = jnp.dot(direction, s.g) < 0
            direction = jnp.where(descent, direction, -s.g)

            gnorm = jnp.linalg.norm(s.g)
            first = s.num_pairs == 0
            init_step = jnp.where(
                first, jnp.minimum(1.0, 1.0 / jnp.maximum(gnorm, 1e-12)), 1.0
            ).astype(dtype)

        with scope("photon.lbfgs.linesearch"):
            if oracle.dir_setup is None:
                ls = wolfe_line_search(
                    lambda x: eval_at(x)[:2],
                    s.x,
                    direction,
                    s.f,
                    s.g,
                    initial_step=init_step,
                    c1=config.ls_c1,
                    c2=config.ls_c2,
                    max_iterations=config.ls_max_iterations,
                )
                x_new, f_new, g_new = ls.x, ls.value, ls.gradient
                carry_new = s.carry
                num_trials = ls.num_evals
                passes = 2 * ls.num_evals
            else:
                phi, accept = oracle.dir_setup(s.carry, s.x, direction)
                res = wolfe_search_phi(
                    phi,
                    s.f,
                    jnp.dot(s.g, direction),
                    (),
                    dtype=dtype,
                    initial_step=init_step,
                    c1=config.ls_c1,
                    c2=config.ls_c2,
                    max_iterations=config.ls_max_iterations,
                )
                x_new = s.x + res.step * direction
                f_new = res.value
                if has_box:
                    # the box path fully re-evaluates at the projected point
                    # below — don't pay accept()'s backward pass to discard it
                    g_new, carry_new = s.g, s.carry
                    passes = jnp.asarray(1, jnp.int32)  # direction margins
                else:
                    g_new, carry_new = accept(res.step)
                    g_new = g_new.astype(dtype)
                    # one forward (direction margins) + one backward (gradient)
                    passes = jnp.asarray(2, jnp.int32)
                num_trials = res.num_evals
                ls = res  # for .success below
            n_evals = s.n_evals + num_trials
            n_passes = s.n_passes + passes
            if has_box:
                x_proj = project_to_box(x_new, config.lower_bounds, config.upper_bounds)
                f_new, g_new, carry_new = eval_at(x_proj)
                x_new = x_proj
                n_evals = n_evals + 1
                n_passes = n_passes + 2

            step_failed = ~ls.success

        with scope("photon.lbfgs.history"):
            # Curvature pair update
            s_vec = x_new - s.x
            y_vec = g_new - s.g
            sy = jnp.dot(s_vec, y_vec)
            accept = sy > _CURVATURE_EPS
            # newest pair first: an accepted pair shifts the rest down one
            # slot and the oldest falls off, so no slot is ever addressed by
            # a per-lane index
            def pushed(hist, row):
                return jnp.where(
                    accept, jnp.concatenate([row[None], hist[:-1]]), hist
                )

            s_hist = pushed(s.s_hist, s_vec)
            y_hist = pushed(s.y_hist, y_vec)
            rho = pushed(s.rho, 1.0 / jnp.where(accept, sy, 1.0))
            num_pairs = jnp.where(accept, s.num_pairs + 1, s.num_pairs)

            it = s.it + 1
            gnorm_new = jnp.linalg.norm(g_new)
            reason = convergence_check(
                it=it,
                value=f_new,
                prev_value=s.f,
                grad_norm=gnorm_new,
                loss_abs_tol=loss_abs_tol,
                grad_abs_tol=grad_abs_tol,
                max_iterations=t,
                step_failed=step_failed,
            )

            return _LBFGSState(
                it=it,
                x=x_new,
                f=f_new,
                g=g_new,
                prev_f=s.f,
                s_hist=s_hist,
                y_hist=y_hist,
                rho=rho,
                num_pairs=num_pairs,
                reason=reason,
                loss_hist=s.loss_hist.at[it].set(f_new),
                gnorm_hist=s.gnorm_hist.at[it].set(gnorm_new),
                n_evals=n_evals,
                n_passes=n_passes,
                carry=carry_new,
            )

    s = lax.while_loop(cond, body, init)

    f_final, g_final, product = s.f, s.g, None
    n_evals, n_passes = s.n_evals, s.n_passes
    if oracle.dir_setup is not None and not has_box:
        # (the box path re-evaluates at the projected point every
        # iteration, so its carried values are already exact)
        # The margin-space accept path never recomputes margins from x —
        # the carry is z_next = z + α·z_d for the whole run, so f32
        # rounding drift accumulates with iteration count. One exact
        # re-evaluation at the final point bounds what downstream
        # consumers (λ-grid model selection, variance, trackers) see;
        # in-loop convergence still runs on carried values, whose drift
        # (~√iters·eps relative) sits far below practical tolerances.
        # This stays OUTSIDE the while-loop body on purpose: an in-loop
        # periodic lax.cond refresh degrades to select under vmap and
        # would charge every per-entity lane the full evaluation every
        # iteration.
        if keep_product and oracle.full_product is not None:
            f_final, g_final, _, product = oracle.full_product(s.x)
            f_final, g_final = f_final.astype(dtype), g_final.astype(dtype)
        else:
            f_final, g_final, _ = eval_at(s.x)
        n_evals = n_evals + 1
        n_passes = n_passes + 2

    # Pad history tails with the final value so downstream consumers can
    # treat the arrays as fully populated; the last populated entry is
    # also overwritten with the exact refreshed value so
    # loss_history[iterations] == value.
    idx = jnp.arange(t + 1)
    loss_hist = jnp.where(idx < s.it, s.loss_hist, f_final)
    gnorm_hist = jnp.where(
        idx < s.it, s.gnorm_hist, jnp.linalg.norm(g_final)
    )

    return OptimizeResult(
        x=s.x,
        value=f_final,
        gradient=g_final,
        iterations=s.it,
        reason=s.reason,
        loss_history=loss_hist,
        grad_norm_history=gnorm_hist,
        n_evals=n_evals,
        n_hvp=jnp.zeros((), jnp.int32),
        n_feature_passes=n_passes,
        product=product,
    )
