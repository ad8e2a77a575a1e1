"""TRON: trust-region Newton with truncated conjugate gradient.

TPU-native re-implementation of the LIBLINEAR algorithm the reference uses
(optimization/TRON.scala:152-339: outer trust-region loop with η/σ radius
update rules, inner truncated CG with MAX_CG_ITERATIONS=20 solving the TR
subproblem via Hessian-vector products). The Hv products come from the GLM
objective's fused forward+backward matmul (ops/objective.py
``hessian_vector``) — under pjit each CG step is one XLA program with a psum,
the analogue of the reference's per-CG-step ``treeAggregate``
(HessianVectorAggregator.scala:143-149).

Defaults per the reference: max_iterations=15, tolerance=1e-5
(TRON.scala:256-276).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp
from jax import lax

from photon_tpu.obs.scopes import scope
from photon_tpu.optimize.common import (
    ConvergenceReason,
    OptimizeResult,
    OptimizerConfig,
    convergence_check,
    project_to_box,
)
from photon_tpu.types import Array

# Trust-region update constants (TRON.scala:97-98, same as LIBLINEAR).
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


class _CGState(NamedTuple):
    i: Array
    d: Array
    r: Array
    p: Array
    rtr: Array
    hit_boundary: Array
    done: Array


def _truncated_cg(
    hvp: Callable[[Array], Array],
    g: Array,
    delta: Array,
    *,
    max_iterations: int,
    tolerance: float,
) -> tuple[Array, Array, Array]:
    """Solve min_d g·d + d·H·d/2 s.t. ‖d‖ ≤ delta, approximately.

    Returns (d, r, n_hvp) with r the final residual -g - H·d and n_hvp the
    number of Hessian-vector products spent
    (TRON.truncatedConjugateGradientMethod, TRON.scala:278-339).
    """
    dtype = g.dtype
    cg_tol = tolerance * jnp.linalg.norm(g)

    r0 = -g
    init = _CGState(
        i=jnp.zeros((), jnp.int32),
        d=jnp.zeros_like(g),
        r=r0,
        p=r0,
        rtr=jnp.dot(r0, r0),
        hit_boundary=jnp.zeros((), bool),
        done=jnp.zeros((), bool),
    )

    def cond(s: _CGState):
        return (~s.done) & (s.i < max_iterations) & (jnp.sqrt(s.rtr) > cg_tol)

    def body(s: _CGState) -> _CGState:
        hp = hvp(s.p)
        php = jnp.dot(s.p, hp)
        # Guard against non-positive curvature (shouldn't happen for convex
        # GLM losses, but keeps the loop total).
        alpha = s.rtr / jnp.where(php > 0, php, 1.0)
        alpha = jnp.where(php > 0, alpha, 0.0)
        d_new = s.d + alpha * s.p

        exceeded = (jnp.linalg.norm(d_new) > delta) | (php <= 0)

        # Backtrack to the trust-region boundary along p.
        d_in = s.d
        std = jnp.dot(d_in, s.p)
        dd = jnp.dot(d_in, d_in)
        pp = jnp.dot(s.p, s.p)
        dsq = delta * delta
        rad = jnp.sqrt(jnp.maximum(std * std + pp * (dsq - dd), 0.0))
        alpha_b = jnp.where(
            std >= 0,
            (dsq - dd) / jnp.where(std + rad > 0, std + rad, 1.0),
            (rad - std) / jnp.where(pp > 0, pp, 1.0),
        )
        d_bound = d_in + alpha_b * s.p
        r_bound = s.r - alpha_b * hp

        alpha_eff = jnp.where(exceeded, alpha_b, alpha)
        d_next = jnp.where(exceeded, d_bound, d_new)
        r_next = jnp.where(exceeded, r_bound, s.r - alpha * hp)

        rtr_new = jnp.dot(r_next, r_next)
        beta = rtr_new / jnp.where(s.rtr > 0, s.rtr, 1.0)
        p_next = jnp.where(exceeded, s.p, r_next + beta * s.p)

        del alpha_eff
        return _CGState(
            i=s.i + 1,
            d=d_next,
            r=r_next,
            p=p_next,
            rtr=rtr_new,
            hit_boundary=s.hit_boundary | exceeded,
            done=s.done | exceeded,
        )

    s = lax.while_loop(cond, body, init)
    return s.d, s.r, s.i


class _TronState(NamedTuple):
    it: Array
    x: Array
    f: Array
    g: Array
    delta: Array
    reason: Array
    loss_hist: Array
    gnorm_hist: Array
    n_evals: Array
    n_hvp: Array


def minimize_tron(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    hvp: Callable[[Array, Array], Array] | None,
    x0: Array,
    config: OptimizerConfig | None = None,
    *,
    hvp_factory: Callable[[Array], Callable[[Array], Array]] | None = None,
) -> OptimizeResult:
    """Minimize a twice-differentiable objective with trust-region Newton.

    ``hvp(x, v)`` returns H(x)·v. ``hvp_factory(x)`` (preferred when the
    curvature has reusable per-center state) returns an H(x)·v closure; it
    is invoked ONCE per outer iteration, so a GLM's loss-curvature pass
    (margins + d2 — one full read of the [N, D] block) is paid once per
    trust-region step instead of once per CG iteration (the reference pays
    it per Hv too: HessianVectorAggregator recomputes margins every call,
    HessianVectorAggregator.scala:143-149 — up to 20 CG steps per outer
    iteration, TRON.scala:278-339). Config defaults to the reference TRON
    envelope (maxIter=15, tol=1e-5, CG ≤ 20).
    """
    if config is None:
        config = OptimizerConfig().tron_defaults()
    factory_provided = hvp_factory is not None
    if hvp_factory is None:
        if hvp is None:
            raise ValueError("need hvp or hvp_factory")

        def hvp_factory(x):
            return lambda v: hvp(x, v)
    elif hvp is not None:
        # a silent winner would mask a curvature mismatch between the two
        raise ValueError("pass hvp=None when hvp_factory is given")
    dtype = x0.dtype
    t = config.max_iterations
    has_box = config.lower_bounds is not None or config.upper_bounds is not None
    if has_box:
        x0 = project_to_box(x0, config.lower_bounds, config.upper_bounds)

    def eval_at(x):
        f, g = value_and_grad(x)
        return f.astype(dtype), g.astype(dtype)

    f_zero, g_zero = eval_at(jnp.zeros_like(x0))
    loss_abs_tol = jnp.abs(f_zero) * config.tolerance
    grad_abs_tol = jnp.linalg.norm(g_zero) * config.tolerance

    f0, g0 = eval_at(x0)
    gnorm0 = jnp.linalg.norm(g0)

    init = _TronState(
        it=jnp.zeros((), jnp.int32),
        x=x0,
        f=f0,
        g=g0,
        delta=gnorm0,
        reason=jnp.zeros((), jnp.int32),
        loss_hist=jnp.full((t + 1,), f0, dtype),
        gnorm_hist=jnp.full((t + 1,), gnorm0, dtype),
        n_evals=jnp.asarray(2, jnp.int32),  # zero-state + initial point
        n_hvp=jnp.zeros((), jnp.int32),
    )

    def cond(s: _TronState):
        return s.reason == ConvergenceReason.NOT_CONVERGED

    def body(s: _TronState) -> _TronState:
        with scope("photon.tron.cg"):
            step, r, cg_iters = _truncated_cg(
                hvp_factory(s.x),
                s.g,
                s.delta,
                max_iterations=config.max_cg_iterations,
                tolerance=config.cg_tolerance,
            )
        with scope("photon.tron.step"):
            snorm = jnp.linalg.norm(step)
            gs = jnp.dot(s.g, step)
            prered = -0.5 * (gs - jnp.dot(step, r))

            x_cand = s.x + step
            if has_box:
                # project into the box after the optimization step (reference
                # TRON.scala:226-228) and evaluate at the projected point
                x_cand = project_to_box(
                    x_cand, config.lower_bounds, config.upper_bounds
                )
            f_new, g_new = eval_at(x_cand)
            actred = s.f - f_new

            # Radius update (TRON.scala:152-251 / LIBLINEAR tron.cpp).
            denom = f_new - s.f - gs
            alpha = jnp.where(
                denom <= 0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * (gs / jnp.where(denom == 0, 1.0, denom)))
            )
            first = s.it == 0
            delta = jnp.where(first, jnp.minimum(s.delta, snorm), s.delta)
            delta = jnp.where(
                actred < _ETA0 * prered,
                jnp.minimum(jnp.maximum(alpha, _SIGMA1) * snorm, _SIGMA2 * delta),
                jnp.where(
                    actred < _ETA1 * prered,
                    jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha * snorm, _SIGMA2 * delta)),
                    jnp.where(
                        actred < _ETA2 * prered,
                        jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                        jnp.maximum(delta, jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                    ),
                ),
            )

            accept = actred > _ETA0 * prered
            x_out = jnp.where(accept, x_cand, s.x)
            f_out = jnp.where(accept, f_new, s.f)
            g_out = jnp.where(accept, g_new, s.g)

            it = s.it + 1
            gnorm_out = jnp.linalg.norm(g_out)
            reason = convergence_check(
                it=it,
                value=f_out,
                prev_value=s.f,
                grad_norm=gnorm_out,
                loss_abs_tol=loss_abs_tol,
                grad_abs_tol=grad_abs_tol,
                max_iterations=t,
                # A rejected step with a tiny radius cannot make progress.
                step_failed=(~accept) & (delta <= 1e-12),
            )
            # A rejected step leaves the loss unchanged; don't let the
            # function-values test fire on a rejection (reference keeps iterating
            # with a shrunken radius).
            reason = jnp.where(
                (~accept)
                & (reason == ConvergenceReason.FUNCTION_VALUES_CONVERGED),
                ConvergenceReason.NOT_CONVERGED,
                reason,
            ).astype(jnp.int32)

            return _TronState(
                it=it,
                x=x_out,
                f=f_out,
                g=g_out,
                delta=delta,
                reason=reason,
                loss_hist=s.loss_hist.at[it].set(f_out),
                gnorm_hist=s.gnorm_hist.at[it].set(gnorm_out),
                n_evals=s.n_evals + 1,
                n_hvp=s.n_hvp + cg_iters,
            )

    s = lax.while_loop(cond, body, init)

    idx = jnp.arange(t + 1)
    loss_hist = jnp.where(idx <= s.it, s.loss_hist, s.f)
    gnorm_hist = jnp.where(idx <= s.it, s.gnorm_hist, jnp.linalg.norm(s.g))

    return OptimizeResult(
        x=s.x,
        value=s.f,
        gradient=s.g,
        iterations=s.it,
        reason=s.reason,
        loss_history=loss_hist,
        grad_norm_history=gnorm_hist,
        n_evals=s.n_evals,
        n_hvp=s.n_hvp,
        # with a GLM hvp_factory: 2 passes/eval + 2/Hv + the once-per-outer-
        # iteration curvature pass the factory hoists out of the CG loop.
        # Unknown for a black-box hvp (left 0 = "not tracked").
        n_feature_passes=(
            2 * s.n_evals + 2 * s.n_hvp + s.it
            if factory_provided
            else jnp.zeros((), jnp.int32)
        ),
    )
