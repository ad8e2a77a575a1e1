"""Strong-Wolfe line search as a single lax.while_loop state machine.

Replaces Breeze's StrongWolfeLineSearch (the reference reaches it through
breeze.optimize.LBFGS, optimization/LBFGS.scala:84). One objective evaluation
per loop iteration; a bracketing stage expands the step until the minimum is
bracketed, then a zoom stage shrinks the bracket with safeguarded quadratic
interpolation. Runs entirely on device, so it vmaps across thousands of
per-entity solves (each lane keeps its own bracket).

Where a value lies within rounding of ``f0`` (``_VALUE_NOISE_EPS`` machine
epsilons of its size) the sufficient-decrease test cannot tell a decrease
from none: the float32 sum of a loss over 4 M rows resolves 0.25 where an
iteration near the optimum gains less, and a strict test then fails on the
last bit, every trial of the search with it, and ends the solve by
rounding (PERF.md, PR 31). Such a value counts as no increase, and the
derivative, which has its own rounding and not the value's, decides: the
approximate Wolfe conditions of Hager and Zhang (SIAM J. Optim. 16, 2005).
A departure from Breeze, which tests the value alone.

Two entry points share the state machine:

- ``wolfe_search_phi`` — the core, driven by a SCALAR oracle
  ``phi(alpha) -> (value, directional_derivative, aux)``. The aux pytree
  rides along so the caller gets back whatever it needs at the accepted
  step (the full gradient for black-box objectives; nothing for GLM
  margin-space searches, where each trial is O(N) elementwise on cached
  margins instead of two feature-block passes — see
  ops/objective.GLMObjective.directional_oracle).
- ``wolfe_line_search`` — the black-box wrapper: phi evaluates
  ``value_and_grad(x0 + alpha*direction)`` and aux carries the gradient.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.types import Array

#: a trial whose value is within this many machine epsilons of |f0| from f0
#: is held to have left the value as it was (the module's docstring)
_VALUE_NOISE_EPS = 4.0


class LineSearchResult(NamedTuple):
    step: Array  # accepted step length (scalar)
    x: Array  # x0 + step * direction
    value: Array
    gradient: Array
    success: Array  # bool: strong Wolfe satisfied (else best Armijo point)
    num_evals: Array


class PhiSearchResult(NamedTuple):
    """Result of the scalar-oracle search (``wolfe_search_phi``)."""

    step: Array
    value: Array
    aux: object  # pytree returned by phi at the accepted step
    success: Array
    num_evals: Array


class _State(NamedTuple):
    i: Array
    stage: Array  # 0 = bracketing, 1 = zoom
    done: Array
    # candidate to evaluate next
    alpha: Array
    # previous bracketing point
    a_prev: Array
    phi_prev: Array
    dphi_prev: Array
    # zoom bracket
    a_lo: Array
    phi_lo: Array
    dphi_lo: Array
    a_hi: Array
    phi_hi: Array
    # accepted point
    a_star: Array
    phi_star: Array
    aux_star: object
    success: Array
    # best Armijo-satisfying point seen (fallback)
    a_best: Array
    phi_best: Array
    aux_best: object
    has_best: Array


def _interp(a_lo, phi_lo, dphi_lo, a_hi, phi_hi):
    """Safeguarded quadratic interpolation min inside [a_lo, a_hi]."""
    d = a_hi - a_lo
    denom = phi_hi - phi_lo - dphi_lo * d
    quad = a_lo - 0.5 * dphi_lo * d * d / jnp.where(denom == 0.0, 1.0, denom)
    bisect = a_lo + 0.5 * d
    lo = jnp.minimum(a_lo, a_hi)
    hi = jnp.maximum(a_lo, a_hi)
    margin = 0.1 * (hi - lo)
    bad = (denom == 0.0) | (quad < lo + margin) | (quad > hi - margin) | ~jnp.isfinite(quad)
    return jnp.where(bad, bisect, quad)


def _sel(cond, a, b):
    """Elementwise pytree select."""
    return jax.tree_util.tree_map(lambda x, y: jnp.where(cond, x, y), a, b)


def wolfe_search_phi(
    phi: Callable[[Array], tuple[Array, Array, object]],
    f0: Array,
    dphi0: Array,
    aux0: object,
    *,
    dtype,
    initial_step: Array | float = 1.0,
    c1: float = 1e-4,
    c2: float = 0.9,
    max_iterations: int = 25,
    expansion: float = 2.0,
) -> PhiSearchResult:
    """Strong-Wolfe search on the scalar oracle ``phi``.

    On failure (no Wolfe point within the evaluation budget) the best
    Armijo point seen is returned with ``success=False``; if none exists,
    step 0 (no movement, ``aux0`` returned).
    """
    f0 = f0.astype(dtype)
    dphi0 = dphi0.astype(dtype)
    zero = jnp.zeros((), dtype)
    noise = _VALUE_NOISE_EPS * jnp.finfo(dtype).eps * jnp.abs(f0)

    init = _State(
        i=jnp.zeros((), jnp.int32),
        stage=jnp.zeros((), jnp.int32),
        done=jnp.zeros((), bool),
        alpha=jnp.asarray(initial_step, dtype),
        a_prev=zero,
        phi_prev=f0,
        dphi_prev=dphi0,
        a_lo=zero,
        phi_lo=f0,
        dphi_lo=dphi0,
        a_hi=zero,
        phi_hi=f0,
        a_star=zero,
        phi_star=f0,
        aux_star=aux0,
        success=jnp.zeros((), bool),
        a_best=zero,
        phi_best=f0,
        aux_best=aux0,
        has_best=jnp.zeros((), bool),
    )

    def cond(s: _State):
        return (~s.done) & (s.i < max_iterations)

    def body(s: _State) -> _State:
        in_zoom = s.stage == 1
        alpha = jnp.where(
            in_zoom, _interp(s.a_lo, s.phi_lo, s.dphi_lo, s.a_hi, s.phi_hi), s.alpha
        )
        f, dphi, aux = phi(alpha)
        f = f.astype(dtype)
        dphi = dphi.astype(dtype)
        armijo = (f <= f0 + c1 * alpha * dphi0) | (jnp.abs(f - f0) <= noise)
        curv = jnp.abs(dphi) <= -c2 * dphi0
        wolfe = armijo & curv

        # track the best Armijo point as a fallback
        better = armijo & ((~s.has_best) | (f < s.phi_best))
        a_best = jnp.where(better, alpha, s.a_best)
        phi_best = jnp.where(better, f, s.phi_best)
        aux_best = _sel(better, aux, s.aux_best)
        has_best = s.has_best | better

        # ---- bracketing stage transitions --------------------------------
        br_to_zoom_hi = (~armijo) | ((s.i > 0) & (f >= s.phi_prev))
        br_to_zoom_rev = armijo & (dphi >= 0.0) & ~br_to_zoom_hi
        br_done = wolfe & ~br_to_zoom_hi
        # zoom bracket produced by the bracketing stage
        br_a_lo = jnp.where(br_to_zoom_hi, s.a_prev, alpha)
        br_phi_lo = jnp.where(br_to_zoom_hi, s.phi_prev, f)
        br_dphi_lo = jnp.where(br_to_zoom_hi, s.dphi_prev, dphi)
        br_a_hi = jnp.where(br_to_zoom_hi, alpha, s.a_prev)
        br_phi_hi = jnp.where(br_to_zoom_hi, f, s.phi_prev)
        enter_zoom = (br_to_zoom_hi | br_to_zoom_rev) & ~br_done

        # ---- zoom stage transitions --------------------------------------
        shrink_hi = (~armijo) | (f >= s.phi_lo)
        zm_done = (~shrink_hi) & curv
        flip = (~shrink_hi) & ~zm_done & (dphi * (s.a_hi - s.a_lo) >= 0.0)
        zm_a_lo = jnp.where(shrink_hi, s.a_lo, alpha)
        zm_phi_lo = jnp.where(shrink_hi, s.phi_lo, f)
        zm_dphi_lo = jnp.where(shrink_hi, s.dphi_lo, dphi)
        zm_a_hi = jnp.where(shrink_hi, alpha, jnp.where(flip, s.a_lo, s.a_hi))
        zm_phi_hi = jnp.where(shrink_hi, f, jnp.where(flip, s.phi_lo, s.phi_hi))
        # bracket collapsed to nothing → give up (done, fallback kicks in)
        zm_stuck = jnp.abs(s.a_hi - s.a_lo) * jnp.maximum(
            jnp.abs(dphi0), 1.0
        ) <= 1e-12

        done_now = jnp.where(in_zoom, zm_done | zm_stuck, br_done)
        star_now = jnp.where(in_zoom, zm_done, br_done)

        next_stage = jnp.where(in_zoom, s.stage, jnp.where(enter_zoom, 1, 0))
        next_alpha = jnp.where(
            in_zoom | enter_zoom, alpha, alpha * expansion
        )

        return _State(
            i=s.i + 1,
            stage=next_stage.astype(jnp.int32),
            done=s.done | done_now,
            alpha=next_alpha,
            a_prev=jnp.where(in_zoom, s.a_prev, alpha),
            phi_prev=jnp.where(in_zoom, s.phi_prev, f),
            dphi_prev=jnp.where(in_zoom, s.dphi_prev, dphi),
            a_lo=jnp.where(in_zoom, zm_a_lo, jnp.where(enter_zoom, br_a_lo, s.a_lo)),
            phi_lo=jnp.where(
                in_zoom, zm_phi_lo, jnp.where(enter_zoom, br_phi_lo, s.phi_lo)
            ),
            dphi_lo=jnp.where(
                in_zoom, zm_dphi_lo, jnp.where(enter_zoom, br_dphi_lo, s.dphi_lo)
            ),
            a_hi=jnp.where(in_zoom, zm_a_hi, jnp.where(enter_zoom, br_a_hi, s.a_hi)),
            phi_hi=jnp.where(
                in_zoom, zm_phi_hi, jnp.where(enter_zoom, br_phi_hi, s.phi_hi)
            ),
            a_star=jnp.where(star_now, alpha, s.a_star),
            phi_star=jnp.where(star_now, f, s.phi_star),
            aux_star=_sel(star_now, aux, s.aux_star),
            success=s.success | star_now,
            a_best=a_best,
            phi_best=phi_best,
            aux_best=aux_best,
            has_best=has_best,
        )

    s = lax.while_loop(cond, body, init)

    # Wolfe point if found, else best Armijo point, else stay put.
    use_best = (~s.success) & s.has_best
    step = jnp.where(s.success, s.a_star, jnp.where(use_best, s.a_best, 0.0))
    value = jnp.where(s.success, s.phi_star, jnp.where(use_best, s.phi_best, f0))
    aux = jax.tree_util.tree_map(
        lambda a, b, c: jnp.where(s.success, a, jnp.where(use_best, b, c)),
        s.aux_star,
        s.aux_best,
        aux0,
    )
    return PhiSearchResult(
        step=step,
        value=value,
        aux=aux,
        success=s.success | use_best,
        num_evals=s.i,
    )


def wolfe_line_search(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    x0: Array,
    direction: Array,
    f0: Array,
    g0: Array,
    *,
    initial_step: Array | float = 1.0,
    c1: float = 1e-4,
    c2: float = 0.9,
    max_iterations: int = 25,
    expansion: float = 2.0,
) -> LineSearchResult:
    """Find alpha satisfying the strong Wolfe conditions along ``direction``.

    Black-box form: each trial is a full ``value_and_grad`` evaluation; the
    gradient rides through the search as the aux pytree so the accepted
    point's gradient comes back without a re-evaluation.
    """
    dtype = x0.dtype

    def phi(alpha):
        f, g = value_and_grad(x0 + alpha * direction)
        return f, jnp.dot(g, direction), g

    res = wolfe_search_phi(
        phi,
        f0,
        jnp.dot(g0, direction),
        g0,
        dtype=dtype,
        initial_step=initial_step,
        c1=c1,
        c2=c2,
        max_iterations=max_iterations,
        expansion=expansion,
    )
    return LineSearchResult(
        step=res.step,
        x=x0 + res.step * direction,
        value=res.value,
        gradient=res.aux,
        success=res.success,
        num_evals=res.num_evals,
    )
