"""OWL-QN: L1 / elastic-net optimization as a jit-compiled while-loop.

TPU-native replacement for Breeze's OWLQN as used by the reference
(optimization/OWLQN.scala:70-85 — L1 weight lives in the optimizer, not the
objective). Implements Andrew & Gao (2007): pseudo-gradient of
F(x) = f(x) + l1·‖x‖₁, two-loop L-BFGS direction on the pseudo-gradient with
orthant alignment, and a backtracking line search with orthant projection.

The (s, y) history is built from gradients of the *smooth* part f, per the
algorithm; convergence accounting follows the reference Optimizer semantics
on the full objective F.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp
from jax import lax

from photon_tpu import obs
from photon_tpu.obs.scopes import scope
from photon_tpu.optimize.common import (
    ConvergenceReason,
    OptimizeResult,
    OptimizerConfig,
    SmoothMarginOracle,
    convergence_check,
    project_to_box,
)
from photon_tpu.optimize.lbfgs import (
    _CURVATURE_EPS,
    evaluate_start,
    two_loop_direction,
)
from photon_tpu.types import Array


def pseudo_gradient(x: Array, g: Array, l1_weight: Array) -> Array:
    """Subgradient-minimal pseudo-gradient of f(x) + l1·‖x‖₁ (Andrew & Gao)."""
    at_zero_neg = g + l1_weight
    at_zero_pos = g - l1_weight
    zero_case = jnp.where(
        at_zero_neg < 0, at_zero_neg, jnp.where(at_zero_pos > 0, at_zero_pos, 0.0)
    )
    return jnp.where(x != 0.0, g + l1_weight * jnp.sign(x), zero_case)


class _OWLQNState(NamedTuple):
    it: Array
    x: Array
    f: Array  # full objective F = f + l1|x|
    g_smooth: Array
    s_hist: Array
    y_hist: Array
    rho: Array
    num_pairs: Array
    pos: Array
    reason: Array
    loss_hist: Array
    gnorm_hist: Array
    n_evals: Array
    n_passes: Array
    # data-dependent tolerances ride the STATE (not trace constants) so a
    # compiled segment program (SegmentedOWLQN) is reusable across solves
    loss_abs_tol: Array
    grad_abs_tol: Array
    carry: object  # margins of the smooth part at x (oracle mode), else ()


class SegmentProgress(NamedTuple):
    """What ``SegmentedOWLQN.advance`` reads back after a segment."""

    iterations: int
    n_evals: int  # line-search trials so far, the start point's two included
    reason: int  # a ``ConvergenceReason`` code; 0 while the solve goes on
    value: float  # the full objective F at the current point

    @property
    def done(self) -> bool:
        return self.reason != int(ConvergenceReason.NOT_CONVERGED)


def _owlqn_machinery(
    value_and_grad: Callable[[Array], tuple[Array, Array]] | None,
    l1_weight: float,
    config: OptimizerConfig,
    *,
    oracle: SmoothMarginOracle | None,
    dtype,
):
    """Shared OWL-QN program pieces: ``(make_init, cond, body, finalize)``.

    ``minimize_owlqn`` runs them as one ``lax.while_loop`` program;
    ``SegmentedOWLQN`` re-dispatches ``body`` in bounded-iteration
    segments from the host. Both drivers execute the identical algebra;
    results agree up to f32 reassociation across the different XLA
    programs (iteration counts can differ by ±1 near tolerance).
    """
    if oracle is not None and value_and_grad is not None:
        raise ValueError("pass value_and_grad=None when oracle is given")
    if oracle is None:
        if value_and_grad is None:
            raise ValueError("need value_and_grad or oracle")
        _vg = value_and_grad

        def _full(x):
            f, g = _vg(x)
            return f, g, ()

        oracle = SmoothMarginOracle(
            full=_full, value_margins=None, grad_from_margins=None
        )
    m = config.num_corrections
    t = config.max_iterations
    l1 = jnp.asarray(l1_weight, dtype)
    has_box = config.lower_bounds is not None or config.upper_bounds is not None

    def eval_smooth(x):
        f, g, carry = oracle.full(x)
        return f.astype(dtype), g.astype(dtype), carry

    def full_value(f_smooth, x):
        return f_smooth + l1 * jnp.sum(jnp.abs(x))

    def make_init(x0: Array) -> _OWLQNState:
        d = x0.shape[-1]
        if has_box:
            x0 = project_to_box(x0, config.lower_bounds, config.upper_bounds)
        # Absolute tolerances off the zero state (Optimizer.scala:181).
        f0s, g0, carry0, f_zero, g_zero, start_passes = evaluate_start(
            eval_smooth, oracle.at_zero, x0, dtype
        )
        pg_zero = pseudo_gradient(jnp.zeros_like(x0), g_zero, l1)
        f0 = full_value(f0s, x0)
        return _OWLQNState(
            it=jnp.zeros((), jnp.int32),
            x=x0,
            f=f0,
            g_smooth=g0,
            s_hist=jnp.zeros((m, d), dtype),
            y_hist=jnp.zeros((m, d), dtype),
            rho=jnp.zeros((m,), dtype),
            num_pairs=jnp.zeros((), jnp.int32),
            pos=jnp.zeros((), jnp.int32),
            reason=jnp.zeros((), jnp.int32),
            loss_hist=jnp.full((t + 1,), f0, dtype),
            gnorm_hist=jnp.full(
                (t + 1,), jnp.linalg.norm(pseudo_gradient(x0, g0, l1)), dtype
            ),
            n_evals=jnp.asarray(2, jnp.int32),  # zero-state + initial point
            n_passes=start_passes,
            loss_abs_tol=jnp.abs(f_zero) * config.tolerance,
            grad_abs_tol=jnp.linalg.norm(pg_zero) * config.tolerance,
            carry=carry0,
        )

    def cond(s: _OWLQNState):
        return s.reason == ConvergenceReason.NOT_CONVERGED

    def body(s: _OWLQNState) -> _OWLQNState:
        with scope("photon.owlqn.direction"):
            pg = pseudo_gradient(s.x, s.g_smooth, l1)
            direction = two_loop_direction(
                pg, s.s_hist, s.y_hist, s.rho, s.num_pairs, s.pos
            )
            # Orthant alignment: zero any component not descending w.r.t. pg.
            direction = jnp.where(direction * pg < 0.0, direction, 0.0)
            # Fall back to -pg if alignment annihilated the direction.
            degenerate = jnp.dot(direction, direction) == 0.0
            direction = jnp.where(degenerate, -pg, direction)

            # Choice orthant: sign(x), or sign(-pg) at zero coordinates.
            xi = jnp.where(s.x != 0.0, jnp.sign(s.x), jnp.sign(-pg))

            first = s.num_pairs == 0
            pg_norm = jnp.linalg.norm(pg)
            init_step = jnp.where(
                first, jnp.minimum(1.0, 1.0 / jnp.maximum(pg_norm, 1e-12)), 1.0
            ).astype(dtype)

        with scope("photon.owlqn.linesearch"):
            # Backtracking line search with orthant projection.
            def project(x_cand):
                return jnp.where(jnp.sign(x_cand) == xi, x_cand, 0.0)

            def ls_cond(carry):
                i, step, done, *_ = carry
                return (~done) & (i < config.ls_max_iterations)

            def _armijo(x_cand, f_cand):
                # Armijo on F with the directional derivative measured
                # along the *projected* displacement (Andrew & Gao eq. 4).
                dx = x_cand - s.x
                suff = f_cand <= s.f + config.ls_c1 * jnp.dot(pg, dx)
                moved = jnp.dot(dx, dx) > 0.0
                return suff & moved

            if oracle.value_margins is None:
                def ls_body(carry):
                    i, step, done, x_b, f_b, g_b, ok = carry
                    x_cand = project(s.x + step * direction)
                    f_s, g_cand, _ = eval_smooth(x_cand)
                    f_cand = full_value(f_s, x_cand)
                    accept = _armijo(x_cand, f_cand)
                    return (
                        i + 1,
                        step * 0.5,
                        done | accept,
                        jnp.where(accept, x_cand, x_b),
                        jnp.where(accept, f_cand, f_b),
                        jnp.where(accept, g_cand, g_b),
                        ok | accept,
                    )

                ls_iters, _, _, x_new, f_new, g_new, ls_ok = lax.while_loop(
                    ls_cond,
                    ls_body,
                    (
                        jnp.zeros((), jnp.int32),
                        init_step,
                        jnp.zeros((), bool),
                        s.x,
                        s.f,
                        s.g_smooth,
                        jnp.zeros((), bool),
                    ),
                )
                carry_new = s.carry
                passes = 2 * ls_iters
            else:
                # value-only trials (1 pass each); margins ride the carry so the
                # accepted gradient is one backward pass after the loop
                def ls_body(carry):
                    i, step, done, x_b, f_b, z_b, ok = carry
                    x_cand = project(s.x + step * direction)
                    f_s, z_cand = oracle.value_margins(x_cand)
                    f_cand = full_value(f_s.astype(dtype), x_cand)
                    accept = _armijo(x_cand, f_cand)
                    z_b = jnp.where(accept, z_cand, z_b)
                    return (
                        i + 1,
                        step * 0.5,
                        done | accept,
                        jnp.where(accept, x_cand, x_b),
                        jnp.where(accept, f_cand, f_b),
                        z_b,
                        ok | accept,
                    )

                ls_iters, _, _, x_new, f_new, z_new, ls_ok = lax.while_loop(
                    ls_cond,
                    ls_body,
                    (
                        jnp.zeros((), jnp.int32),
                        init_step,
                        jnp.zeros((), bool),
                        s.x,
                        s.f,
                        s.carry,
                        jnp.zeros((), bool),
                    ),
                )
                if has_box:
                    # the box path fully re-evaluates at the projected point —
                    # don't pay a backward pass only to discard it
                    g_new, carry_new = s.g_smooth, z_new
                    passes = ls_iters
                else:
                    g_new = oracle.grad_from_margins(x_new, z_new).astype(dtype)
                    carry_new = z_new
                    passes = ls_iters + 1
            n_passes = s.n_passes + passes
            if has_box:
                # box projection after every step, like the reference OWLQN
                # (constraintMap flows through the LBFGS base, LBFGS.scala:59-82)
                x_proj = project_to_box(
                    x_new, config.lower_bounds, config.upper_bounds
                )
                f_s, g_new, carry_new = eval_smooth(x_proj)
                f_new = full_value(f_s, x_proj)
                x_new = x_proj
                ls_iters = ls_iters + 1
                n_passes = n_passes + 2

        with scope("photon.owlqn.history"):
            # History update with smooth gradients.
            s_vec = x_new - s.x
            y_vec = g_new - s.g_smooth
            sy = jnp.dot(s_vec, y_vec)
            accept_pair = sy > _CURVATURE_EPS
            pos = s.pos
            s_hist = jnp.where(accept_pair, s.s_hist.at[pos].set(s_vec), s.s_hist)
            y_hist = jnp.where(accept_pair, s.y_hist.at[pos].set(y_vec), s.y_hist)
            rho = jnp.where(
                accept_pair,
                s.rho.at[pos].set(1.0 / jnp.where(accept_pair, sy, 1.0)),
                s.rho,
            )
            pos = jnp.where(accept_pair, (pos + 1) % m, pos)
            num_pairs = jnp.where(accept_pair, s.num_pairs + 1, s.num_pairs)

            it = s.it + 1
            pg_new = pseudo_gradient(x_new, g_new, l1)
            pg_new_norm = jnp.linalg.norm(pg_new)
            reason = convergence_check(
                it=it,
                value=f_new,
                prev_value=s.f,
                grad_norm=pg_new_norm,
                loss_abs_tol=s.loss_abs_tol,
                grad_abs_tol=s.grad_abs_tol,
                max_iterations=t,
                step_failed=~ls_ok,
            )

            return _OWLQNState(
                it=it,
                x=x_new,
                f=f_new,
                g_smooth=g_new,
                s_hist=s_hist,
                y_hist=y_hist,
                rho=rho,
                num_pairs=num_pairs,
                pos=pos,
                reason=reason,
                loss_hist=s.loss_hist.at[it].set(f_new),
                gnorm_hist=s.gnorm_hist.at[it].set(pg_new_norm),
                n_evals=s.n_evals + ls_iters,
                n_passes=n_passes,
                loss_abs_tol=s.loss_abs_tol,
                grad_abs_tol=s.grad_abs_tol,
                carry=carry_new,
            )

    def finalize(s: _OWLQNState) -> OptimizeResult:
        pg_final = pseudo_gradient(s.x, s.g_smooth, l1)
        idx = jnp.arange(t + 1)
        loss_hist = jnp.where(idx <= s.it, s.loss_hist, s.f)
        gnorm_hist = jnp.where(
            idx <= s.it, s.gnorm_hist, jnp.linalg.norm(pg_final)
        )
        return OptimizeResult(
            x=s.x,
            value=s.f,
            gradient=pg_final,
            iterations=s.it,
            reason=s.reason,
            loss_history=loss_hist,
            grad_norm_history=gnorm_hist,
            n_evals=s.n_evals,
            n_hvp=jnp.zeros((), jnp.int32),
            n_feature_passes=s.n_passes,
        )

    return make_init, cond, body, finalize


def minimize_owlqn(
    value_and_grad: Callable[[Array], tuple[Array, Array]] | None,
    x0: Array,
    l1_weight: float,
    config: OptimizerConfig = OptimizerConfig(),
    *,
    oracle: SmoothMarginOracle | None = None,
) -> OptimizeResult:
    """Minimize f(x) + l1_weight·‖x‖₁ where ``value_and_grad`` evaluates the
    smooth part f. Returns the reference-shaped ``OptimizeResult`` (the
    ``gradient`` field holds the pseudo-gradient at the solution).

    With a ``SmoothMarginOracle`` each backtracking trial computes the
    VALUE only (one feature pass — Armijo never needs the gradient) and
    the accepted point's gradient comes from its carried margins with one
    backward pass: trials+1 passes per iteration vs 2·trials black-box.
    """
    make_init, cond, body, finalize = _owlqn_machinery(
        value_and_grad, l1_weight, config, oracle=oracle, dtype=x0.dtype
    )
    s = lax.while_loop(cond, body, make_init(x0))
    return finalize(s)


class SegmentedOWLQN:
    """Host-segmented OWL-QN: the identical solve re-dispatched in
    bounded-iteration device programs.

    Why: a single while-loop solve at high-dim sparse scale can run many
    minutes inside ONE device program. That is (a) unkillable — a client
    timeout leaves the program occupying the chip — and (b) exposed to a
    runtime's per-program execution limit, which surfaces as
    `UNAVAILABLE: TPU device error` mid-solve. Segmenting
    bounds every dispatch to ``segment_iters`` optimizer iterations; the
    host re-dispatches until converged (one scalar sync per segment).
    Segment boundaries are also natural checkpoint/preemption points.

    The jitted init/segment/finalize take the problem data as an ARGUMENT
    (via ``oracle_factory(data)`` built at trace time), never as a closure
    constant: a closed-over batch lowers as dense literals baked into the
    StableHLO module — at config-3 scale that ships ~0.5 GB of constants
    to the (already slow) remote compiler and can duplicate the batch in
    HBM. jax.jit's own cache keys on the argument shapes, so warm-up and
    timed solves share one compile (the data-dependent tolerances ride
    the state, not the trace).

    The reference's Spark equivalent kills stragglers at task granularity
    (SURVEY §5.3); this is the TPU-native analogue at optimizer-iteration
    granularity.
    """

    def __init__(
        self,
        value_and_grad: Callable[[Array], tuple[Array, Array]] | None,
        l1_weight: float,
        config: OptimizerConfig = OptimizerConfig(),
        *,
        oracle_factory: Callable[[object], SmoothMarginOracle] | None = None,
        segment_iters: int = 16,
    ):
        import jax

        if segment_iters < 1:
            raise ValueError(f"segment_iters={segment_iters} < 1")
        if oracle_factory is not None and value_and_grad is not None:
            raise ValueError(
                "pass value_and_grad=None when oracle_factory is given"
            )
        self.segment_iters = segment_iters
        self.last_num_segments = 0
        k = segment_iters

        def machinery(data, dtype):
            oracle = (
                oracle_factory(data) if oracle_factory is not None else None
            )
            return _owlqn_machinery(
                value_and_grad, l1_weight, config, oracle=oracle, dtype=dtype
            )

        @jax.jit
        def init_f(x0, data):
            make_init, _, _, _ = machinery(data, x0.dtype)
            return make_init(x0)

        @jax.jit
        def segment_f(s, data):
            _, cond, body, _ = machinery(data, s.x.dtype)
            it0 = s.it
            return lax.while_loop(
                lambda ss: cond(ss) & (ss.it - it0 < k), body, s
            )

        @jax.jit
        def final_f(s, data):
            _, _, _, finalize = machinery(data, s.x.dtype)
            return finalize(s)

        self._init_f, self._segment_f, self._final_f = (
            init_f,
            segment_f,
            final_f,
        )

    # The step handle: what ``__call__`` is written on, for a caller that
    # paces the solve itself (a checkpoint or a deadline between segments,
    # a benchmark that times one segment). Each piece runs in a host span,
    # so a profiler capture shows which of them an idle device waited on.

    def start(self, x0: Array, data: object = ()) -> _OWLQNState:
        """The solve's state at ``x0``, dispatched and not waited for. Its
        ``reason`` is NOT_CONVERGED: the first test is the first
        segment's."""
        with obs.span("owlqn.init", cat="solve"):
            return self._init_f(x0, data)

    def advance(
        self, state: _OWLQNState, data: object = ()
    ) -> tuple[_OWLQNState, SegmentProgress]:
        """One segment on: at most ``segment_iters`` iterations on the
        device, then the one read-back that paces the loop. Returns the
        new state (on the device) and its counters (on the host)."""
        import jax

        with obs.span("owlqn.segment", cat="solve"):
            state = self._segment_f(state, data)
        with obs.span("owlqn.sync", cat="solve"):
            # phl-ok: PHL002 the segment's ONE read-back: it paces the host loop
            it, n_evals, reason, value = jax.device_get(
                (state.it, state.n_evals, state.reason, state.f)
            )
        # phl-ok: PHL002 host scalars already, fetched by the read-back above
        return state, SegmentProgress(int(it), int(n_evals), int(reason), float(value))

    def finish(self, state: _OWLQNState, data: object = ()) -> OptimizeResult:
        """The ``OptimizeResult`` of a solve at ``state``, on the device."""
        with obs.span("owlqn.final", cat="solve"):
            return self._final_f(state, data)

    def __call__(self, x0: Array, data: object = ()) -> OptimizeResult:
        s = self.start(x0, data)
        n_seg, done = 0, False
        while not done:
            s, progress = self.advance(s, data)
            done = progress.done
            n_seg += 1
        self.last_num_segments = n_seg
        return self.finish(s, data)
