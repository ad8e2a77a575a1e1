"""The device scopes: every kernel and optimizer phase under one stable
``photon.*`` name.

A scope is a ``jax.named_scope``: it changes nothing but the ``op_name``
metadata of the operations traced inside it, which then reads
``jit(segment_f)/while/body/photon.owlqn.linesearch/photon.matvec/photon.gather/gather``
whatever the compiler numbers the fusion this time.
``analysis/hlo.instruction_scopes`` joins a compiled executable's
instruction names (what a profiler trace's device events carry) to these
names, so that "the gather" is the same row before and after a change to
``ops/gather.py``.

``SCOPES`` is the whole vocabulary, name -> (layer, one line of meaning);
:func:`scope` refuses a name that is not in it, and ``tests/test_scopes.py``
holds the table and the call sites to each other. The layers are PERF.md
section 3's.
"""
from __future__ import annotations

import jax

SCOPES: dict[str, tuple[str, str]] = {
    # --- kernels (ops/) ---------------------------------------------------
    "photon.matvec": ("kernels", "X.v: margins from coefficients, dense or sparse ELL"),
    "photon.rmatvec": ("kernels", "X^T.r: gradient side, dense, flat scatter or windowed"),
    "photon.gather": ("kernels", "1-element table gather: row fetch and lane select, or the plain gather"),
    "photon.gather.fetch": ("kernels", "the gather's first half: the 128-lane row each element lives in, one segment's block"),
    "photon.gather.select": ("kernels", "the gather's second half: each element's lane out of its fetched row"),
    "photon.rmatvec.prefix": ("kernels", "windowed X^T.r: centring and the cumsum of contributions"),
    "photon.rmatvec.bounds": ("kernels", "windowed X^T.r: prefix sums read at the static column bounds"),
    "photon.rmatvec.combine": ("kernels", "windowed X^T.r: instance partials summed into their windows"),
    "photon.loss": ("kernels", "pointwise loss, its derivatives and their weighted sums over rows"),
    "photon.hvp": ("kernels", "Hessian-vector product: curvature at the centre, then a matvec and an rmatvec"),
    # --- optimizer programs (optimize/) -----------------------------------
    "photon.owlqn.direction": ("optimizer programs", "pseudo-gradient, two-loop recursion, orthant alignment"),
    "photon.owlqn.linesearch": ("optimizer programs", "backtracking Armijo trials and the accepted point's gradient"),
    "photon.owlqn.history": ("optimizer programs", "curvature-pair and loss/gradient-norm history writes, convergence test"),
    "photon.lbfgs.direction": ("optimizer programs", "two-loop recursion and the descent guard"),
    "photon.lbfgs.linesearch": ("optimizer programs", "strong-Wolfe search and the accepted point's gradient"),
    "photon.lbfgs.history": ("optimizer programs", "curvature-pair and history writes, convergence test"),
    "photon.tron.cg": ("optimizer programs", "truncated conjugate gradient on the trust-region subproblem"),
    "photon.tron.step": ("optimizer programs", "candidate evaluation, radius update, acceptance, convergence test"),
    # --- GAME programs (game/) --------------------------------------------
    "photon.re.solve": ("random-effect programs", "one size bucket's vmapped per-entity solves"),
    "photon.re.chunk": ("random-effect programs", "one chunk of a bucket too large to solve at once: the solver's loops over that chunk's entities"),
    "photon.re.fetch": ("random-effect programs", "a bucket's residual offsets fetched from sample order by its rows' positions"),
    "photon.re.rescore": ("random-effect programs", "one width's kept rows, merged into sample order at placement, dotted with their entities' coefficients"),
    "photon.descent.residual": ("descent loop", "a coordinate's residual: the total less its own old score, the offsets it trains on"),
    "photon.descent.rescore": ("descent loop", "a coordinate's new score and the total rebuilt from it"),
    "photon.score.batch": ("scorer", "GameScorer's fused batch program: every coordinate's margin"),
}


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`; ``KeyError``
    for any other, so a typo cannot open a scope no reader knows."""
    if name not in SCOPES:
        raise KeyError(f"{name!r} is not in photon_tpu.obs.scopes.SCOPES")
    return jax.named_scope(name)
