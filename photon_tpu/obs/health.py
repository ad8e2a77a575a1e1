"""Sync-free training health: per-coordinate loss / grad-norm / finiteness.

A NaN that enters a coordinate's state mid-fit poisons every later sweep
silently — the checkpoint, the best-by-validation snapshot, and the
exported model all inherit it, and the failure surfaces hours later as a
0.5-AUC scoring run. The fix must not cost the sync-free steady state
PR 2 bought (ONE read-back barrier per sweep, pinned by dispatch-count
tests), so the health signals are computed INSIDE the already-dispatched
fused sweep programs and read back AS the existing sweep barrier:

- :func:`sweep_health` runs under jit inside each coordinate's
  ``_sweep_body`` (and eagerly on the unfused reference path): three 0-d
  scalars — summed final loss, global gradient L2 norm, and a fused
  ``isfinite`` sentinel over every state leaf — riding the program's
  existing outputs. Zero extra dispatches.
- descent folds those scalars into the ONE per-sweep read-back
  (``util/force.fetch_scalars`` — the barrier fetch and the health fetch
  are the same single device→host round trip), surfaces them as
  ``health.*`` metrics and tracker-row fields, and applies the
  divergence policy at the sweep boundary.

Policies (``GameEstimator(on_divergence=...)``, env override
``PHOTON_ON_DIVERGENCE``):

- ``"raise"`` (default): the fit fails loudly with
  :class:`DivergenceError` at the first sweep boundary where a
  coordinate's health scalars go non-finite.
- ``"warn"``: log + lifecycle event, keep training (triage mode).
- ``"halt_coordinate"``: the diverged coordinate is re-initialized and
  frozen (excluded from later sweeps); the others keep training. The
  recovery re-score costs one dispatch — paid only at the divergence
  boundary, never in the steady state.
"""
from __future__ import annotations

import os

__all__ = [
    "DIVERGENCE_POLICIES",
    "DivergenceError",
    "resolve_policy",
    "sweep_health",
]

DIVERGENCE_POLICIES = ("raise", "warn", "halt_coordinate")


class DivergenceError(RuntimeError):
    """A coordinate's sweep produced non-finite loss/gradient/state.

    Carries the offending coordinate, the sweep iteration, and the host
    health row so drivers can report exactly where the fit went bad."""

    def __init__(self, coordinate: str, iteration: int, health: dict):
        self.coordinate = coordinate
        self.iteration = iteration
        self.health = dict(health)
        super().__init__(
            f"coordinate {coordinate!r} diverged at sweep {iteration}: "
            f"loss={health.get('loss')!r} gnorm={health.get('gnorm')!r} "
            f"finite={health.get('finite')!r}"
        )


def resolve_policy(policy: str | None) -> str:
    """Validated divergence policy: explicit argument wins, then the
    ``PHOTON_ON_DIVERGENCE`` env, then ``"raise"``."""
    if policy is None:
        policy = os.environ.get("PHOTON_ON_DIVERGENCE", "").strip() or "raise"
    if policy not in DIVERGENCE_POLICIES:
        raise ValueError(
            f"on_divergence must be one of {DIVERGENCE_POLICIES}, "
            f"got {policy!r}"
        )
    return policy


def sweep_health(state, info) -> dict:
    """Per-coordinate health triple and the solves' counters as 0-d device
    arrays, computed from a sweep step's EXISTING outputs (works traced —
    inside the fused sweep program — and eagerly on the unfused reference
    path):

    - ``loss``: Σ of the optimizer's final objective values (a scalar
      for FE/MF; summed over the per-entity lanes of every RE bucket);
    - ``gnorm``: global L2 norm over every final gradient leaf;
    - ``finite``: fused sentinel — loss AND gnorm AND every float state
      leaf finite. Any NaN/Inf anywhere in the new state flips it;
    - ``iterations``, ``evaluations``, ``feature_passes``: one entry per
      solve (an RE coordinate has one per bucket), the optimizer
      iterations, the objective evaluations and the passes over the
      feature block (``OptimizeResult.n_feature_passes``: what ran) of its
      slowest lane. They ride home with the triple in the sweep's one
      read-back.

    ``info`` is one OptimizeResult-like or a list of them (the RE
    multi-bucket case); ``state`` is the coordinate's new state pytree.
    """
    import jax
    import jax.numpy as jnp

    # a LIST is the RE multi-bucket case; a bare OptimizeResult is a
    # NamedTuple (i.e. a tuple!), so the type check must not unpack it
    infos = info if isinstance(info, list) else [info]
    loss = sum(jnp.sum(r.value) for r in infos)
    gsq = sum(
        jnp.sum(jnp.square(r.gradient.astype(jnp.float32))) for r in infos
    )
    gnorm = jnp.sqrt(gsq)
    finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
    for leaf in jax.tree_util.tree_leaves(state):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            finite = finite & jnp.all(jnp.isfinite(leaf))
    return {
        "loss": jnp.asarray(loss, jnp.float32),
        "gnorm": jnp.asarray(gnorm, jnp.float32),
        "finite": finite,
        "iterations": tuple(jnp.max(jnp.asarray(r.iterations)) for r in infos),
        "evaluations": tuple(jnp.max(jnp.asarray(r.n_evals)) for r in infos),
        "feature_passes": tuple(
            jnp.max(jnp.asarray(r.n_feature_passes)) for r in infos
        ),
    }
