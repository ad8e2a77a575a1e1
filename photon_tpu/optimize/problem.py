"""Optimization problems: objective + optimizer + regularization + variance.

Reference parity: photon-api optimization/GeneralizedLinearOptimizationProblem
.scala, DistributedOptimizationProblem.scala (per-λ mutable reg weight
:62-73, coefficient variance :82-96, runWithSampling :145-160),
SingleNodeOptimizationProblem.scala, RegularizationContext.scala,
OptimizerFactory.scala and VarianceComputationType.scala.

The Distributed/SingleNode split disappears on TPU: one ``GLMProblem``
drives the same jit-compiled solve whether the batch is replicated on one
chip, sharded over the mesh's data axis (XLA inserts psum), or vmapped
per entity.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Callable

import jax
import jax.numpy as jnp

from photon_tpu import obs
from photon_tpu.data.sampling import build_down_sampler
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optimize.common import OptimizeResult, OptimizerConfig
from photon_tpu.optimize.lbfgs import minimize_lbfgs
from photon_tpu.optimize.owlqn import minimize_owlqn
from photon_tpu.optimize.tron import minimize_tron
from photon_tpu.types import Array, LabeledBatch, OptimizerType, TaskType


class RegularizationType(enum.Enum):
    """Reference RegularizationType.scala."""

    NONE = "NONE"
    L1 = "L1"
    L2 = "L2"
    ELASTIC_NET = "ELASTIC_NET"


@dataclasses.dataclass(frozen=True)
class RegularizationContext:
    """L1/L2 mixing (reference RegularizationContext.scala): for
    ELASTIC_NET with mixing parameter α, l1 = α·λ and l2 = (1−α)·λ."""

    regularization_type: RegularizationType = RegularizationType.NONE
    elastic_net_alpha: float | None = None

    def __post_init__(self):
        if (
            self.regularization_type == RegularizationType.ELASTIC_NET
            and self.elastic_net_alpha is not None
            and not (0.0 <= self.elastic_net_alpha <= 1.0)
        ):
            raise ValueError("elastic net alpha must be in [0, 1]")

    def l1_weight(self, reg_weight: float) -> float:
        if self.regularization_type == RegularizationType.L1:
            return reg_weight
        if self.regularization_type == RegularizationType.ELASTIC_NET:
            alpha = 0.5 if self.elastic_net_alpha is None else self.elastic_net_alpha
            return alpha * reg_weight
        return 0.0

    def l2_weight(self, reg_weight: float) -> float:
        if self.regularization_type == RegularizationType.L2:
            return reg_weight
        if self.regularization_type == RegularizationType.ELASTIC_NET:
            alpha = 0.5 if self.elastic_net_alpha is None else self.elastic_net_alpha
            return (1.0 - alpha) * reg_weight
        return 0.0


class VarianceComputationType(enum.Enum):
    """Reference VarianceComputationType: NONE / SIMPLE (1/diag(H)) /
    FULL (diag(H⁻¹) via Cholesky)."""

    NONE = "NONE"
    SIMPLE = "SIMPLE"
    FULL = "FULL"


@dataclasses.dataclass(frozen=True)
class GLMProblemConfig:
    """Everything needed to build a solve for one coordinate/λ."""

    task: TaskType = TaskType.LOGISTIC_REGRESSION
    optimizer: OptimizerType = OptimizerType.LBFGS
    optimizer_config: OptimizerConfig = OptimizerConfig()
    regularization: RegularizationContext = RegularizationContext()
    regularization_weight: float = 0.0
    variance_computation: VarianceComputationType = VarianceComputationType.NONE
    down_sampling_rate: float = 1.0

    def with_regularization_weight(self, w: float) -> "GLMProblemConfig":
        """λ-grid reweighting (reference mutable reg weight update)."""
        return dataclasses.replace(self, regularization_weight=w)


@dataclasses.dataclass(frozen=True)
class GLMProblem:
    """A concrete, jit-able GLM solve.

    ``solve(batch, w0)`` returns an OptimizeResult; ``variances(batch, w)``
    the per-coefficient variance estimates. Both are pure functions of
    device arrays — shard the batch and they distribute; vmap them and they
    batch per entity.
    """

    config: GLMProblemConfig
    objective: GLMObjective

    @staticmethod
    def build(
        config: GLMProblemConfig,
        normalization: NormalizationContext = NormalizationContext(),
        mesh=None,
    ) -> "GLMProblem":
        with obs.span(
            "problem.build", cat="setup", optimizer=config.optimizer.name
        ):
            loss = loss_for_task(config.task)
            if config.optimizer == OptimizerType.TRON and not loss.twice_diff:
                raise ValueError(
                    f"TRON requires a twice-differentiable loss; {loss.name} is not "
                    "(reference restricts smoothed hinge to LBFGS/OWLQN)"
                )
            l1 = config.regularization.l1_weight(config.regularization_weight)
            l2 = config.regularization.l2_weight(config.regularization_weight)
            if l1 > 0 and config.optimizer not in (
                OptimizerType.LBFGS,
                OptimizerType.OWLQN,
            ):
                raise ValueError("L1/elastic-net requires OWLQN")
            objective = GLMObjective(
                loss=loss,
                l2_weight=l2,
                l1_weight=l1,
                normalization=normalization,
                mesh=mesh,
            )
            return GLMProblem(config=config, objective=objective)

    # --- solving ----------------------------------------------------------

    def value_and_gradient_fn(
        self, batch: LabeledBatch
    ) -> Callable[[Array], tuple[Array, Array]]:
        return lambda w: self.objective.value_and_gradient(w, batch)

    def objective_for_weight(self, reg_weight) -> GLMObjective:
        """Objective with l1/l2 recomputed from a (possibly traced) λ.

        The regularization *type* stays static so jit control flow is stable
        across a λ grid; only the weight values are data. This is the traced
        analogue of the reference's mutable reg weight
        (DistributedOptimizationProblem.scala:62-73, OWLQN.scala:70-85).
        """
        if reg_weight is None:
            return self.objective
        return dataclasses.replace(
            self.objective,
            l1_weight=self.config.regularization.l1_weight(reg_weight),
            l2_weight=self.config.regularization.l2_weight(reg_weight),
        )

    def solve(
        self,
        batch: LabeledBatch,
        w0: Array,
        reg_weight=None,
        *,
        extra_offsets: Array | None = None,
    ) -> OptimizeResult:
        """Run the solve. ``reg_weight`` may be a traced scalar: passing the
        λ-grid value here (instead of rebuilding the problem per λ) keeps one
        compiled program per coordinate across the whole grid.

        ``extra_offsets`` (e.g. the coordinate-descent residual scores) is
        folded into the batch offsets INSIDE the program. This is the
        donation-safe fused-sweep entry: callers hand over the pristine
        batch plus the residual instead of pre-building a mutated batch
        pytree, so the offset add fuses into the objective's margin pass
        and the only [N] temporary is the one XLA schedules.

        Telemetry: runs in an ``optimize.solve`` span. Called eagerly
        (legacy GLM grid) the span is the solve wall; called under a jit
        trace (GAME fused sweeps) it records the TRACE wall once per
        compile and nothing in the steady state — either way no device
        work is added. Per-iteration counters (``n_evals``, line-search
        trials) live in the returned OptimizeResult; eager callers feed
        them to the registry via :func:`record_optimize_metrics`."""
        return self._spanned_solve(
            batch, w0, reg_weight, extra_offsets=extra_offsets
        )

    def solve_keeping_product(
        self,
        batch: LabeledBatch,
        w0: Array,
        reg_weight=None,
        *,
        extra_offsets: Array | None = None,
    ) -> OptimizeResult:
        """``solve`` for a caller that scores the solution next: the
        result's ``product`` is ``objective.product(result.x, batch)`` as
        the solve's last exact evaluation made it, so the score needs no
        pass of its own. ``None`` where the optimizer ends on no such
        evaluation (OWL-QN, TRON, box constraints): the caller then makes
        the product itself."""
        return self._spanned_solve(
            batch,
            w0,
            reg_weight,
            extra_offsets=extra_offsets,
            keep_product=True,
        )

    def _spanned_solve(self, batch, w0, reg_weight, **kwargs) -> OptimizeResult:
        with obs.span(
            "optimize.solve",
            cat="solve",
            optimizer=self.config.optimizer.name,
            task=self.config.task.name,
        ):
            obs.counter("optimize.solves")
            return self._solve(batch, w0, reg_weight, **kwargs)

    def _solve(
        self,
        batch: LabeledBatch,
        w0: Array,
        reg_weight=None,
        *,
        extra_offsets: Array | None = None,
        keep_product: bool = False,
    ) -> OptimizeResult:
        if extra_offsets is not None:
            batch = batch._replace(offsets=batch.offsets + extra_offsets)
        cfg = self.config.optimizer_config
        objective = self.objective_for_weight(reg_weight)
        vg = lambda w: objective.value_and_gradient(w, batch)  # noqa: E731
        opt = self.config.optimizer
        # Static dispatch: branch on the regularization TYPE (not the traced
        # weight value) so the λ grid reuses one compiled program.
        has_l1 = self.config.regularization.regularization_type in (
            RegularizationType.L1,
            RegularizationType.ELASTIC_NET,
        )
        if has_l1 or opt == OptimizerType.OWLQN:
            # value-only backtracking trials (1 feature pass each) with the
            # accepted gradient from carried margins
            return minimize_owlqn(
                None,
                w0,
                objective.l1_weight,
                cfg,
                oracle=objective.smooth_margin_oracle(batch),
            )
        if opt == OptimizerType.TRON:
            # fully untouched config → switch to TRON's own defaults
            # (field-wise check excluding the bounds, which may be arrays —
            # dataclass == would hit numpy's ambiguous-truth error; a config
            # with bounds set is customized, so no swap either way)
            d = OptimizerConfig()
            untouched = cfg.lower_bounds is None and cfg.upper_bounds is None and all(
                getattr(cfg, f.name) == getattr(d, f.name)
                for f in dataclasses.fields(OptimizerConfig)
                if f.name not in ("lower_bounds", "upper_bounds")
            )
            if untouched:
                cfg = cfg.tron_defaults()
            return minimize_tron(
                vg,
                None,
                w0,
                cfg,
                # curvature hoisted out of the CG loop: one margin pass per
                # trust-region step instead of per Hv
                hvp_factory=lambda w: objective.hessian_operator(w, batch),
            )
        # LBFGS and LBFGSB (box bounds live in the OptimizerConfig). The
        # line search is in margin space — trials cost O(N) elementwise
        # instead of two feature passes (biggest win inside the vmapped
        # per-entity solves, where one straggler lane's trials would cost
        # every lane a feature pass).
        return minimize_lbfgs(
            None,
            w0,
            cfg,
            oracle=objective.directional_oracle(batch),
            keep_product=keep_product,
        )

    # --- variances --------------------------------------------------------

    def variances(self, batch: LabeledBatch, w: Array) -> Array | None:
        """Coefficient variance estimates (reference
        DistributedOptimizationProblem.computeVariances:82-96):
        SIMPLE → 1/diag(H); FULL → diag(H⁻¹) by Cholesky (XLA potrf —
        the reference reaches LAPACK dpotri via netlib JNI, util/Linalg.scala).
        """
        vc = self.config.variance_computation
        if vc == VarianceComputationType.NONE:
            return None
        if vc == VarianceComputationType.SIMPLE:
            d = self.objective.hessian_diagonal(w, batch)
            return 1.0 / jnp.maximum(d, 1e-12)
        h = self.objective.hessian_matrix(w, batch)
        eye = jnp.eye(h.shape[-1], dtype=h.dtype)
        chol = jax.scipy.linalg.cho_factor(h + 1e-12 * eye)
        return jnp.diagonal(jax.scipy.linalg.cho_solve(chol, eye))

    # --- sampling ---------------------------------------------------------

    def down_sampler(self):
        """Host-side sampler applied before batching (reference
        runWithSampling:145-160)."""
        return build_down_sampler(
            self.config.task.is_classification, self.config.down_sampling_rate
        )
