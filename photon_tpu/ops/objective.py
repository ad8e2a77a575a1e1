"""GLM objective functions: value / gradient / Hessian-vector / Hessian matrix.

This is the TPU-native replacement for the reference's distributed compute
kernel — the streaming aggregators in photon-lib function/glm/
(ValueAndGradientAggregator.scala:36-247, HessianVectorAggregator.scala:143-149,
HessianMatrixAggregator.scala:96) and the objective hierarchy
(function/ObjectiveFunction.scala:25, DiffFunction.scala:25,
TwiceDiffFunction.scala:25, L2Regularization.scala:26-140).

Design: everything is a pure jnp expression over a dense ``LabeledBatch``.
Under ``pjit`` with the batch axis sharded, XLA lowers the sum-reductions to
``psum`` over ICI — the reference's ``treeAggregate(depth)`` with the tree
shape left to the compiler. Under ``vmap`` the same code becomes the
per-entity local objective (the reference's SingleNodeObjectiveFunction).
One code path replaces the reference's Distributed/SingleNode split.

All reductions are weighted sums:
    value = Σᵢ wᵢ·l(zᵢ, yᵢ) + λ/2·‖w‖²
    grad  = Xᵀ(wᵢ·l′) + λw
    Hv    = Xᵀ(wᵢ·l″·(X v)) + λv
    H     = Xᵀ diag(wᵢ·l″) X + λI
with margins zᵢ = x·(w .* factor) + margin_shift + offsetᵢ when a
NormalizationContext is active (see ops/normalization.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from photon_tpu.obs.scopes import scope
from photon_tpu.ops.losses import PointwiseLoss
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.optimize.common import (
    DirectionalOracle,
    SmoothMarginOracle,
)
from photon_tpu.types import Array, LabeledBatch, SparseBatch


def matvec(batch, v: Array) -> Array:
    """X·v for either batch layout.

    Dense: one MXU matmul. When the feature block is stored bfloat16, the
    coefficient operand is cast down but the MXU accumulates in float32
    (``preferred_element_type``) — halved HBM traffic and doubled MXU rate
    at full-precision accumulation; optimizer state stays float32. Sparse
    ELL: gather the K coefficient slots per row and row-sum — padding slots
    hold value 0 so they vanish. This (plus ``rmatvec``) is how the sparse
    path preserves the reference aggregator's never-densify property
    (ValueAndGradientAggregator.scala:36-80) on TPU.
    """
    with scope("photon.matvec"):
        return _matvec(batch, v)


def _matvec(batch, v: Array) -> Array:
    if isinstance(batch, SparseBatch):
        return _ell_matvec(v, batch.indices, batch.values)
    x = batch.features
    if x.dtype == jnp.bfloat16:
        return jax.lax.dot_general(
            x,
            v.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return x @ v


def _ell_matvec(table: Array, indices: Array, values: Array) -> Array:
    """Σₖ table[indices[:, k]] · values[:, k] over a padded-ELL block.

    XLA:TPU's element gather is serialized, so on a TPU the K coefficient
    slots of a row come through ops/gather's row fetch and lane select.
    Where the block's fetched rows pass ``_SEG_BYTES`` the pass runs the
    segment loop itself: a segment is a block of R rows with all K slots,
    taken by a dynamic slice of the [K, n] view (how the [n, K] arrays lie
    on the chip, slot-major: the transpose is a relabelling), and the body
    (``gather.fetch_select_dot``) fetches, selects, multiplies by the values
    and sums over K, so the loop stacks [R] margins and the fetched rows
    never leave fast memory."""
    from photon_tpu.ops import gather

    if indices.ndim == 2 and gather.fetches_rows():
        plan = gather.segment_plan(
            *indices.shape, table.dtype.itemsize, 128
        )
        if plan.steps > 1:
            t2 = gather.lane_rows(table)
            rows_block = functools.partial(gather.fetch_select_dot, t2)
            return gather.map_segments(
                rows_block, (indices.T, values.T), plan, axis=1
            )
    return jnp.sum(gather.take_1d(table, indices) * values, axis=-1)


def _use_windows(batch, per_row: Array) -> bool:
    """Single routing decision for every windowed reduction (gradient AND
    variance paths): a column-window layout is present and the reduction is
    a plain 1-D row weighting."""
    return getattr(batch, "windows", None) is not None and per_row.ndim == 1


def _windowed_rmatvec_dispatch(windows, per_row: Array, dim: int, mesh):
    """One routing decision for every windowed Xᵀ· reduction (gradient AND
    variance paths): instance-sharded shard_map under a mesh, the
    single-chip pass otherwise."""
    if mesh is not None:
        from photon_tpu.parallel.sparse import sharded_windowed_rmatvec

        return sharded_windowed_rmatvec(windows, per_row, dim, mesh)
    from photon_tpu.ops.sparse_windows import rmatvec_windows_prefix

    return rmatvec_windows_prefix(windows, per_row, dim)


def rmatvec(batch, per_row: Array, dim: int, mesh=None) -> Array:
    """Xᵀ·per_row for either batch layout (``dim`` = static feature count,
    always taken from the coefficient vector's shape).

    Sparse ELL: flat scatter-add over the N·K (index, value·r) pairs. Under
    pjit with rows sharded, each shard scatters into its own [dim] partial
    and XLA inserts the psum — same collective the dense Xᵀr gets. When the
    batch carries a column-window layout (built on a TPU at high dim), the
    scatter is rerouted through ops/sparse_windows — XLA:TPU's serialized
    scatter lowering is minutes/eval at 10⁶-segment scale; the windowed
    prefix-sum pass is dense work.
    """
    with scope("photon.rmatvec"):
        return _rmatvec(batch, per_row, dim, mesh)


def _rmatvec(batch, per_row: Array, dim: int, mesh) -> Array:
    if isinstance(batch, SparseBatch):
        if _use_windows(batch, per_row):
            return _windowed_rmatvec_dispatch(
                batch.windows, per_row, dim, mesh
            )
        flat = (batch.values * per_row[:, None]).reshape(-1)
        return jax.ops.segment_sum(
            flat, batch.indices.reshape(-1), num_segments=dim
        )
    x = batch.features
    if x.dtype == jnp.bfloat16:
        return jax.lax.dot_general(
            x,
            per_row.astype(jnp.bfloat16),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return x.T @ per_row


@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """Weighted pointwise-loss objective with optional L2 and normalization.

    ``l1_weight`` is carried for OWLQN (the optimizer applies it through the
    pseudo-gradient; the smooth part here never includes it), mirroring the
    reference where L1 lives in Breeze's OWLQN not the objective
    (optimization/OWLQN.scala:70-85).
    """

    loss: PointwiseLoss
    l2_weight: float = 0.0
    l1_weight: float = 0.0
    normalization: NormalizationContext = NormalizationContext()
    #: set for multi-chip solves over window-carrying sparse batches — the
    #: backward pass then uses the instance-sharded shard_map reduction
    mesh: object = None

    # --- margins ----------------------------------------------------------

    def product(self, coef: Array, batch) -> Array:
        """X·(coef .* factor): the part of the margins that reads the
        feature block, and all of a score that does."""
        with scope("photon.matvec"):
            return _matvec(
                batch, self.normalization.effective_coefficients(coef)
            )

    def margins(self, coef: Array, batch, product: Array | None = None) -> Array:
        """The margins at ``coef``; over ``product`` (``self.product`` of
        the same point) where the caller holds it."""
        if product is None:
            product = self.product(coef, batch)
        # offsets and shift ride in the matvec's scope: the compiler fuses
        # them onto the product, and a fusion is named by its last operation
        with scope("photon.matvec"):
            z = product + batch.offsets
            if self.normalization.shifts is not None:
                z = z + self.normalization.margin_shift(coef)
            return z

    def _back(self, per_row: Array, batch, dim: int) -> Array:
        """Xᵀ·per_row, mapped back through the normalization transform.

        d margin/d coef = factor .* (x − shift), with factor ≡ 1 when only
        shifts are set. The shift correction is the margin-shift algebra that
        keeps the sparse path sparse (reference
        ValueAndGradientAggregator.scala:36-80).
        """
        with scope("photon.rmatvec"):  # the corrections too, as in margins
            g = _rmatvec(batch, per_row, dim, self.mesh)
            if self.normalization.shifts is not None:
                g = g - jnp.sum(per_row) * self.normalization.shifts
            if self.normalization.factors is not None:
                g = g * self.normalization.factors
            return g

    # --- value / gradient -------------------------------------------------

    def value(self, coef: Array, batch) -> Array:
        z = self.margins(coef, batch)
        with scope("photon.loss"):
            raw = jnp.sum(batch.weights * self.loss.loss(z, batch.labels))
        return raw + 0.5 * self.l2_weight * jnp.dot(coef, coef)

    def gradient(self, coef: Array, batch) -> Array:
        return self.value_and_gradient(coef, batch)[1]

    def value_and_gradient(self, coef: Array, batch) -> tuple[Array, Array]:
        return self._value_grad_margins(coef, batch)[:2]

    def _value_grad_margins(
        self, coef: Array, batch, z: Array | None = None
    ) -> tuple[Array, Array, Array]:
        """(f, g, z) — single implementation shared by the black-box path
        and the directional oracle, so the two line-search modes can never
        drift onto different objectives. ``z``: the margins at ``coef``
        where the caller holds them (one backward pass is then all that
        reads the block)."""
        if z is None:
            z = self.margins(coef, batch)
        with scope("photon.loss"):
            losses, d1 = self.loss.loss_and_d1(z, batch.labels)
            value = jnp.sum(
                batch.weights * losses
            ) + 0.5 * self.l2_weight * jnp.dot(coef, coef)
            per_row = batch.weights * d1
        grad = self._back(per_row, batch, coef.shape[-1]) + self.l2_weight * coef
        return value, grad, z

    # --- second order -----------------------------------------------------

    def hessian_vector(self, coef: Array, v: Array, batch) -> Array:
        """H·v via one forward + one backward matmul (no O(D²) memory)."""
        return self.hessian_operator(coef, batch)(v)

    def directional_oracle(self, batch) -> "DirectionalOracle":
        """Margin-space line-search oracle for L-BFGS (optimize/lbfgs.py).

        Margins are AFFINE in the step: z(x+αd) = z(x) + α·z_d with
        z_d = X·(d.*factor) + margin_shift(d) — so once z(x) (carried
        across iterations) and z_d (one feature pass per iteration) are in
        hand, every line-search trial costs O(N) elementwise loss algebra
        instead of two feature-block passes, and the accepted point's
        gradient is one backward pass from its margins. Per iteration: 2
        feature passes total, independent of trial count — the win is
        largest for vmapped per-entity solves, where one straggler lane's
        extra trials used to cost every lane a full feature pass. (The
        reference pays 2 passes per trial through Breeze's line search,
        optimization/LBFGS.scala:84.)
        """

        def full(x: Array):
            return self._value_grad_margins(x, batch)

        def full_product(x: Array):
            p = self.product(x, batch)
            z = self.margins(x, batch, product=p)
            return (*self._value_grad_margins(x, batch, z), p)

        def dir_setup(carry_z: Array, x: Array, d: Array):
            z_d = matvec(batch, self.normalization.effective_coefficients(d))
            if self.normalization.shifts is not None:
                z_d = z_d + self.normalization.margin_shift(d)
            xx = jnp.dot(x, x)
            xd = jnp.dot(x, d)
            dd = jnp.dot(d, d)

            def phi(alpha):
                with scope("photon.loss"):
                    z = carry_z + alpha * z_d
                    losses, d1 = self.loss.loss_and_d1(z, batch.labels)
                    reg = 0.5 * self.l2_weight * (
                        xx + 2.0 * alpha * xd + alpha * alpha * dd
                    )
                    f = jnp.sum(batch.weights * losses) + reg
                    dphi = jnp.sum(
                        batch.weights * d1 * z_d
                    ) + self.l2_weight * (xd + alpha * dd)
                return f, dphi, ()

            def accept(alpha):
                with scope("photon.loss"):
                    z = carry_z + alpha * z_d
                    _, d1 = self.loss.loss_and_d1(z, batch.labels)
                    per_row = batch.weights * d1
                g = self._back(
                    per_row, batch, x.shape[-1]
                ) + self.l2_weight * (x + alpha * d)
                return g, z

            return phi, accept

        return DirectionalOracle(
            full=full,
            dir_setup=dir_setup,
            at_zero=functools.partial(self._at_zero, batch),
            full_product=full_product,
        )

    def _at_zero(self, batch, x: Array):
        """``_value_grad_margins`` at zero coefficients without a forward
        pass, the oracles' ``at_zero``: X·0 is 0 and ``margin_shift(0)`` is
        0, so the margins there are the offsets (in the shape and type
        ``margins`` gives at ``x``); the loss on them and one backward pass
        are all that is left."""
        like = jax.eval_shape(self.margins, x, batch)
        z = jnp.broadcast_to(batch.offsets, like.shape).astype(like.dtype)
        return self._value_grad_margins(jnp.zeros_like(x), batch, z)

    def smooth_margin_oracle(self, batch) -> SmoothMarginOracle:
        """Value-only trial oracle for OWLQN (optimize/owlqn.py): each
        backtracking trial pays one forward pass; the backward pass runs
        once, on the accepted point's carried margins."""

        def value_margins(x: Array):
            z = self.margins(x, batch)
            with scope("photon.loss"):
                f = jnp.sum(
                    batch.weights * self.loss.loss(z, batch.labels)
                ) + 0.5 * self.l2_weight * jnp.dot(x, x)
            return f, z

        def grad_from_margins(x: Array, z: Array):
            with scope("photon.loss"):
                _, d1 = self.loss.loss_and_d1(z, batch.labels)
                per_row = batch.weights * d1
            return self._back(per_row, batch, x.shape[-1]) + self.l2_weight * x

        return SmoothMarginOracle(
            full=lambda x: self._value_grad_margins(x, batch),
            value_margins=value_margins,
            grad_from_margins=grad_from_margins,
            at_zero=functools.partial(self._at_zero, batch),
        )

    def hessian_operator(self, coef: Array, batch) -> Callable:
        """H(coef)·v closure with the loss curvature precomputed.

        The margin pass (one full read of the feature block) depends only
        on the CENTER, not on v — TRON's truncated CG applies H·v up to 20
        times per trust-region step at a fixed center (TRON.scala:278-339),
        so hoisting it cuts each Hv from three feature passes to two.
        """
        with scope("photon.hvp"):
            z = self.margins(coef, batch)
            with scope("photon.loss"):
                d2w = batch.weights * self.loss.d2(z, batch.labels)
        dim = coef.shape[-1]

        def hv(v: Array) -> Array:
            with scope("photon.hvp"):
                xv = matvec(
                    batch, self.normalization.effective_coefficients(v)
                )
                if self.normalization.shifts is not None:
                    xv = xv + self.normalization.margin_shift(v)
                return self._back(d2w * xv, batch, dim) + self.l2_weight * v

        return hv

    def hessian_matrix(self, coef: Array, batch) -> Array:
        """Dense D×D Hessian (used for coefficient variances on small D;
        a sparse batch is densified here — FULL variance is O(D²) memory
        regardless, so it is only reachable when D is small anyway)."""
        z = self.margins(coef, batch)
        with scope("photon.loss"):
            d2 = batch.weights * self.loss.d2(z, batch.labels)
        x = self._transformed_features(batch, coef.shape[-1])
        h = x.T @ (d2[:, None] * x)
        d = coef.shape[-1]
        return h + self.l2_weight * jnp.eye(d, dtype=h.dtype)

    def _transformed_features(self, batch, dim: int) -> Array:
        """Materialized x' = (x − shift) .* factor (only for the dense-Hessian
        paths, where D is small)."""
        if isinstance(batch, SparseBatch):
            n = batch.indices.shape[0]
            rows = jnp.arange(n, dtype=batch.indices.dtype)[:, None]
            x = (
                jnp.zeros((n, dim), dtype=batch.values.dtype)
                .at[rows, batch.indices]
                .add(batch.values)
            )
        else:
            x = batch.features
        if self.normalization.shifts is not None:
            x = x - self.normalization.shifts
        if self.normalization.factors is not None:
            x = x * self.normalization.factors
        return x

    def hessian_diagonal(self, coef: Array, batch) -> Array:
        """diag(H) without materializing H (reference uses it for variance
        approximation, DistributedOptimizationProblem.scala:82-96).

        Sparse path stays sparse via the binomial expansion
        Σᵢ sᵢ(xᵢⱼ−shiftⱼ)² = Σᵢ sᵢxᵢⱼ² − 2·shiftⱼ·Σᵢ sᵢxᵢⱼ + shiftⱼ²·Σᵢ sᵢ
        — two segment-sums plus a scalar, no densification.
        """
        z = self.margins(coef, batch)
        with scope("photon.loss"):
            d2 = batch.weights * self.loss.d2(z, batch.labels)
        dim = coef.shape[-1]
        if isinstance(batch, SparseBatch):
            # diag(X^T D X): the same reductions as rmatvec, squared values
            with scope("photon.rmatvec"):
                return self._sparse_hessian_diagonal(batch, d2, dim)
        x = self._transformed_features(batch, dim)
        return jnp.sum(d2[:, None] * jnp.square(x), axis=0) + self.l2_weight

    def _sparse_hessian_diagonal(self, batch, d2: Array, dim: int) -> Array:
        windows = getattr(batch, "windows", None)
        if _use_windows(batch, d2):
            # same scatter-cliff reroute as rmatvec: Σᵢ d2ᵢ·xᵢⱼ² is a
            # windowed Xᵀ·d2 with squared stored values
            sq_windows = windows._replace(
                vals=jnp.square(windows.vals)
            )
            sq = _windowed_rmatvec_dispatch(
                sq_windows, d2, dim, self.mesh
            )
            if self.normalization.shifts is not None:
                lin = _windowed_rmatvec_dispatch(
                    windows, d2, dim, self.mesh
                )
                shifts = self.normalization.shifts
                sq = (
                    sq
                    - 2.0 * shifts * lin
                    + jnp.square(shifts) * jnp.sum(d2)
                )
            diag = sq
            if self.normalization.factors is not None:
                diag = diag * jnp.square(self.normalization.factors)
            return diag + self.l2_weight
        flat_idx = batch.indices.reshape(-1)
        sq = jax.ops.segment_sum(
            (jnp.square(batch.values) * d2[:, None]).reshape(-1),
            flat_idx,
            num_segments=dim,
        )
        if self.normalization.shifts is not None:
            lin = jax.ops.segment_sum(
                (batch.values * d2[:, None]).reshape(-1),
                flat_idx,
                num_segments=dim,
            )
            shifts = self.normalization.shifts
            sq = sq - 2.0 * shifts * lin + jnp.square(shifts) * jnp.sum(d2)
        diag = sq
        if self.normalization.factors is not None:
            diag = diag * jnp.square(self.normalization.factors)
        return diag + self.l2_weight

    # --- helpers ----------------------------------------------------------

    def with_l2(self, l2_weight: float) -> "GLMObjective":
        """Per-λ reweighting without rebuilding (reference mutable reg weight,
        DistributedOptimizationProblem.scala:62-73)."""
        return dataclasses.replace(self, l2_weight=l2_weight)

    def with_l1(self, l1_weight: float) -> "GLMObjective":
        return dataclasses.replace(self, l1_weight=l1_weight)
