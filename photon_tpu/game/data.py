"""GAME data containers and the bucketed random-effect dataset build.

TPU-native redesign of the reference's GAME data layer:

- ``GameData`` replaces ``RDD[GameDatum]`` (data/GameDatum.scala:56-58,
  GameConverters.scala:49-131) with a columnar host container: label /
  offset / weight columns, one matrix per feature shard (``CSRMatrix``, or
  ``DenseMatrix`` where the shard is handed in as its ``[n, d]`` array),
  and one string id column per entity tag. Sample identity is array
  position.

- ``RandomEffectDataset`` replaces the reference's
  ``activeData: RDD[(REId, LocalDataSet)]`` + projectors
  (data/RandomEffectDataSet.scala:47-56, :239-265;
  projector/IndexMapProjectorRDD.scala:34-110) with **size-bucketed, padded,
  masked device arrays**: entities are grouped by (sample-count, projected-
  feature-count) buckets; each bucket is a dense [E, n_max, d_max] block with
  per-entity column index maps (the index-compaction projector), per-row
  sample positions for score scatter, and an active-row mask produced by
  reservoir sampling. One ``vmap``-ped L-BFGS per bucket replaces the
  per-entity JVM solves (RandomEffectCoordinate.scala:104-127).

Everything here is host-side numpy; device transfer happens in the
coordinate layer.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Mapping, Sequence

import numpy as np

from photon_tpu.game.config import ProjectorType, RandomEffectCoordinateConfig
from photon_tpu.ops.losses import POSITIVE_RESPONSE_THRESHOLD

#: Entity key for mesh-padding rows: such rows carry weight 0 and belong to
#: no random-effect entity (they are skipped when grouping by entity).
PAD_ENTITY_KEY = "__photon_pad__"


@dataclasses.dataclass
class CSRMatrix:
    """Features-only CSR block (one feature shard)."""

    indptr: np.ndarray
    indices: np.ndarray
    values: np.ndarray
    num_cols: int

    @property
    def num_rows(self) -> int:
        return self.indptr.shape[0] - 1

    def row(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        lo, hi = self.indptr[i], self.indptr[i + 1]
        return self.indices[lo:hi], self.values[lo:hi]

    def to_dense(self, dtype=np.float32) -> np.ndarray:
        n, d = self.num_rows, self.num_cols
        out = np.zeros((n, d), dtype=dtype)
        if d and len(self.indices) == n * d and bool(
            np.all(np.diff(self.indptr) == d)
        ):
            # every row stores d slots: they go to their columns row by
            # row, a block of rows at a time, and no [n x d] int64 index is
            # built (8 bytes a cell: the row index, or the column indices
            # widened for the scatter)
            idx, val = self.indices.reshape(n, d), self.values.reshape(n, d)
            step = max(1, _CANONICAL_CHECK_CHUNK_ELEMS // d)
            for lo in range(0, n, step):
                np.put_along_axis(
                    out[lo : lo + step], idx[lo : lo + step],
                    val[lo : lo + step].astype(dtype, copy=False), axis=1,
                )
            return out
        rows = np.repeat(np.arange(n), np.diff(self.indptr))
        out[rows, self.indices] = self.values
        return out

    def slice_rows(self, lo: int, hi: int) -> "CSRMatrix":
        """Rows ``[lo, hi)``, re-based so the slice stands alone."""
        nz_lo, nz_hi = int(self.indptr[lo]), int(self.indptr[hi])
        return CSRMatrix(
            indptr=(self.indptr[lo : hi + 1] - nz_lo).astype(self.indptr.dtype),
            indices=self.indices[nz_lo:nz_hi],
            values=self.values[nz_lo:nz_hi],
            num_cols=self.num_cols,
        )

    def pad_rows(self, pad: int) -> "CSRMatrix":
        """``pad`` empty rows appended."""
        return CSRMatrix(
            indptr=np.concatenate(
                [self.indptr, np.full(pad, self.indptr[-1], self.indptr.dtype)]
            ),
            indices=self.indices,
            values=self.values,
            num_cols=self.num_cols,
        )

    @staticmethod
    def concat(mats: Sequence["CSRMatrix"]) -> "CSRMatrix":
        indptrs = [mats[0].indptr]
        base = int(mats[0].indptr[-1])
        for m in mats[1:]:
            indptrs.append(m.indptr[1:] + base)
            base += int(m.indptr[-1])
        return CSRMatrix(
            indptr=np.concatenate(indptrs),
            indices=np.concatenate([m.indices for m in mats]),
            values=np.concatenate([m.values for m in mats]),
            num_cols=mats[0].num_cols,
        )

    def to_ell(
        self, dtype=np.float32, nnz_pad_multiple: int = 8
    ) -> tuple[np.ndarray, np.ndarray]:
        """CSR → padded-ELL (indices [N, K] int32, values [N, K]) without
        densifying (see ``data.dataset.csr_to_ell``)."""
        from photon_tpu.data.dataset import csr_to_ell

        return csr_to_ell(
            self.indptr,
            self.indices,
            self.values,
            dtype=dtype,
            nnz_pad_multiple=nnz_pad_multiple,
        )

    @staticmethod
    def from_dense(x: np.ndarray) -> "CSRMatrix":
        n, d = x.shape
        mask = x != 0
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(mask.sum(axis=1), out=indptr[1:])
        return CSRMatrix(
            indptr=indptr,
            indices=np.nonzero(mask)[1].astype(np.int32),
            values=x[mask].astype(np.float64),
            num_cols=d,
        )


@dataclasses.dataclass
class DenseMatrix:
    """A feature shard handed in as its dense ``[n, d]`` array: every row
    holds every column. The coordinates take it as it is: the fixed effect
    places ``to_dense()`` (the array itself where the dtype is the
    array's), the random-effect build gathers its rows by index. Nothing
    on that path makes the CSR of full rows, which at ``[2**23, 128]``
    would cost an int32 index and a row index beside the values (PERF.md,
    PR 36).

    ``indptr`` / ``indices`` / ``values`` are that CSR all the same, made
    when read, for the consumers that walk stored slots (the host scorers,
    the request spool, the feature cache's writer): whatever takes a
    ``CSRMatrix`` takes this, at the CSR's cost."""

    array: np.ndarray  # [n, d]

    def __post_init__(self):
        self.array = np.asarray(self.array)
        if self.array.ndim != 2:
            raise ValueError(
                f"a dense shard is an [n, d] array, got shape {self.array.shape}"
            )

    @property
    def num_rows(self) -> int:
        return self.array.shape[0]

    @property
    def num_cols(self) -> int:
        return self.array.shape[1]

    def row(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        return np.arange(self.num_cols, dtype=np.int32), self.array[i]

    def to_dense(self, dtype=np.float32) -> np.ndarray:
        return np.asarray(self.array, dtype=dtype)

    to_ell = CSRMatrix.to_ell  # over the CSR of full rows below

    @property
    def indptr(self) -> np.ndarray:
        return np.arange(self.num_rows + 1, dtype=np.int64) * self.num_cols

    @property
    def indices(self) -> np.ndarray:
        return np.tile(np.arange(self.num_cols, dtype=np.int32), self.num_rows)

    @property
    def values(self) -> np.ndarray:
        return self.array.reshape(-1)

    def slice_rows(self, lo: int, hi: int) -> "DenseMatrix":
        return DenseMatrix(self.array[lo:hi])

    def pad_rows(self, pad: int) -> "DenseMatrix":
        """``pad`` rows of zeros appended (a CSR shard appends empty rows:
        the same numbers)."""
        return DenseMatrix(np.pad(self.array, [(0, pad), (0, 0)]))

    @staticmethod
    def concat(mats: Sequence["DenseMatrix"]) -> "DenseMatrix":
        return DenseMatrix(np.concatenate([m.array for m in mats]))


FeatureShard = CSRMatrix | DenseMatrix


@dataclasses.dataclass
class GameData:
    """Columnar GAME dataset: N samples, S feature shards, T id tags."""

    labels: np.ndarray
    offsets: np.ndarray
    weights: np.ndarray
    feature_shards: Mapping[str, FeatureShard]
    id_tags: Mapping[str, np.ndarray]  # tag → [N] array of entity keys
    uids: Sequence[str | None] | None = None  # per-sample ids (score output)
    #: ingest provenance, set by the reader that produced this data (the
    #: feature cache tags {"source": "cache", ...}); None for host-built
    #: or avro-decoded data. Informational only — slices/concats drop it.
    provenance: Mapping | None = None

    def __post_init__(self):
        n = self.num_samples
        for name, shard in self.feature_shards.items():
            if shard.num_rows != n:
                raise ValueError(f"shard {name} has {shard.num_rows} rows != {n}")
        for tag, col in self.id_tags.items():
            if len(col) != n:
                raise ValueError(f"id tag {tag} has {len(col)} rows != {n}")
        if self.uids is not None and len(self.uids) != n:
            raise ValueError(f"uids has {len(self.uids)} rows != {n}")

    @property
    def num_samples(self) -> int:
        return self.labels.shape[0]

    def shard_dataset(self, shard: str):
        """One feature shard + the shared label/offset/weight columns as a
        flat DataSet (the single-shard view the GLM stack consumes)."""
        from photon_tpu.data.dataset import DataSet

        m = self.feature_shards[shard]
        return DataSet(
            indptr=m.indptr,
            indices=m.indices,
            values=m.values,
            labels=self.labels,
            offsets=self.offsets,
            weights=self.weights,
            num_features=m.num_cols,
        )

    @staticmethod
    def build(
        labels: np.ndarray,
        feature_shards: Mapping[str, FeatureShard | np.ndarray],
        *,
        offsets: np.ndarray | None = None,
        weights: np.ndarray | None = None,
        id_tags: Mapping[str, Sequence] | None = None,
        uids: Sequence[str | None] | None = None,
    ) -> "GameData":
        """A shard handed in as a bare ``[n, d]`` array is a dense shard
        (:class:`DenseMatrix`): what is handed in decides."""
        n = len(labels)
        return GameData(
            labels=np.asarray(labels, dtype=np.float64),
            offsets=np.zeros(n) if offsets is None else np.asarray(offsets),
            weights=np.ones(n) if weights is None else np.asarray(weights),
            feature_shards={
                name: DenseMatrix(m) if isinstance(m, np.ndarray) else m
                for name, m in feature_shards.items()
            },
            id_tags={
                t: np.asarray(v).astype(str)
                for t, v in (id_tags or {}).items()
            },
            uids=uids,
        )


def slice_game_data(data: GameData, lo: int, hi: int) -> GameData:
    """Row-range view ``[lo, hi)`` of a GameData (CSR rows re-based so the
    slice is self-contained — the unit the streaming scorer consumes)."""
    lo = max(0, int(lo))
    hi = min(data.num_samples, int(hi))
    shards = {
        name: m.slice_rows(lo, hi) for name, m in data.feature_shards.items()
    }
    return GameData(
        labels=data.labels[lo:hi],
        offsets=data.offsets[lo:hi],
        weights=data.weights[lo:hi],
        feature_shards=shards,
        id_tags={t: np.asarray(col)[lo:hi] for t, col in data.id_tags.items()},
        uids=None if data.uids is None else list(data.uids[lo:hi]),
    )


def concat_game_data(pieces: Sequence[GameData]) -> GameData:
    """Concatenate GameData pieces row-wise (same shards / id tags / uid
    presence required). Used by the streaming chunk assembler to carry
    partial rows across avro part-file boundaries."""
    if not pieces:
        raise ValueError("concat_game_data needs at least one piece")
    if len(pieces) == 1:
        return pieces[0]
    first = pieces[0]
    shard_names = set(first.feature_shards)
    tag_names = set(first.id_tags)
    for p in pieces[1:]:
        if set(p.feature_shards) != shard_names or set(p.id_tags) != tag_names:
            raise ValueError("GameData pieces disagree on shards or id tags")
        if (p.uids is None) != (first.uids is None):
            raise ValueError("GameData pieces disagree on uid presence")
    shards = {}
    for name in first.feature_shards:
        mats = [p.feature_shards[name] for p in pieces]
        num_cols = mats[0].num_cols
        if any(m.num_cols != num_cols for m in mats):
            raise ValueError(f"shard {name} width differs across pieces")
        if all(isinstance(m, DenseMatrix) for m in mats):
            shards[name] = DenseMatrix.concat(mats)
        else:  # a dense piece among CSR ones joins them as its CSR
            shards[name] = CSRMatrix.concat(mats)
    uids = None
    if first.uids is not None:
        uids = [u for p in pieces for u in p.uids]
    return GameData(
        labels=np.concatenate([p.labels for p in pieces]),
        offsets=np.concatenate([p.offsets for p in pieces]),
        weights=np.concatenate([p.weights for p in pieces]),
        feature_shards=shards,
        id_tags={
            t: np.concatenate([np.asarray(p.id_tags[t]) for p in pieces])
            for t in first.id_tags
        },
        uids=uids,
    )


def entity_row_indices(index, keys, oov: int) -> np.ndarray:
    """Map entity keys to dense table rows, ``oov`` for unseen keys — the
    scoring-time entity lookup shared by random-effect and MF models."""
    keys = np.asarray(keys)
    return np.fromiter(
        (index.get(k, oov) for k in keys), dtype=np.int64, count=len(keys)
    )


def pad_game_data(data: GameData, multiple: int) -> GameData:
    """Round the sample count up to ``multiple`` with zero-weight rows.

    Mesh sharding needs every device-sharded dimension evenly divisible, so
    the estimator pads once at ingest; padding rows have weight 0 (invisible
    to every weighted reduction), empty feature rows, and the PAD_ENTITY_KEY
    id tag (excluded from random-effect grouping).
    """
    from photon_tpu.parallel.mesh import pad_rows_to_multiple

    n = data.num_samples
    target = pad_rows_to_multiple(n, multiple)
    if target == n:
        return data
    pad = target - n
    shards = {name: m.pad_rows(pad) for name, m in data.feature_shards.items()}
    id_tags = {
        tag: np.concatenate(
            [np.asarray(col).astype(str), np.full(pad, PAD_ENTITY_KEY)]
        )
        for tag, col in data.id_tags.items()
    }
    uids = None
    if data.uids is not None:
        uids = list(data.uids) + [None] * pad
    return GameData(
        labels=np.concatenate([data.labels, np.zeros(pad)]),
        offsets=np.concatenate([data.offsets, np.zeros(pad)]),
        weights=np.concatenate([data.weights, np.zeros(pad)]),
        feature_shards=shards,
        id_tags=id_tags,
        uids=uids,
    )


# ---------------------------------------------------------------------------
# Random-effect dataset build
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class REBucket:
    """One (n_max, d_max) size bucket of entities, ready for device.

    The layout separates TRAINING from SCORING (the reference's active/
    passive split, RandomEffectDataSet.scala:239-330, done TPU-first):

    - Train blocks hold ONLY the reservoir-capped ACTIVE rows, so the
      vmapped per-entity solves never touch a passive row and the row
      padding is bounded by the active upper bound — at CTR skew the
      head entities' tens of thousands of passive rows used to inflate
      the blocks ~2× past the data (VERDICT r4 weak #2).
    - Flat score arrays cover ALL kept rows (active + passive) with ZERO
      padding: per sample one compacted feature row, its entity slot and
      its global position — scoring is a row-gather of coefficients + an
      einsum + a unique scatter (the same shape as the validation
      scorer's `_REBucketValBlock`), not an einsum over padded blocks.

    features: [E, n_max, d_max] dense projected features (ACTIVE rows)
    labels/offsets/weights: [E, n_max] (weights 0 on padding)
    active_mask: [E, n_max] 1.0 where the row participates in training
    col_index: [E, d_max] global feature index per local column (-1 pad)
    sample_pos: [E, n_max] global sample position (num_samples ⇒ pad,
        out-of-bounds by construction so the residual gather clamps it)
    entity_ids: [E] dense entity index into the vocab
    score_feats: [M, d_max] compacted features of ALL kept rows (rows
        whose sample weight is 0 are zeroed so they score exactly 0)
    score_slot: [M] entity slot within this bucket per kept row
    score_pos: [M] global sample position per kept row
    """

    features: np.ndarray
    labels: np.ndarray
    offsets: np.ndarray
    weights: np.ndarray
    active_mask: np.ndarray
    col_index: np.ndarray
    sample_pos: np.ndarray
    entity_ids: np.ndarray
    score_feats: np.ndarray
    score_slot: np.ndarray
    score_pos: np.ndarray

    @property
    def num_entities(self) -> int:
        return self.features.shape[0]

    @property
    def padded_samples(self) -> int:
        return self.features.shape[1]

    @property
    def projected_dim(self) -> int:
        return self.features.shape[2]


@dataclasses.dataclass
class RandomEffectDataset:
    """All buckets for one random-effect coordinate + entity vocabulary."""

    random_effect_type: str
    feature_shard: str
    vocab: np.ndarray  # [num_entities] entity keys (strings)
    entity_index: dict  # key → dense index
    buckets: list[REBucket]
    num_samples: int
    num_features: int  # global feature dim of the shard
    # Random-projection matrix when projector_type == RANDOM (else None):
    projection_matrix: np.ndarray | None = None

    @property
    def num_entities(self) -> int:
        return len(self.vocab)

    def total_active_samples(self) -> int:
        return int(sum(b.active_mask.sum() for b in self.buckets))

    def shape_stats(self) -> dict:
        """Compile-bill accounting of the bucketed layout: each bucket is
        one traced solve sub-program per sweep program, and each DISTINCT
        (rows, d) shape is one solve program XLA must actually build —
        the unit the shape budget governs (compile_watch / PERF.md r6)."""
        shapes = sorted(
            {(b.padded_samples, b.projected_dim) for b in self.buckets}
        )
        return {
            "bucket_solves": len(self.buckets),
            "distinct_shapes": len(shapes),
            "shapes": [list(s) for s in shapes],
        }

    def memory_budget(self, bytes_per_element: int = 4) -> dict:
        """Device-memory accounting for the bucketed layout (VERDICT r2
        weak #4: the HBM footprint must be budgeted, not asserted): per
        bucket, feature blocks [E, n, d] dominate; labels/offsets/weights/
        train_weights are [E, n] each and sample_pos is int32 [E, n]."""
        per_bucket = []
        total = 0
        coefficients = 0
        for b in self.buckets:
            e, n_rows, d = b.features.shape
            feat = e * n_rows * d * bytes_per_element
            vecs = 4 * e * n_rows * bytes_per_element + e * n_rows * 4
            # flat score arrays: [M, d] features + two int32 [M] vectors
            score = b.score_feats.size * bytes_per_element + 2 * (
                b.score_pos.size * 4
            )
            per_bucket.append(
                {
                    "shape": [e, n_rows, d],
                    "bytes": int(feat + vecs + score),
                    "score_rows": int(b.score_pos.size),
                }
            )
            total += feat + vecs + score
            coefficients += e * d
        return {
            "buckets": per_bucket,
            "total_bytes": int(total),
            "coefficient_count": int(coefficients),
            "coefficient_bytes": int(coefficients * bytes_per_element),
        }

    def padding_waste(self) -> dict:
        """Padding-waste accounting per bucket (VERDICT r1 weak #5): rows
        actually carrying ACTIVE samples vs. total padded training rows
        shipped to device. Scoring pays zero padding by construction (flat
        per-sample arrays), so the ``score_rows`` count is exact — only the
        train blocks can waste compute."""
        per_bucket = []
        used_total = 0
        padded_total = 0
        score_rows_total = 0
        for b in self.buckets:
            used = int((b.active_mask > 0).sum())
            padded = int(b.labels.size)
            per_bucket.append(
                {
                    "shape": list(b.features.shape),
                    "used_cells": used,
                    "padded_cells": padded,
                    "waste": round(1.0 - used / padded, 4) if padded else 0.0,
                    "score_rows": int(b.score_pos.size),
                }
            )
            used_total += used
            padded_total += padded
            score_rows_total += int(b.score_pos.size)
        return {
            "buckets": per_bucket,
            "total_used": used_total,
            "total_padded": padded_total,
            "score_rows": score_rows_total,
            "total_waste": (
                round(1.0 - used_total / padded_total, 4) if padded_total else 0.0
            ),
        }


def _ceil_pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def _ceil_pow2_vec(arr: np.ndarray, floor: int) -> np.ndarray:
    """Elementwise next power of two ≥ floor (exact: log2 of a power of two
    is exactly representable in float64)."""
    a = np.maximum(np.asarray(arr, dtype=np.int64), floor)
    return (1 << np.ceil(np.log2(a)).astype(np.int64)).astype(np.int64)


#: default cap on the TOTAL distinct (rows, d) bucket shapes across the
#: RE coordinates of one fit (split across d-groups — see ShapePool and
#: _split_shape_budget). Chosen from the measured config-5 CPU-shape
#: tradeoff curve (PERF.md r6): the pooled level DP at 11 levels cuts
#: distinct solve shapes 19 → 11 (1.7×) for +0.5 points of padding
#: waste; 12 is a free lunch (−1.1 points) but saves fewer programs; 10
#: and below blow the ≤2-point padding budget at bench skew (+2.7
#: points at 10, +5.9 at 9). At the config-5 NOMINAL shape the curve is
#: friendlier (both coordinates saturate the 16-level cap): budget 10
#: projects 2.1× fewer bucket programs at +1.3 points.
DEFAULT_SHAPE_BUDGET = 11


def re_shape_budget(config_value: int | None = None) -> int | None:
    """Resolve the effective shape budget for one RE coordinate — the cap
    on the coordinate's (or, pooled, the fit's) TOTAL distinct (rows, d)
    bucket shapes, split across d-groups (_split_shape_budget).

    Precedence: ``PHOTON_RE_SHAPE_BUDGET`` env (A/B lever; ``0`` disables)
    > the config's ``shape_budget`` field (``0`` disables) >
    ``DEFAULT_SHAPE_BUDGET``. Returns None when disabled. Single parse
    site — the checkpoint fingerprint must hash the same resolution the
    build uses (a different budget changes the per-bucket state SHAPES,
    so resuming across it must be the clean stale-config error)."""
    env = os.environ.get("PHOTON_RE_SHAPE_BUDGET", "").strip()
    if env:
        v = int(env)
        return v if v > 0 else None
    if config_value is not None:
        return config_value if config_value > 0 else None
    return DEFAULT_SHAPE_BUDGET


def _split_shape_budget(budget: int | None, n_groups: int) -> int | None:
    """Per-d-group share of a distinct-shape budget: the budget bounds the
    TOTAL distinct (rows, d) count, so a multi-width level set splits it.
    Single definition — ShapePool.freeze and the unpooled per-coordinate
    fallback must agree, or the same knob means two different caps."""
    if budget is None or n_groups <= 1:
        return budget
    return max(1, budget // n_groups)


def _optimal_row_levels(
    sizes: np.ndarray,
    waste_target: float = 0.12,
    max_levels: int = 16,
    shape_budget: int | None = None,
) -> np.ndarray:
    """Row-count quantization levels minimizing padded rows.

    Power-of-two rounding wastes up to 50% per entity and compounds under
    bucket merging (measured 0.49-0.60 total at bench Zipf skew, VERDICT r4
    weak #2). Instead: sort the distinct active-row counts, DP-partition
    them into K contiguous segments (cost of a segment = entity count ×
    its max size — every member pads up to the segment max), and take the
    SMALLEST K whose optimal waste is ≤ ``waste_target`` (capped at
    ``max_levels`` — each level is one compiled program shape, and
    compiles are the dominant fixed cost of a cold fit).
    O(U²·K) over U distinct sizes; U is bounded by the active upper bound,
    and single-size datasets short-circuit.

    ``shape_budget`` tightens the level cap below ``max_levels`` (the
    compile-bill governor, VERDICT r5 next #5): the DP then returns the
    waste-OPTIMAL ≤-budget partition — strictly better than merging an
    unbudgeted level set after the fact, because segment boundaries move
    jointly instead of greedily.
    """
    if shape_budget is not None:
        max_levels = min(max_levels, int(shape_budget))
    u, c = np.unique(np.asarray(sizes, dtype=np.int64), return_counts=True)
    U = len(u)
    if U <= 1:
        return u
    C = np.concatenate(([0], np.cumsum(c)))
    used = float((u * c).sum())
    budget = used / max(1.0 - waste_target, 1e-9)
    dp_prev = np.full(U + 1, np.inf)
    dp_prev[0] = 0.0
    args: list[np.ndarray] = []
    best_k = None
    for _k in range(1, min(max_levels, U) + 1):
        dp_k = np.full(U + 1, np.inf)
        arg_k = np.zeros(U + 1, dtype=np.int64)
        for j in range(1, U + 1):
            cand = dp_prev[:j] + (C[j] - C[:j]) * u[j - 1]
            a = int(np.argmin(cand))
            dp_k[j] = cand[a]
            arg_k[j] = a
        args.append(arg_k)
        dp_prev = dp_k
        if dp_k[U] <= budget:
            best_k = _k
            break
    if best_k is None:
        best_k = len(args)  # max_levels levels: best achievable waste
    levels = []
    j, k = U, best_k
    while k > 0:
        i = args[k - 1][j]
        levels.append(int(u[j - 1]))
        j = int(i)
        k -= 1
    return np.asarray(sorted(levels), dtype=np.int64)


def _shard_major_entity_order(
    loads: np.ndarray, entity_shards: int
) -> np.ndarray:
    """Order a bucket's entities shard-major with balanced per-shard load.

    Capacity-constrained balanced assignment (reference
    RandomEffectDataSetPartitioner.scala:113-147 greedily packs the
    heaviest entities onto the least-loaded partition): the bucket's
    entity axis will be block-split into ``entity_shards`` contiguous
    chunks after padding, so chunk capacities are fixed. Entities are
    taken heaviest-first and dealt SNAKE-wise across the shards that
    still have room (forward, then reverse, alternating per round) —
    the classic zigzag partition, whose per-shard load gap is bounded
    by one entity's load per direction change. Fully vectorized: the
    r4 per-entity least-loaded greedy (argmin per entity) was 81 s of
    a 109 s dataset build at 6.25M entities and would dominate the 10⁹-
    coefficient build. The trailing chunk keeps the slack for
    mesh-padding lanes. Returns a permutation of entity slots
    (shard-major, ascending original index within a shard).
    """
    e = len(loads)
    e_pad = ((e + entity_shards - 1) // entity_shards) * entity_shards
    chunk = e_pad // entity_shards
    # Real entities fill slots [0, e); chunk s covers slots
    # [s*chunk, (s+1)*chunk), so its REAL capacity is clipped by e —
    # padding lanes occupy the tail slots of the final chunk(s).
    # Capacities are non-increasing in s.
    caps = np.clip(
        e - chunk * np.arange(entity_shards, dtype=np.int64), 0, chunk
    )
    order = np.argsort(-loads, kind="stable")  # heaviest first
    # round r (0..chunk-1) visits the k_r shards with capacity > r —
    # always a PREFIX [0, k_r) because caps are non-increasing
    ks = np.searchsorted(-caps, -np.arange(chunk, dtype=np.int64),
                         side="left")
    starts = np.concatenate(([0], np.cumsum(ks)))
    assert starts[-1] == e
    rr = np.repeat(np.arange(chunk, dtype=np.int64), ks)
    pos = np.arange(e, dtype=np.int64) - starts[rr]
    shard_seq = np.where(rr % 2 == 0, pos, ks[rr] - 1 - pos)
    shard_of = np.empty(e, dtype=np.int64)
    shard_of[order] = shard_seq
    # shard-major layout; stable sort keeps ascending original order
    # within a shard
    return np.argsort(shard_of, kind="stable").astype(np.int64)


def _pack_shape_keys(n_pad: np.ndarray, d_pad: np.ndarray) -> np.ndarray:
    """(n, d) padded shape → one int64 sort key (single packing site)."""
    return n_pad.astype(np.int64) << 32 | d_pad.astype(np.int64)


#: auto-consolidation stops at merges adding this many padded cells: 1M
#: f32 cells ≈ 4 MB of extra blocks ≈ microseconds of VPU/HBM work, traded
#: against one saved per-sweep program dispatch (tens of µs on device)
_MERGE_CELL_BUDGET = 1_000_000

#: bool elements per chunk for the canonical-index check below — bounds
#: the comparison intermediate at ~4 MB regardless of N
_CANONICAL_CHECK_CHUNK_ELEMS = 1 << 22


def _rows_are_canonical(
    indices: np.ndarray, num_rows: int, num_cols: int
) -> bool:
    """True when every stored row's column indices are exactly
    ``0..num_cols-1`` in order (storage order == column order, the
    precondition for reshaping CSR values straight to [N, d]).

    Checked in fixed-size ROW CHUNKS: a one-shot
    ``indices.reshape(N, d) == arange(d)`` materializes a full [N, d]
    bool array — ~4 GB transient at the 10⁹-coefficient north-star shape
    (2.5e8×16), pure peak-RSS pressure during the build the fast path
    exists to speed up (ADVICE r5 #1). Chunking keeps the intermediate
    at ~4 MB and preserves the early-exit on first mismatch.
    """
    if num_cols <= 0:
        return False
    idx2d = indices.reshape(num_rows, num_cols)
    expect = np.arange(num_cols, dtype=indices.dtype)
    chunk = max(1, _CANONICAL_CHECK_CHUNK_ELEMS // num_cols)
    for start in range(0, num_rows, chunk):
        block = idx2d[start : start + chunk]
        if not np.array_equal(
            block, np.broadcast_to(expect, block.shape)
        ):
            return False
    return True


def _rows_are_full(shard) -> bool:
    """Whether the block fills can gather rows straight from an ``[n, d]``
    value matrix: a dense shard, or a CSR shard whose every row stores all
    columns. Full rows alone are not enough there: reshaping the values
    assumes STORAGE order == column order, and readers may emit full rows
    with unsorted indices (the intercept appended last), so the per-row
    index pattern is verified too. ``PHOTON_RE_DENSE_FAST=0`` sends every
    shard through the per-nonzero machinery (the A/B lever)."""
    if shard.num_cols <= 0 or os.environ.get("PHOTON_RE_DENSE_FAST", "1") == "0":
        return False
    if isinstance(shard, DenseMatrix):
        return True
    return bool(
        np.all((shard.indptr[1:] - shard.indptr[:-1]) == shard.num_cols)
    ) and _rows_are_canonical(shard.indices, shard.num_rows, shard.num_cols)


def _consolidate_shapes(
    keys: np.ndarray,
    counts: np.ndarray,
    max_buckets: int | None,
    cell_allowance: int | None = None,
) -> np.ndarray | None:
    """Merge small size-buckets until at most ``max_buckets`` distinct
    (n, d) shapes remain (VERDICT r3 weak #5: 17 sequential bucket solves
    per coordinate per sweep is a dispatch-bound tail on device; fewer,
    larger vmapped blocks trade padded cells for program count).

    ``keys``/``counts`` are the unique packed shape keys and their entity
    counts. Returns the merged key per input class (or None when nothing
    merges). Greedy: repeatedly merge the PAIR of shapes whose union shape
    (elementwise max) adds the fewest padded cells across both shapes'
    entities. Two stopping rules compose:

    * auto (always on): keep merging while the best merge adds fewer than
      ``_MERGE_CELL_BUDGET`` padded cells. The unit is absolute on
      purpose: one bucket = one dispatched program per sweep (tens of µs
      on device), while a padded cell costs ~ns of VPU/HBM time — so a
      sub-million-cell merge is always profitable, and a huge merge (e.g.
      doubling a million-entity bucket's rows) is always refused,
      independent of what fraction of the dataset it is;
    * ``max_buckets`` hard cap (optional): keep merging regardless of cost
      until the count is reached — for on-chip A/B of the padding-vs-
      program-count tradeoff (``PHOTON_RE_MAX_BUCKETS`` overrides; 0
      disables consolidation entirely);
    * ``cell_allowance`` (optional): total extra padded cells all merges
      together may add. The build passes the coordinate's remaining waste
      budget here so consolidation cannot undo the DP-optimal row levels —
      without it, re-merging a large tail bucket one level up is cheap in
      absolute cells yet pushes total waste far past the target (the exact
      regression VERDICT r4 weak #2 measured).

    Deterministic, so sharded==unsharded bucketing stays stable.
    """
    env = os.environ.get("PHOTON_RE_MAX_BUCKETS", "").strip()
    if env:
        max_buckets = int(env)
    if max_buckets is not None and max_buckets <= 0:
        return None  # 0 (or anything non-positive) disables consolidation
    shapes = [
        [int(k >> 32), int(k & 0xFFFFFFFF), int(c)]
        for k, c in zip(keys, counts)
    ]
    # target[i] = index of the shape entity-class i was merged into
    target = list(range(len(shapes)))
    alive = set(target)
    merged_any = False
    while len(alive) > 1:
        best = None
        alive_list = sorted(alive)
        for ai in range(len(alive_list)):
            for bi in range(ai + 1, len(alive_list)):
                a, b = shapes[alive_list[ai]], shapes[alive_list[bi]]
                nm, dm = max(a[0], b[0]), max(a[1], b[1])
                added = a[2] * (nm * dm - a[0] * a[1]) + b[2] * (
                    nm * dm - b[0] * b[1]
                )
                if best is None or added < best[0]:
                    best = (added, alive_list[ai], alive_list[bi], nm, dm)
        added, ai, bi, nm, dm = best
        over_cap = max_buckets is not None and len(alive) > max_buckets
        budget = _MERGE_CELL_BUDGET
        if cell_allowance is not None:
            budget = min(budget, cell_allowance + 1)
        if not over_cap and added >= budget:
            break
        shapes[ai] = [nm, dm, shapes[ai][2] + shapes[bi][2]]
        alive.discard(bi)
        if cell_allowance is not None:
            # forced (over-cap) merges are charged too, floored at 0:
            # `cell_allowance` documents the TOTAL cells all merges may
            # add, so a small max_buckets must not leave the voluntary
            # phase its full original budget on top of the forced spend
            cell_allowance = max(0, cell_allowance - added)
        merged_any = True
        for i, t in enumerate(target):
            if t == bi:
                target[i] = ai
    if not merged_any:
        return None
    return np.asarray(
        [
            np.int64(shapes[target[i]][0]) << 32
            | np.int64(shapes[target[i]][1])
            for i in range(len(keys))
        ]
    )


class ShapePool:
    """Cross-coordinate bucket-shape consolidation (the shape budget).

    Each distinct (rows, d) bucket shape is one traced-and-compiled solve
    program, and the r5 DP row levels — optimal per coordinate — produce
    near-duplicate level sets ACROSS coordinates (user {1,2,4,9,23,55,128}
    vs item {2,4,6,8,11,17,...} at bench skew) that multiply the compile
    bill for no modeling benefit (VERDICT r5 weak #4 / next #5). The pool
    runs the row-level DP ONCE per d-group over the POOLED per-entity
    size distribution of every participating coordinate, so all of them
    snap to one shared level set. This is provably the padded-cell
    optimum among all schemes that bound the global distinct-shape count:
    any scheme is some union level set L that every coordinate snaps up
    into, and the pooled DP minimizes total padded cells over |L| ≤
    budget. λ-grid points share shapes by construction (the grid reuses
    the built coordinates; λ is a traced scalar).

    Protocol: ``observe(d_pad, n_trn)`` per coordinate (from
    ``profile_random_effect_shapes`` — exact for dense-fast-path and
    random-projection shards), ``freeze()`` once, then pass the pool to
    ``build_random_effect_dataset``. Coordinates whose shard cannot be
    cheaply profiled (general sparse index-compaction: d_proj needs the
    per-nonzero pair machinery) opt out and fall back to the
    per-coordinate budgeted DP — they still share any level that
    coincides, they just don't steer the pooled optimum.
    """

    def __init__(self, budget: int | None, waste_target: float = 0.12):
        self.budget = budget
        self.waste_target = waste_target
        self._sizes: dict[int, list[np.ndarray]] = {}
        self._levels: dict[int, np.ndarray] = {}
        self._frozen = False

    def observe(self, d_pad: np.ndarray, n_trn: np.ndarray) -> None:
        if self._frozen:
            raise RuntimeError("ShapePool is frozen")
        d_pad = np.asarray(d_pad, dtype=np.int64)
        n_trn = np.asarray(n_trn, dtype=np.int64)
        for dv in np.unique(d_pad):
            self._sizes.setdefault(int(dv), []).append(n_trn[d_pad == dv])

    def freeze(self) -> "ShapePool":
        if not self._frozen:
            group_budget = _split_shape_budget(self.budget, len(self._sizes))
            for dv, chunks in self._sizes.items():
                self._levels[dv] = _optimal_row_levels(
                    np.concatenate(chunks),
                    waste_target=self.waste_target,
                    shape_budget=group_budget,
                )
            self._frozen = True
        return self

    def covers(self, d: int) -> bool:
        return self._frozen and int(d) in self._levels

    def levels_for(self, d: int, sizes: np.ndarray) -> np.ndarray:
        """Shared levels for one d-group, extended to cover ``sizes`` (a
        defensive top-up only — an exact profile already saw them)."""
        levels = self._levels[int(d)]
        top = int(np.max(sizes)) if len(sizes) else 0
        if top > int(levels[-1]):
            levels = np.concatenate([levels, [top]])
        return levels

    def stats(self) -> dict:
        return {
            "budget": self.budget,
            "levels_per_d_group": {
                str(d): [int(x) for x in lv]
                for d, lv in sorted(self._levels.items())
            },
            "distinct_shapes": int(sum(len(lv) for lv in self._levels.values())),
        }


def profile_random_effect_shapes(
    data: GameData,
    config: RandomEffectCoordinateConfig,
    *,
    existing_model_keys=None,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Cheap exact (d_pad, n_trn) per-entity shape profile of the build —
    the input ``ShapePool.observe`` needs, WITHOUT the block fills.

    Exact because every input of the bucket-shape decision is
    deterministic in the entity size histogram: active counts are
    ``min(counts, upper_bound)`` regardless of which rows the reservoir
    picks, the entity lower bound reads raw counts, and the projected
    width is the shard column count (dense fast path) or the fixed
    random-projection dim. Returns None for shards it cannot profile
    without the per-nonzero pair machinery (general sparse index
    compaction / Pearson capping) — those coordinates opt out of pooling.
    """
    shard = data.feature_shards[config.feature_shard]
    if config.projector_type == ProjectorType.RANDOM:
        d_proj = config.random_projection_dim or 64
    elif config.features_to_samples_ratio is None and _rows_are_full(shard):
        d_proj = shard.num_cols
    else:
        return None
    keys = np.asarray(data.id_tags[config.random_effect_type])
    valid = keys[keys != PAD_ENTITY_KEY]
    vocab, counts = np.unique(valid, return_counts=True)
    entity_kept = counts >= config.active_data_lower_bound
    if existing_model_keys is not None:
        has_prior = np.isin(vocab, np.asarray(list(existing_model_keys)))
        entity_kept = entity_kept | ~has_prior
    counts = counts[entity_kept]
    ub = config.active_data_upper_bound
    n_trn = np.maximum(
        np.minimum(counts, ub) if ub is not None else counts, 1
    ).astype(np.int64)
    d_pad = np.full(len(n_trn), _ceil_pow2(max(int(d_proj), 1)), np.int64)
    return d_pad, n_trn


def build_random_effect_dataset(
    data: GameData,
    config: RandomEffectCoordinateConfig,
    *,
    seed: int = 0,
    intercept_col: int | None = None,
    entity_shards: int = 1,
    existing_model_keys=None,
    shape_pool: ShapePool | None = None,
) -> RandomEffectDataset:
    """Group samples by entity, apply bounds/sampling/projection, bucket.

    Mirrors RandomEffectDataSet.apply (:239-265): group by entity with a
    reservoir-sampling training cap, drop entities below the lower bound,
    per-entity feature selection (index compaction + Pearson cap,
    LocalDataSet.filterFeaturesByPearsonCorrelationScore:135,221-276), then —
    TPU-specific — pack each entity's ACTIVE rows into padded dense train
    blocks at DP-optimal (n, d) size levels, and every kept row (active +
    passive) into flat, padding-free score arrays (the reference's active/
    passive split, RandomEffectDataSet.scala:239-330).

    Fully vectorized (VERDICT r1 missing #4): grouping via argsort + segment
    boundaries, reservoir caps via per-row random keys ranked within entity,
    per-entity feature unions and Pearson correlations via (entity, column)
    pair segment-sums over the CSR nonzeros, block fill via per-bucket fancy
    indexing. No per-row/per-nonzero Python loops — a 10⁶-sample build is
    seconds, not hours.

    ``entity_shards`` > 1 orders each bucket's entities shard-major with a
    vectorized snake-deal over active-row loads (reference
    RandomEffectDataSetPartitioner's balancing goal; see
    _shard_major_entity_order) so the coordinate's block split over the
    mesh entity axis is balanced.
    """
    rng = np.random.default_rng(seed)
    shard = data.feature_shards[config.feature_shard]
    keys = np.asarray(data.id_tags[config.random_effect_type])
    n = data.num_samples

    # --- group rows by entity -----------------------------------------
    # mesh-padding rows (PAD_ENTITY_KEY) belong to no entity
    valid_idx = np.flatnonzero(keys != PAD_ENTITY_KEY)
    vocab, entity_of_valid = np.unique(keys[valid_idx], return_inverse=True)
    num_v = len(vocab)
    counts = np.bincount(entity_of_valid, minlength=num_v)

    # sample indices sorted by entity (ascending sample order within entity)
    order = valid_idx[np.argsort(entity_of_valid, kind="stable")]
    ent_sorted = np.repeat(np.arange(num_v), counts)
    group_starts = np.zeros(num_v + 1, dtype=np.int64)
    np.cumsum(counts, out=group_starts[1:])

    rnd_proj = None
    if config.projector_type == ProjectorType.RANDOM:
        k = config.random_projection_dim or 64
        rnd_proj = rng.normal(size=(shard.num_cols, k)) / np.sqrt(k)

    # --- active selection: reservoir cap via random keys --------------
    ub = config.active_data_upper_bound
    if ub is not None and len(order):
        rand_keys = rng.random(len(order))
        # random order within each entity; rank < ub ⇒ active
        sel = np.lexsort((rand_keys, ent_sorted))
        rank = np.arange(len(order)) - group_starts[ent_sorted]
        active_sorted = np.empty(len(order), dtype=bool)
        active_sorted[sel] = rank < ub
    else:
        active_sorted = np.ones(len(order), dtype=bool)
    active_counts = np.minimum(counts, ub) if ub is not None else counts

    # --- passive filtering + entity lower bound -----------------------
    # strict '>' keeps passive rows, matching the reference's
    # `.filter(_._2 > passiveDataLowerBound)`
    num_passive = counts - active_counts
    drop_passive = (num_passive > 0) & (
        num_passive <= config.passive_data_lower_bound
    )
    entity_kept = counts >= config.active_data_lower_bound
    if existing_model_keys is not None:
        # ignoreThresholdForNewModels: entities WITHOUT a prior model bypass
        # the lower bound; entities with one must still meet it (reference
        # RandomEffectDataSet.generateActiveData:
        # `size >= lowerBound || !existingKeys.contains(key)`).
        has_prior = np.isin(vocab, np.asarray(list(existing_model_keys)))
        entity_kept = entity_kept | ~has_prior
    keep_sorted = entity_kept[ent_sorted] & (
        active_sorted | ~drop_passive[ent_sorted]
    )

    kept_rows = order[keep_sorted]  # global sample indices
    kept_ent = ent_sorted[keep_sorted]
    kept_active = active_sorted[keep_sorted].astype(np.float64)
    n_k = np.bincount(kept_ent, minlength=num_v)
    kept_starts = np.zeros(num_v + 1, dtype=np.int64)
    np.cumsum(n_k, out=kept_starts[1:])
    row_rank = np.arange(len(kept_rows)) - kept_starts[kept_ent]

    # --- nonzeros of kept rows ----------------------------------------
    # FAST DENSE PATH: when every row stores ALL columns (a dense shard
    # routed through CSR) and no per-entity feature selection applies,
    # the (entity, column) pair machinery is pure overhead — at 10⁹-
    # coefficient scale it materializes ~45 GB of per-nonzero arrays and
    # sorts 10⁹ pair keys on the host. Each entity's compacted space is
    # then the full column space (col_index = arange), and block/score
    # fills become direct row gathers from the [N, d] value matrix.
    fast_dense = (
        rnd_proj is None
        and config.features_to_samples_ratio is None
        and _rows_are_full(shard)
    )
    if fast_dense:
        # a dense shard's own array, or a full-row CSR's values seen as one
        x2d = np.ascontiguousarray(
            shard.values.reshape(shard.num_rows, shard.num_cols),
            dtype=np.float32,
        )
        local_of_pair = pair_inv = None
        d_proj = np.full(num_v, shard.num_cols)
    else:
        nnz_per_row = (
            shard.indptr[kept_rows + 1] - shard.indptr[kept_rows]
        ).astype(np.int64)
        # gather each kept row's nonzero span
        nnz_src = _concat_ranges(shard.indptr[kept_rows], nnz_per_row)
        nnz_col = shard.indices[nnz_src].astype(np.int64)
        nnz_val = shard.values[nnz_src].astype(np.float64)
        nnz_ent = np.repeat(kept_ent, nnz_per_row)
        nnz_rowpos = np.repeat(np.arange(len(kept_rows)), nnz_per_row)

        local_of_pair = None
        pair_inv = None
        d_proj = np.full(
            num_v, rnd_proj.shape[1] if rnd_proj is not None else 0
        )
    if not fast_dense and rnd_proj is None:
        # --- index-compaction projection: per-entity feature unions ----
        combined = nnz_ent * np.int64(shard.num_cols) + nnz_col
        pairs, pair_inv = np.unique(combined, return_inverse=True)
        pair_ent = (pairs // shard.num_cols).astype(np.int64)
        pair_col = (pairs % shard.num_cols).astype(np.int64)
        d_all = np.bincount(pair_ent, minlength=num_v)
        pair_starts = np.searchsorted(pair_ent, np.arange(num_v))

        keep_pair = np.ones(len(pairs), dtype=bool)
        if config.features_to_samples_ratio is not None:
            cap = np.maximum(
                1,
                (config.features_to_samples_ratio * active_counts).astype(
                    np.int64
                ),
            )
            needs_cap = d_all > cap
            if needs_cap.any():
                # Pearson |corr(feature, label)| per (entity, column) pair
                # over ACTIVE rows, via segment sums on the nonzeros
                # (zero entries contribute nothing to the raw sums).
                w_act = kept_active[nnz_rowpos]
                y_nnz = data.labels[kept_rows][nnz_rowpos]
                m = len(pairs)
                sum_x = np.bincount(
                    pair_inv, weights=nnz_val * w_act, minlength=m
                )
                sum_x2 = np.bincount(
                    pair_inv, weights=nnz_val**2 * w_act, minlength=m
                )
                sum_xy = np.bincount(
                    pair_inv, weights=nnz_val * y_nnz * w_act, minlength=m
                )
                y_kept = data.labels[kept_rows]
                n_act = np.bincount(
                    kept_ent, weights=kept_active, minlength=num_v
                )
                sum_y = np.bincount(
                    kept_ent, weights=y_kept * kept_active, minlength=num_v
                )
                sum_y2 = np.bincount(
                    kept_ent, weights=y_kept**2 * kept_active, minlength=num_v
                )
                na = n_act[pair_ent]
                var_x = sum_x2 - sum_x**2 / np.maximum(na, 1)
                var_y = (sum_y2 - sum_y**2 / np.maximum(n_act, 1))[pair_ent]
                denom = np.sqrt(np.maximum(var_x * var_y, 0.0))
                num = np.abs(sum_xy - sum_x * sum_y[pair_ent] / np.maximum(na, 1))
                corr = np.where(denom > 0, num / np.where(denom > 0, denom, 1), 0.0)
                if intercept_col is not None:
                    corr = np.where(pair_col == intercept_col, np.inf, corr)
                # rank pairs within entity by descending corr (ties: ascending
                # column, matching argsort stability over ascending cols)
                by_corr = np.lexsort((pair_col, -corr, pair_ent))
                corr_rank = np.empty(m, dtype=np.int64)
                corr_rank[by_corr] = (
                    np.arange(m) - pair_starts[pair_ent[by_corr]]
                )
                cap_eff = np.where(needs_cap, cap, np.iinfo(np.int64).max)
                keep_pair = corr_rank < cap_eff[pair_ent]

        # local column index per kept pair: rank among kept pairs within
        # entity in ascending-column order (pairs are already ent-major,
        # col-ascending from np.unique)
        csum = np.cumsum(keep_pair)
        base = np.concatenate(([0], csum))[pair_starts]
        local_of_pair = np.where(
            keep_pair, csum - 1 - base[pair_ent], -1
        ).astype(np.int64)
        d_proj = np.bincount(pair_ent[keep_pair], minlength=num_v)

    # --- bucket assignment (vectorized; a 10⁶-entity per-entity Python
    # loop costs more than the rest of the build combined) ---------------
    # TRAIN blocks hold only ACTIVE rows, so shapes key on the active
    # count — DP-optimal row levels (waste-bounded) instead of power-of-
    # two rounding, which wasted up to 60% of RE compute at bench Zipf
    # skew (VERDICT r4 weak #2). Passive rows live in the flat score
    # arrays, padding-free.
    n_act = np.bincount(
        kept_ent, weights=kept_active, minlength=num_v
    ).astype(np.int64)
    # rank among the entity's ACTIVE rows (garbage on passive rows — only
    # read under the active mask)
    act = kept_active > 0
    act_prefix = np.concatenate(([0], np.cumsum(act)))
    act_rank = (act_prefix[1:] - 1) - act_prefix[kept_starts[kept_ent]]

    ent_list = np.flatnonzero(entity_kept & (n_k > 0))
    n_trn = np.maximum(n_act[ent_list], 1)
    d_pad = _ceil_pow2_vec(np.maximum(d_proj[ent_list], 1), floor=8)
    n_lvl = np.empty_like(n_trn)
    budget = re_shape_budget(config.shape_budget)
    d_groups = np.unique(d_pad)
    group_budget = _split_shape_budget(budget, len(d_groups))
    pooled_groups = 0
    for dv in d_groups:
        grp = d_pad == dv
        if (
            budget is not None
            and shape_pool is not None
            and shape_pool.covers(int(dv))
        ):
            # shared pooled levels: every participating coordinate snaps
            # into ONE level set, so same-width coordinates contribute
            # the same (n, d) solve shapes to the compile bill
            levels = shape_pool.levels_for(int(dv), n_trn[grp])
            pooled_groups += 1
        else:
            levels = _optimal_row_levels(
                n_trn[grp], shape_budget=group_budget
            )
        n_lvl[grp] = levels[np.searchsorted(levels, n_trn[grp])]
    combined = _pack_shape_keys(n_lvl, d_pad)
    shape_keys, shape_inv = np.unique(combined, return_inverse=True)
    # consolidation may spend at most the remaining waste budget on top of
    # the DP levels (plus the absolute per-merge cap) — see
    # _consolidate_shapes. Under an active shape budget the greedy pass
    # is SKIPPED (unless a hard cap forces it): the ≤-budget DP / pooled
    # level set IS the consolidation policy there, and per-coordinate
    # greedy merges on top would both de-share the cross-coordinate
    # level set and make a standalone rebuild diverge from the
    # estimator's pooled build (model buckets must stay reproducible
    # from (data, config, seed) alone in the single-coordinate case).
    used_cells = int((n_trn * d_pad).sum())
    padded_cells = int((n_lvl * d_pad).sum())
    allowance = max(0, int(0.18 * used_cells) - (padded_cells - used_cells))
    env_cap = os.environ.get("PHOTON_RE_MAX_BUCKETS", "").strip()
    hard_cap = config.max_buckets is not None or (
        env_cap != "" and int(env_cap) > 0
    )
    merged = (
        _consolidate_shapes(
            shape_keys,
            np.bincount(shape_inv, minlength=len(shape_keys)),
            config.max_buckets,
            cell_allowance=allowance,
        )
        if len(shape_keys) > 1 and (budget is None or hard_cap)
        else None
    )
    if merged is not None:
        combined = merged[shape_inv]
        shape_keys, shape_inv = np.unique(combined, return_inverse=True)
    inv_order = np.argsort(shape_inv, kind="stable")
    shape_counts = np.bincount(shape_inv, minlength=len(shape_keys))
    shape_bounds = np.concatenate(([0], np.cumsum(shape_counts)))
    # One bucket per shape, however many entities: a bucket is one vmapped
    # solve inside the coordinate's sweep program, and that solve bounds its
    # own device memory by looping over entity chunks (game/coordinate.py,
    # RE_SOLVE_BYTES); under a mesh every shard loops alone, with no
    # collective to keep short.
    # bucket_specs is shape-major by construction: np.unique returns
    # ascending packed (n<<32|d) keys, which orders like (n, d) tuples
    bucket_specs: list[tuple[int, int, np.ndarray]] = [
        (
            int(key >> 32),
            int(key & 0xFFFFFFFF),
            ent_list[inv_order[shape_bounds[bi] : shape_bounds[bi + 1]]],
        )
        for bi, key in enumerate(shape_keys)
    ]

    # per-entity slot assignment within its bucket (shard-major balanced
    # when an entity mesh axis exists; load = active rows, the per-sweep
    # training cost) + flat score-row starts per entity
    slot_of_entity = np.full(num_v, -1, dtype=np.int64)
    bucket_of_entity = np.full(num_v, -1, dtype=np.int64)
    flat_start_of_entity = np.zeros(num_v, dtype=np.int64)
    for bi, (n_max, d_max, ents) in enumerate(bucket_specs):
        ents = np.asarray(ents, dtype=np.int64)
        if entity_shards > 1 and len(ents) > 1:
            perm = _shard_major_entity_order(
                n_act[ents].astype(np.float64), entity_shards
            )
            ents = ents[perm]
        bucket_specs[bi] = (n_max, d_max, ents)
        slot_of_entity[ents] = np.arange(len(ents))
        bucket_of_entity[ents] = bi
        flat_start_of_entity[ents] = np.concatenate(
            ([0], np.cumsum(n_k[ents])[:-1])
        )

    # --- fill buckets via fancy indexing ------------------------------
    row_bucket = bucket_of_entity[kept_ent]
    row_slot = slot_of_entity[kept_ent]
    # flat score-row index of every kept row (slot-major within bucket)
    flat_row = flat_start_of_entity[kept_ent] + row_rank

    # Rows grouped by bucket ONCE (stable sort + range bounds): a per-
    # bucket boolean scan over every kept row is O(buckets × rows): at the
    # 10⁹-coefficient build's ~30 buckets that alone re-read 70M-row masks
    # thirty times and pushed the host build past its budget.
    order_rb = np.argsort(row_bucket, kind="stable")
    rb_bounds = np.searchsorted(
        row_bucket[order_rb], np.arange(len(bucket_specs) + 1)
    )
    if not fast_dense:
        # same one-pass grouping for the per-nonzero and (entity, column)
        # pair streams — the sparse/projection branches would otherwise
        # rescan every nonzero per bucket (O(buckets × nnz), the exact
        # pattern the row grouping above removes)
        nnz_bucket = row_bucket[nnz_rowpos]
        order_nz = np.argsort(nnz_bucket, kind="stable")
        nz_bounds = np.searchsorted(
            nnz_bucket[order_nz], np.arange(len(bucket_specs) + 1)
        )
        if rnd_proj is None:
            pair_bucket = bucket_of_entity[pair_ent]
            order_pair = np.argsort(pair_bucket, kind="stable")
            pair_bounds = np.searchsorted(
                pair_bucket[order_pair], np.arange(len(bucket_specs) + 1)
            )

    buckets = []
    for bi, (n_max, d_max, ents) in enumerate(bucket_specs):
        ents = np.asarray(ents, dtype=np.int64)
        E = len(ents)
        feats = np.zeros((E, n_max, d_max), dtype=np.float32)
        labels = np.zeros((E, n_max), dtype=np.float32)
        offsets = np.zeros((E, n_max), dtype=np.float32)
        weights = np.zeros((E, n_max), dtype=np.float32)
        active_mask = np.zeros((E, n_max), dtype=np.float32)
        col_index = np.full((E, d_max), -1, dtype=np.int32)
        sample_pos = np.full((E, n_max), n, dtype=np.int32)  # n ⇒ OOB pad

        rows_in_b = order_rb[rb_bounds[bi] : rb_bounds[bi + 1]]
        m_b = int(n_k[ents].sum())
        score_feats = np.zeros((m_b, d_max), dtype=np.float32)
        score_slot = np.zeros(m_b, dtype=np.int32)
        score_pos = np.zeros(m_b, dtype=np.int32)
        fr_b = flat_row[rows_in_b]
        score_slot[fr_b] = row_slot[rows_in_b]
        score_pos[fr_b] = kept_rows[rows_in_b]

        act_rows = rows_in_b[act[rows_in_b]]
        s, r = row_slot[act_rows], act_rank[act_rows]
        rows_act = kept_rows[act_rows]
        labels[s, r] = data.labels[rows_act]
        offsets[s, r] = data.offsets[rows_act]
        weights[s, r] = data.weights[rows_act]
        active_mask[s, r] = 1.0
        sample_pos[s, r] = rows_act

        if fast_dense:
            d_col = shard.num_cols
            score_feats[fr_b, :d_col] = x2d[kept_rows[rows_in_b]]
            col_index[:, :d_col] = np.arange(d_col, dtype=np.int32)
        elif rnd_proj is None:
            nz_sel = order_nz[nz_bounds[bi] : nz_bounds[bi + 1]]
            lc = local_of_pair[pair_inv[nz_sel]]
            ok = lc >= 0  # Pearson-dropped columns vanish
            score_feats[
                flat_row[nnz_rowpos[nz_sel][ok]], lc[ok]
            ] = nnz_val[nz_sel][ok]
            # per-entity global column map
            pb = order_pair[pair_bounds[bi] : pair_bounds[bi + 1]]
            ent_pairs = pb[local_of_pair[pb] >= 0]
            col_index[
                slot_of_entity[pair_ent[ent_pairs]],
                local_of_pair[ent_pairs],
            ] = pair_col[ent_pairs].astype(np.int32)
        else:
            nz_sel = order_nz[nz_bounds[bi] : nz_bounds[bi + 1]]
            k = rnd_proj.shape[1]
            dense = np.zeros((m_b, k), dtype=np.float64)
            np.add.at(
                dense,
                flat_row[nnz_rowpos[nz_sel]],
                nnz_val[nz_sel, None] * rnd_proj[nnz_col[nz_sel]],
            )
            score_feats[:, :k] = dense.astype(np.float32)

        # train blocks gather the active rows' flat features (one source
        # of truth for the compaction/projection algebra)
        feats[s, r, :] = score_feats[flat_row[act_rows]]
        # rows with sample weight 0 score exactly 0 (the old block path
        # masked them with `where(weights > 0)`)
        w_b = np.asarray(data.weights)[kept_rows[rows_in_b]]
        zero_rows = fr_b[w_b <= 0]
        if len(zero_rows):
            score_feats[zero_rows] = 0.0

        buckets.append(
            REBucket(
                features=feats,
                labels=labels,
                offsets=offsets,
                weights=weights,
                active_mask=active_mask,
                col_index=col_index,
                sample_pos=sample_pos,
                entity_ids=ents.astype(np.int32),
                score_feats=score_feats,
                score_slot=score_slot,
                score_pos=score_pos,
            )
        )

    return RandomEffectDataset(
        random_effect_type=config.random_effect_type,
        feature_shard=config.feature_shard,
        vocab=vocab,
        entity_index={k: i for i, k in enumerate(vocab)},
        buckets=buckets,
        num_samples=n,
        num_features=shard.num_cols,
        projection_matrix=rnd_proj,
    )


def _concat_ranges(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Vectorized ``concat([arange(s, s+l) for s, l in zip(starts, lengths)])``."""
    total = int(lengths.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    nz = lengths > 0
    starts_nz = starts[nz].astype(np.int64)
    lengths_nz = lengths[nz].astype(np.int64)
    ends_nz = np.cumsum(lengths_nz)
    out = np.ones(total, dtype=np.int64)
    out[0] = starts_nz[0]
    # at each range boundary, jump from the previous range's last value
    out[ends_nz[:-1]] = starts_nz[1:] - (starts_nz[:-1] + lengths_nz[:-1] - 1)
    return np.cumsum(out)


def labels_are_binary(labels: np.ndarray) -> bool:
    u = set(np.unique(labels))
    return u <= {0.0, 1.0} or u <= {-1.0, 1.0}


def positive_rate(labels: np.ndarray) -> float:
    return float((labels > POSITIVE_RESPONSE_THRESHOLD).mean())
