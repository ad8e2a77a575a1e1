"""Streaming GAME inference engine: fused device scoring + overlapped ingest.

The reference GameScoringDriver (photon-client
cli/game/scoring/GameScoringDriver.scala) at least streamed scoring
through Spark partitions; the seed-era path here was worse — a host-side
Python loop over coordinates summing numpy einsums over the fully
materialized dataset, one monolithic write at the end. This module
replaces both halves:

- **Fused device scoring** (:class:`GameScorer`): ONE jit-compiled XLA
  program per batch shape computes every coordinate's margin — the
  fixed-effect matvec over a padded-ELL feature block, the random-effect
  per-entity coefficient gather (entity→table-row indices resolved on
  host per chunk from the model's memoized vocab index, coefficients
  gathered on device — no per-call dict rebuild, no numpy einsum), the
  matrix-factorization factor dot — plus offsets, with the batch buffers
  donated (off-CPU; see ``util/target.donation_enabled``). Batches are
  padded to a SMALL FIXED SET of shapes — a constant row count and
  power-of-two ELL widths, the shape-budget philosophy of ``game/data``
  applied to inference — so steady-state scoring triggers zero retraces
  (compile_watch-pinned). The per-shape programs are AOT-precompilable
  through the same ``lower().compile()`` + executable-cache pattern as
  PR 3's ``descent.precompile_coordinates``.

- **Overlapped streaming pipeline** (:meth:`GameScorer.stream`): chunk
  decode (avro → GameData, on a producer thread) → feature/entity index
  mapping + padding → host→device transfer, double-buffered against
  device compute (dispatch is async; the read-back of batch *i* happens
  after batch *i+1* is enqueued) → score read-back → the caller's sink
  (typically :class:`photon_tpu.io.model_io.ShardedScoringWriter`).
  Host staging is bounded: at most ``MAX_STAGED_CHUNKS`` decoded chunks
  sit on the producer side (one in the hand-off queue + one the producer
  is holding) and the consumer keeps up to two more in flight (the chunk
  being assembled/dispatched plus the double-buffered pending one whose
  read-back is deferred) — four decoded chunks total, a constant
  independent of dataset size. Size host memory for
  ``4 × batch_rows`` rows of features, not 2×.

Every stage runs under ``obs`` spans (``score.decode`` / ``score.ingest``
/ ``score.h2d`` / ``score.readback`` / ``score.write`` inside a
``score.stream`` root) with ``score.batches`` / ``score.samples`` /
``score.padded_rows`` counters and a ``score.batch_seconds`` histogram.

**Latency lifecycle (the SLO plane's input).** Each batch additionally
carries a monotonic BIRTH timestamp — the load source's scheduled
arrival stamp (``chunk.slo_arrival_t``, ``time.perf_counter`` timebase;
``scripts/load_harness.py`` sets it so queueing delay counts against
the budget — no coordinated omission) or, absent one, the moment its
chunk decode began. Per-batch stage walls (``queue`` hand-off wait,
``decode``, ``assemble``, ``h2d``, ``dispatch``, ``pipeline`` —
the double-buffer read-back hold — ``readback``, ``write``) feed
``score.stage_seconds.<stage>`` histograms, end-to-end
birth→done walls feed ``score.e2e_seconds``, and each finished batch
reports to :mod:`photon_tpu.obs.slo` — a batch that blows the armed
deadline increments a violation counter tagged with its DOMINANT stage,
so a p99 regression names decode-vs-H2D-vs-write instead of a bare
number.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu import obs
from photon_tpu.obs import causal, slo
from photon_tpu.obs.scopes import scope
from photon_tpu.game.data import (
    GameData,
    _ceil_pow2,
    entity_row_indices,
    pad_game_data,
    slice_game_data,
)
from photon_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    MatrixFactorizationModel,
    RandomEffectModel,
)
from photon_tpu.util import compile_watch, faults
from photon_tpu.util.retry import RetryPolicy, is_transient, retry_call
from photon_tpu.util.sanitize import sanctioned_transfers, transfer_sanitizer
from photon_tpu.util.target import donation_enabled

logger = logging.getLogger(__name__)

#: default rows per scoring batch (`--score-batch-rows`; env override
#: PHOTON_SCORE_BATCH_ROWS wins, the same env-over-config precedence as
#: the training-side shape budget)
DEFAULT_BATCH_ROWS = 8192

#: widest feature shard the random-effect gather will densify per batch
#: ([rows, d+1] f32 block); wider no-projection RE shards fall back to
#: the monolithic host path (PHOTON_SCORE_DENSE_COLS override)
DEFAULT_DENSE_COLS_MAX = 4096

#: hard bound on fully-decoded chunks staged on the PRODUCER side at
#: once: one in the producer→consumer queue plus the one the producer
#: just finished (blocked on the put). The consumer holds up to two more
#: (current + double-buffered pending), so total live residency is
#: bounded at MAX_STAGED_CHUNKS + 2 — still a constant.
MAX_STAGED_CHUNKS = 2

#: default producer-watchdog timeout (seconds): how long the consumer
#: waits for the NEXT decoded chunk before declaring the producer hung
#: (``PHOTON_STREAM_WATCHDOG_S`` override; 0 disables). Generous by
#: design — it must only fire on a genuinely stuck producer, never on a
#: slow disk
DEFAULT_WATCHDOG_S = 300.0

#: per-batch transient retry (the "requeue": the decoded chunk is still
#: on host, so a retry re-stages and re-dispatches the same batch)
BATCH_RETRY_POLICY = RetryPolicy(attempts=3, base_s=0.5, cap_s=15.0)


def stream_watchdog_s(config_value: float | None = None) -> float:
    """Producer-watchdog seconds: ``PHOTON_STREAM_WATCHDOG_S`` env >
    explicit value > :data:`DEFAULT_WATCHDOG_S`; 0 disables."""
    env = os.environ.get("PHOTON_STREAM_WATCHDOG_S", "").strip()
    if env:
        v = float(env)  # phl-ok: PHL002 parses an env-var string, not device data
    elif config_value is not None:
        # phl-ok: PHL002 parses a config knob (host int/float), not device data
        v = float(config_value)
    else:
        return DEFAULT_WATCHDOG_S
    if v < 0:
        raise ValueError(f"stream watchdog must be >= 0, got {v}")
    return v


class StreamError(RuntimeError):
    """A streaming-pipeline failure the monolithic path does not share —
    the class the scoring driver's opt-in degrade escape catches."""


class ProducerDiedError(StreamError):
    """The decode producer thread died WITHOUT handing the consumer a
    sentinel or a failure — abrupt thread death (the chaos
    ``scoring.producer`` fault). The watchdog converts what would be an
    eternal ``q.get()`` into this clean error."""


class StreamStallError(StreamError):
    """The producer is alive but produced nothing for the whole watchdog
    window — a hung decode / slow-host stall. Raised instead of
    silently wedging the scoring run."""


def score_batch_rows(config_value: int | None = None) -> int:
    """Rows per scoring batch: ``PHOTON_SCORE_BATCH_ROWS`` env >
    CLI/config value > :data:`DEFAULT_BATCH_ROWS`."""
    env = os.environ.get("PHOTON_SCORE_BATCH_ROWS", "").strip()
    if env:
        v = int(env)
    elif config_value is not None:
        v = int(config_value)
    else:
        return DEFAULT_BATCH_ROWS
    if v < 1:
        raise ValueError(f"score batch rows must be >= 1, got {v}")
    return v


def score_output_partitions(config_value: int | None = None) -> int:
    """Output score shards: ``PHOTON_SCORE_PARTITIONS`` env > CLI/config
    value > 1."""
    env = os.environ.get("PHOTON_SCORE_PARTITIONS", "").strip()
    if env:
        v = int(env)
    elif config_value is not None:
        v = int(config_value)
    else:
        return 1
    if v < 1:
        raise ValueError(f"score output partitions must be >= 1, got {v}")
    return v


class UnsupportedModelLayout(ValueError):
    """The fused score program cannot express this model layout (e.g. a
    no-projection random effect on a feature shard wider than the dense
    gather limit). Drivers catch exactly this to fall back to the
    monolithic host path — a plain ``ValueError`` (bad batch-rows /
    partition / env knob values) must NOT silently demote the run."""


# ---------------------------------------------------------------------------
# static coordinate specs (decided once per model at engine build)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _FixedSpec:
    cid: str
    shard: str


@dataclasses.dataclass(frozen=True)
class _RandomSpec:
    cid: str
    shard: str
    tag: str
    projected: bool
    num_entities: int


@dataclasses.dataclass(frozen=True)
class _MFSpec:
    cid: str
    row_tag: str
    col_tag: str
    num_rows: int
    num_cols: int


@dataclasses.dataclass
class StreamStats:
    """Counters and walls the streaming pipeline records per run."""

    batches: int = 0
    samples: int = 0
    padded_rows: int = 0
    max_staged_chunks: int = 0
    #: transient per-batch retries spent (H2D + dispatch re-runs; the
    #: decoded chunk stays on host, so a retry is a requeue, not a loss)
    batch_retries: int = 0
    #: per-batch dispatch→read-back walls (batch 0 pays the compiles)
    batch_walls_s: list = dataclasses.field(default_factory=list)
    #: per-batch END-TO-END walls: birth (scheduled arrival when the
    #: load source stamps ``slo_arrival_t``, else decode start) → batch
    #: fully finished (scores written) — queueing included
    e2e_walls_s: list = dataclasses.field(default_factory=list)
    #: per-stage walls, one list per lifecycle stage (queue / decode /
    #: assemble / h2d / dispatch / readback / write)
    stage_walls_s: dict = dataclasses.field(default_factory=dict)
    #: batches that blew the armed SLO deadline (0 when no SLO armed),
    #: and the census by dominant stage
    deadline_violations: int = 0
    violations_by_stage: dict = dataclasses.field(default_factory=dict)
    #: compile_watch delta over the whole stream / over batch 0 only
    compiles: dict = dataclasses.field(default_factory=dict)
    compiles_first_batch: dict = dataclasses.field(default_factory=dict)
    wall_s: float = 0.0
    #: requests shed instead of answered (serving engine: queue-full /
    #: deadline / oversize rejections). Shed requests have NO e2e wall —
    #: the percentiles below cover answered work only, and expose this
    #: count alongside so a partial run cannot masquerade as a full one
    shed: int = 0

    def latency_percentiles(self, warm_only: bool = True) -> dict:
        """p50/p95/p99 batch latency (warm = batch 0 excluded)."""
        walls = self.batch_walls_s[1:] if warm_only else self.batch_walls_s
        if not walls:
            return {}
        arr = np.asarray(walls)
        return {
            f"p{p}": round(float(np.percentile(arr, p)), 6)
            for p in (50, 95, 99)
        }

    def e2e_percentiles(self, warm_only: bool = False) -> dict:
        """Exact (numpy, not bucketed) p50/p90/p99/p99.9 of end-to-end
        batch latency — queueing delay included. All batches by default:
        an open-loop load report must not exclude the cold batch its
        arrivals already charged.

        Percentiles cover ANSWERED work only — shed requests never get
        an e2e wall — so ``count`` (answered) and ``shed`` ride along:
        a report from a partial run (load shedding, a mid-stream stall)
        must say how much work its percentiles describe."""
        walls = self.e2e_walls_s[1:] if warm_only else self.e2e_walls_s
        if not walls:
            return {"count": 0, "shed": self.shed} if self.shed else {}
        arr = np.asarray(walls)
        out = {
            # phl-ok: PHL002 post-run numpy percentile of host walls, no device value involved
            f"p{p:g}": round(float(np.percentile(arr, p)), 6)
            for p in (50, 90, 99, 99.9)
        }
        # phl-ok: PHL002 post-run numpy moment of host walls, no device value involved
        out["mean"] = round(float(arr.mean()), 6)
        # phl-ok: PHL002 post-run numpy moment of host walls, no device value involved
        out["max"] = round(float(arr.max()), 6)
        out["count"] = len(walls)
        out["shed"] = self.shed
        return out

    def stage_percentiles(self) -> dict:
        """Exact per-stage p50/p90/p99 — the latency waterfall
        ``scoring-summary.json`` carries."""
        out = {}
        for stage, walls in self.stage_walls_s.items():
            if not walls:
                continue
            arr = np.asarray(walls)
            out[stage] = {
                f"p{p}": round(float(np.percentile(arr, p)), 6)
                for p in (50, 90, 99)
            }
        return out


@dataclasses.dataclass
class StreamResult:
    """What :meth:`GameScorer.stream` returns."""

    scores: np.ndarray | None
    stats: StreamStats


class _Failure:
    def __init__(self, exc: BaseException):
        self.exc = exc


@dataclasses.dataclass
class _ChunkItem:
    """One decoded chunk plus its latency-lifecycle stamps (all
    ``time.perf_counter`` timebase): ``birth_t`` is the load source's
    scheduled-arrival stamp when present (``chunk.slo_arrival_t`` —
    open-loop harnesses set it so queueing counts against the deadline)
    or the moment decode began; ``decoded_t`` anchors the consumer's
    hand-off ``queue`` wait."""

    chunk: GameData
    birth_t: float
    decode_s: float
    decoded_t: float
    #: the chunk's causal trace (obs/causal.py TraceCtx; the shared null
    #: context when tracing is disarmed, None for hand-built items)
    trace: object = None


class _StageCounter:
    """Per-stream staged-chunk accounting. Stream-local (not scorer
    state) so an orphaned producer from a failed stream — one that
    outlives the 5 s reap join mid-decode — can only touch its own dead
    stream's counter, never a later stream's ``max_staged_chunks``."""

    def __init__(self):
        self.lock = threading.Lock()
        self.value = 0


_DONE = object()


class GameScorer:
    """Fused, shape-bucketed, streamable device scorer for a GameModel.

    Raises :class:`UnsupportedModelLayout` at construction for model
    layouts the fused program cannot express (a no-projection random
    effect on a feature shard wider than ``dense_cols_max``) — callers
    fall back to the monolithic host path.

    Scores match ``GameTransformer.score`` (margins + offsets) to f32
    accumulation tolerance; padding rows are dropped before any result
    leaves the engine.
    """

    def __init__(
        self,
        model: GameModel,
        *,
        batch_rows: int | None = None,
        dense_cols_max: int | None = None,
        donate: bool | None = None,
        watchdog_s: float | None = None,
    ):
        self.model = model
        self.batch_rows = score_batch_rows(batch_rows)
        self.watchdog_s = stream_watchdog_s(watchdog_s)
        env_cols = os.environ.get("PHOTON_SCORE_DENSE_COLS", "").strip()
        self.dense_cols_max = (
            int(env_cols)
            if env_cols
            else (dense_cols_max or DEFAULT_DENSE_COLS_MAX)
        )
        self._donate = (
            bool(donate) if donate is not None else donation_enabled()
        )

        self._fixed: list[_FixedSpec] = []
        self._random: list[_RandomSpec] = []
        self._mf: list[_MFSpec] = []
        #: shard → expected width, per device representation
        self._ell_shards: dict[str, int] = {}
        self._dense_shards: dict[str, int] = {}
        params: dict = {"fe": {}, "re": {}, "mf": {}}

        for cid, cm in model.coordinates.items():
            if isinstance(cm, FixedEffectModel):
                w = np.asarray(cm.model.coefficients.means, dtype=np.float32)
                self._fixed.append(_FixedSpec(cid=cid, shard=cm.feature_shard))
                self._ell_shards.setdefault(cm.feature_shard, len(w))
                params["fe"][cid] = jnp.asarray(w)
            elif isinstance(cm, RandomEffectModel):
                params["re"][cid] = self._pack_random_effect(cid, cm)
            elif isinstance(cm, MatrixFactorizationModel):
                u = np.concatenate(
                    [cm.row_factors, np.zeros((1, cm.num_factors))]
                ).astype(np.float32)
                v = np.concatenate(
                    [cm.col_factors, np.zeros((1, cm.num_factors))]
                ).astype(np.float32)
                self._mf.append(
                    _MFSpec(
                        cid=cid,
                        row_tag=cm.row_entity_type,
                        col_tag=cm.col_entity_type,
                        num_rows=len(cm.row_vocab),
                        num_cols=len(cm.col_vocab),
                    )
                )
                params["mf"][cid] = {"u": jnp.asarray(u), "v": jnp.asarray(v)}
            else:
                raise ValueError(f"unknown coordinate model for {cid!r}")

        self._params = params
        self._jit = (
            jax.jit(self._score_fn, donate_argnums=(1,))
            if self._donate
            else jax.jit(self._score_fn)
        )
        #: shape-key → AOT Compiled executable (descent.precompile pattern:
        #: ``lower().compile()`` does not feed the jit call cache, so the
        #: dispatch path consults this cache first)
        self._aot: dict = {}

    def aot_executables(self) -> dict:
        """The per-batch-shape AOT executables, keyed by ELL-width shape
        signature — the same accessor contract as
        ``Coordinate.aot_executables``, so the SPMD program auditor
        (``analysis.hlo.audit_scorer``) covers the streaming scorer's
        fused programs exactly like the fit's."""
        return self._aot

    # -- model packing ------------------------------------------------------

    def _pack_random_effect(self, cid: str, cm: RandomEffectModel) -> dict:
        """Device tables for one RE coordinate: per-entity coefficients in
        their local (compacted or projected) space, plus the column map
        back to the shard's global feature space. Row E (the appended
        zero row) scores unmodeled/unseen entities as exactly 0."""
        e_n = len(cm.vocab)
        if cm.projection_matrix is not None:
            k = cm.projection_matrix.shape[1]
            coef = np.zeros((e_n + 1, k), dtype=np.float32)
            for b in cm.buckets:
                w = np.asarray(b.coefficients, dtype=np.float32)
                coef[np.asarray(b.entity_ids)] = w[:, :k]
            self._random.append(
                _RandomSpec(
                    cid=cid,
                    shard=cm.feature_shard,
                    tag=cm.random_effect_type,
                    projected=True,
                    num_entities=e_n,
                )
            )
            self._ell_shards.setdefault(cm.feature_shard, cm.num_features)
            return {
                "coef": jnp.asarray(coef),
                "proj": jnp.asarray(
                    np.asarray(cm.projection_matrix, dtype=np.float32)
                ),
            }
        d_shard = cm.num_features
        if d_shard > self.dense_cols_max:
            raise UnsupportedModelLayout(
                f"random-effect coordinate {cid!r} scores on shard "
                f"{cm.feature_shard!r} with {d_shard} columns — wider than "
                f"the fused scorer's dense gather limit "
                f"({self.dense_cols_max}; PHOTON_SCORE_DENSE_COLS). Use "
                "the monolithic scoring path for this model."
            )
        d_pack = max(
            (int(np.asarray(b.col_index).shape[1]) for b in cm.buckets),
            default=1,
        )
        coef = np.zeros((e_n + 1, d_pack), dtype=np.float32)
        # invalid column slots point at the dense block's appended zero
        # column (index d_shard), so padded coefficients multiply zero
        col = np.full((e_n + 1, d_pack), d_shard, dtype=np.int32)
        for b in cm.buckets:
            ids = np.asarray(b.entity_ids)
            ci = np.asarray(b.col_index)
            w = np.asarray(b.coefficients, dtype=np.float32)
            d_b = ci.shape[1]
            coef[ids, :d_b] = w
            col[ids, :d_b] = np.where(ci >= 0, ci, d_shard).astype(np.int32)
        self._random.append(
            _RandomSpec(
                cid=cid,
                shard=cm.feature_shard,
                tag=cm.random_effect_type,
                projected=False,
                num_entities=e_n,
            )
        )
        self._dense_shards.setdefault(cm.feature_shard, d_shard)
        return {"coef": jnp.asarray(coef), "col": jnp.asarray(col)}

    # -- the fused program --------------------------------------------------

    def _score_fn(self, params, batch):
        """Total margin + offsets for one padded batch — every coordinate
        in ONE program, so a steady-state batch is a single dispatch."""
        with scope("photon.score.batch"):
            return self._score_terms(params, batch)

    def _score_terms(self, params, batch):
        total = batch["offsets"]
        for s in self._fixed:
            idx, val = batch["ell"][s.shard]
            w = params["fe"][s.cid]
            total = total + jnp.sum(val * jnp.take(w, idx, axis=0), axis=1)
        for s in self._random:
            tab = params["re"][s.cid]
            e = batch["eidx"][s.cid]
            coef = jnp.take(tab["coef"], e, axis=0)  # [B, d]
            if s.projected:
                idx, val = batch["ell"][s.shard]
                # x_eff = x @ P without densifying x: gather P rows per
                # nonzero slot (padding slots are value 0 → vanish)
                p_rows = jnp.take(tab["proj"], idx, axis=0)  # [B, K, k]
                x_eff = jnp.einsum("bs,bsk->bk", val, p_rows)
                total = total + jnp.sum(coef * x_eff, axis=1)
            else:
                x = batch["dense"][s.shard]  # [B, d_shard + 1]
                cols = jnp.take(tab["col"], e, axis=0)  # [B, d]
                xg = jnp.take_along_axis(x, cols, axis=1)
                total = total + jnp.sum(coef * xg, axis=1)
        for s in self._mf:
            tabs = params["mf"][s.cid]
            u = jnp.take(tabs["u"], batch["mf"][s.cid][0], axis=0)
            v = jnp.take(tabs["v"], batch["mf"][s.cid][1], axis=0)
            total = total + jnp.sum(u * v, axis=1)
        return total

    # -- host batch assembly ------------------------------------------------

    def _host_batch(self, chunk: GameData) -> dict:
        """Pad one chunk to the fixed batch row count and assemble the
        numpy batch pytree (ELL blocks at power-of-two widths, dense
        blocks with an appended zero column, entity table rows)."""
        n = chunk.num_samples
        if n > self.batch_rows:
            raise ValueError(
                f"chunk has {n} rows > batch_rows={self.batch_rows}"
            )
        padded = pad_game_data(chunk, self.batch_rows)
        batch: dict = {
            "offsets": padded.offsets.astype(np.float32),
            "ell": {},
            "dense": {},
            "eidx": {},
            "mf": {},
        }
        for shard, width in self._ell_shards.items():
            m = padded.feature_shards[shard]
            if m.num_cols != width:
                raise ValueError(
                    f"shard {shard!r} has {m.num_cols} columns; the model "
                    f"was indexed for {width}"
                )
            k_raw = int(np.max(np.diff(m.indptr))) if m.num_rows else 1
            idx, val = m.to_ell(
                nnz_pad_multiple=_ceil_pow2(max(k_raw, 1))
            )
            batch["ell"][shard] = (idx, val)
        for shard, width in self._dense_shards.items():
            m = padded.feature_shards[shard]
            if m.num_cols != width:
                raise ValueError(
                    f"shard {shard!r} has {m.num_cols} columns; the model "
                    f"was indexed for {width}"
                )
            x = np.zeros((self.batch_rows, width + 1), dtype=np.float32)
            rows = np.repeat(np.arange(m.num_rows), np.diff(m.indptr))
            x[rows, m.indices] = m.values
            batch["dense"][shard] = x
        for s in self._random:
            cm = self.model.coordinates[s.cid]
            batch["eidx"][s.cid] = entity_row_indices(
                cm.entity_row_index,
                padded.id_tags[s.tag],
                s.num_entities,
            ).astype(np.int32)
        for s in self._mf:
            cm = self.model.coordinates[s.cid]
            ri = entity_row_indices(
                cm.row_index, padded.id_tags[s.row_tag], s.num_rows
            ).astype(np.int32)
            ci = entity_row_indices(
                cm.col_index, padded.id_tags[s.col_tag], s.num_cols
            ).astype(np.int32)
            batch["mf"][s.cid] = (ri, ci)
        return batch

    def _shape_key(self, batch) -> tuple:
        """Batch-shape signature: row count is fixed, so only the ELL
        widths vary — the small set the zero-retrace policy bounds."""
        return tuple(
            sorted((s, b[0].shape[1]) for s, b in batch["ell"].items())
        )

    # -- dispatch (AOT cache first, jit fallback) ---------------------------

    def _dispatch(self, batch_dev, key):
        exe = self._aot.get(key)
        if exe is not None:
            try:
                return exe(self._params, batch_dev)
            except (TypeError, ValueError) as e:
                # only call-time argument rejection (raised BEFORE
                # execution, donated buffers survive) falls back —
                # mirror of Coordinate._aot_call
                self._aot.pop(key, None)
                logger.warning(
                    "precompiled score program rejected its inputs "
                    "(%s: %s); falling back to the jit path",
                    type(e).__name__, e,
                )
        return self._jit(self._params, batch_dev)

    def precompile(
        self, ell_widths: Mapping[str, int] | None = None
    ) -> dict:
        """AOT-compile the fused score program for one batch shape (PR 3's
        ``lower().compile()`` + executable-cache machinery): ``ell_widths``
        maps each ELL-represented shard to the nnz width to pad for
        (snapped up to its power-of-two level); dense shards and the row
        count are fixed by construction. Returns a compile report
        (``wall_s``, compile_watch delta, cache key)."""
        compile_watch.install()
        widths = {
            shard: _ceil_pow2(int((ell_widths or {}).get(shard, 1)))
            for shard in self._ell_shards
        }
        b = self.batch_rows
        sds: dict = {
            "offsets": jax.ShapeDtypeStruct((b,), jnp.float32),
            "ell": {
                shard: (
                    jax.ShapeDtypeStruct((b, k), jnp.int32),
                    jax.ShapeDtypeStruct((b, k), jnp.float32),
                )
                for shard, k in widths.items()
            },
            "dense": {
                shard: jax.ShapeDtypeStruct((b, d + 1), jnp.float32)
                for shard, d in self._dense_shards.items()
            },
            "eidx": {
                s.cid: jax.ShapeDtypeStruct((b,), jnp.int32)
                for s in self._random
            },
            "mf": {
                s.cid: (
                    jax.ShapeDtypeStruct((b,), jnp.int32),
                    jax.ShapeDtypeStruct((b,), jnp.int32),
                )
                for s in self._mf
            },
        }
        key = tuple(sorted(widths.items()))
        t0 = time.perf_counter()
        with compile_watch.watch() as cw, obs.span(
            "precompile.program", cat="compile", program="score"
        ):
            self._aot[key] = self._jit.lower(self._params, sds).compile()
        # static footprint per batch shape into the memory ledger (what
        # each scoring shape NEEDS on device, from XLA's own accounting)
        obs.memory.record_executable(f"score:{key}", self._aot[key])
        return {
            "program": "score",
            "key": key,
            "wall_s": round(time.perf_counter() - t0, 4),
            "backend_compile_s": cw["backend_compile_s"],
            "cache_hits": cw["cache_hits"],
            "cache_misses": cw["cache_misses"],
        }

    # -- streaming pipeline -------------------------------------------------

    def _produce(
        self,
        chunk_iter: Iterator,
        q: queue.Queue,
        stats,
        staged: _StageCounter,
        stop: threading.Event,
    ):
        """Producer thread: pull (decode) chunks and hand them off through
        the bounded queue. The staged counter covers chunks that are fully
        decoded but not yet picked up by the consumer. ``stop`` is the
        consumer's abort signal — every put is bounded by it so a failed
        consumer never leaves this thread blocked on a full queue holding
        decoded chunks."""
        # chaos hook OUTSIDE the failure-reporting try below: an
        # injected ``error`` here kills this thread with NO sentinel and
        # NO _Failure — abrupt thread death, exactly what the consumer's
        # watchdog must convert into ProducerDiedError; ``stall`` here
        # models the hung producer the stall watchdog covers
        faults.fault_point("scoring.producer")

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        ctx = causal.null()
        try:
            while not stop.is_set():
                t_pull = time.perf_counter()
                # one causal trace per chunk, minted before decode so an
                # injected decode fault lands inside this chunk's chain
                ctx = causal.mint("score.chunk", kind="score")
                with ctx.active(), obs.span("score.decode"):
                    # chaos hook inside the try: a decode fault reports
                    # through the normal _Failure hand-off (the source's
                    # own per-file retries have already been spent by
                    # the time an error reaches here)
                    faults.fault_point("scoring.chunk")
                    chunk = next(chunk_iter, _DONE)
                t_decoded = time.perf_counter()
                if chunk is _DONE:
                    put(_DONE)
                    return
                # birth: the load source's scheduled-arrival stamp wins
                # (open-loop Poisson harness — queueing delay counts),
                # else the batch is born when its decode began. The
                # decode stage clips to POST-birth wall: a paced source
                # sleeping until the scheduled arrival inside next() is
                # idle time before the request exists, not decode work —
                # charging it would misname the dominant stage
                arrival = getattr(chunk, "slo_arrival_t", None)
                # phl-ok: PHL002 parses a host monotonic stamp the load source attached, not device data
                birth = t_pull if arrival is None else float(arrival)
                item = _ChunkItem(
                    chunk=chunk,
                    birth_t=birth,
                    decode_s=max(0.0, t_decoded - max(t_pull, birth)),
                    decoded_t=t_decoded,
                    trace=ctx,
                )
                # decode slice + the flow START the consumer's assemble
                # arrow binds to (flow ts inside the slice)
                ctx.event(
                    "score.decode", t_decoded - item.decode_s,
                    item.decode_s, cat="score", rows=chunk.num_samples,
                )
                ctx.flow("s", t_decoded - item.decode_s)
                with staged.lock:
                    staged.value += 1
                    stats.max_staged_chunks = max(
                        stats.max_staged_chunks, staged.value
                    )
                if not put(item):
                    return
        except BaseException as e:  # propagate into the consumer loop
            ctx.finish("error")
            put(_Failure(e))

    def _next_item(self, q: queue.Queue, producer: threading.Thread):
        """Watchdog-guarded hand-off read. A healthy producer satisfies
        the short poll almost always; the slow paths convert the two
        silent-wedge modes into clean typed errors:

        * producer thread DEAD with an empty queue (it never put its
          sentinel — abrupt death) → :class:`ProducerDiedError`;
        * producer alive but silent for the whole watchdog window (hung
          decode, stalled host) → :class:`StreamStallError`.
        """
        waited = 0.0
        poll = 0.5 if self.watchdog_s == 0 else min(0.5, self.watchdog_s)
        while True:
            try:
                return q.get(timeout=poll)
            except queue.Empty:
                pass
            if not producer.is_alive():
                try:  # it may have put + exited between timeout and check
                    return q.get_nowait()
                except queue.Empty:
                    obs.counter("score.producer_deaths")
                    raise ProducerDiedError(
                        "score-decode producer thread died without "
                        "reporting a result or an error; the stream "
                        "cannot make progress"
                    ) from None
            waited += poll
            if self.watchdog_s and waited >= self.watchdog_s:
                obs.counter("score.stream_stalls")
                raise StreamStallError(
                    f"score-decode producer produced nothing for "
                    f"{waited:.0f}s (watchdog "
                    f"PHOTON_STREAM_WATCHDOG_S={self.watchdog_s:g}); "
                    "treating the stream as hung"
                )

    def stream(
        self,
        chunks: Iterable[GameData],
        *,
        on_batch: Callable[[GameData, np.ndarray], None] | None = None,
        collect_scores: bool = True,
    ) -> StreamResult:
        """Run the overlapped pipeline over ``chunks``.

        ``on_batch(chunk, scores)`` is called in input order as each
        batch's scores arrive (padding rows already dropped, float64) —
        the sharded avro writers hang here. ``collect_scores=True`` also
        concatenates all scores (cheap: 8 bytes/row; it is the feature
        blocks that streaming keeps off the host)."""
        stats = StreamStats()
        # arm the latency SLO from PHOTON_SLO_SPEC (no-op when unset or
        # when a tracker was installed programmatically) — driver runs
        # get deadline tracking with no code change; same deal for the
        # causal trace plane via PHOTON_TRACE
        slo.ensure_from_env()
        causal.ensure_from_env()
        collected: list[np.ndarray] = [] if collect_scores else None
        q: queue.Queue = queue.Queue(maxsize=MAX_STAGED_CHUNKS - 1)
        stop = threading.Event()
        staged = _StageCounter()
        t_start = time.perf_counter()
        cw_start = compile_watch.snapshot()
        producer = threading.Thread(
            target=self._produce,
            args=(iter(chunks), q, stats, staged, stop),
            name="score-decode",
            daemon=True,
        )

        def finish(pending) -> None:
            dev_scores, item, t_dispatch, stages, t_enqueued = pending
            chunk = item.chunk
            tr = item.trace if item.trace is not None else causal.null()
            t_r0 = time.perf_counter()
            # the double-buffer hold: batch i's read-back is deferred
            # until batch i+1 enqueues — real latency from this batch's
            # perspective, attributed explicitly so it can't masquerade
            # as (or hide behind) another stage
            stages["pipeline"] = t_r0 - t_enqueued
            with obs.span("score.readback", rows=chunk.num_samples):
                obs.memory.count_d2h(int(dev_scores.nbytes))
                with sanctioned_transfers(
                    "score read-back — the one sanctioned D2H of the "
                    "double-buffered pipeline"
                ):
                    scores = np.asarray(dev_scores)[
                        : chunk.num_samples
                    ].astype(np.float64)
            stages["readback"] = time.perf_counter() - t_r0
            # pipeline (the double-buffer hold) CONTAINS the next
            # batch's assemble/h2d/dispatch slices on this track —
            # Perfetto nests them, which IS the overlap, visible
            tr.event(
                "score.pipeline", t_enqueued, stages["pipeline"],
                cat="score",
            )
            tr.event(
                "score.readback", t_r0, stages["readback"],
                cat="score", rows=chunk.num_samples,
            )
            # flow FINISH inside the read-back slice: the arrow closing
            # this chunk's causal chain
            tr.flow("f", t_r0)
            wall = time.perf_counter() - t_dispatch
            if not stats.batch_walls_s:
                stats.compiles_first_batch = compile_watch.delta(cw_start)
            stats.batch_walls_s.append(wall)
            stats.batches += 1
            stats.samples += chunk.num_samples
            obs.counter("score.batches")
            obs.counter("score.samples", chunk.num_samples)
            obs.histogram("score.batch_seconds", wall)
            if collected is not None:
                collected.append(scores)
            if on_batch is not None:
                t_w0 = time.perf_counter()
                with obs.span("score.write", rows=chunk.num_samples):
                    on_batch(chunk, scores)
                stages["write"] = time.perf_counter() - t_w0
                tr.event(
                    "score.write", t_w0, stages["write"], cat="score"
                )
            # the batch's latency lifecycle closes HERE: end-to-end wall
            # from birth (scheduled arrival / decode start) through the
            # sink write, per-stage walls into their histograms, and the
            # SLO verdict — a blown deadline is tagged with the stage
            # that ate the budget
            e2e = time.perf_counter() - item.birth_t
            stats.e2e_walls_s.append(e2e)
            for stage, sec in stages.items():
                stats.stage_walls_s.setdefault(stage, []).append(sec)
                obs.histogram(f"score.stage_seconds.{stage}", sec)
            obs.histogram("score.e2e_seconds", e2e)
            dominant = slo.observe_batch(e2e, stages)
            tr.finish(
                "ok" if dominant is None else "deadline", e2e_s=e2e
            )
            if dominant is not None:
                stats.deadline_violations += 1
                stats.violations_by_stage[dominant] = (
                    stats.violations_by_stage.get(dominant, 0) + 1
                )
            # flight-recorder tap at the read-back choke point: host
            # values the batch's sanctioned D2H already produced
            obs.flight.record(
                "score_batch",
                batch=stats.batches,
                rows=chunk.num_samples,
                wall_s=round(wall, 6),
                e2e_s=round(e2e, 6),
                violation_stage=dominant,
            )

        # the transfer sanitizer (PHOTON_SANITIZE=transfers, a no-op
        # otherwise): any IMPLICIT host transfer in the consumer loop —
        # a numpy leaf sneaking into a dispatch, a stray float() — fails
        # loudly; the H2D staging and the score read-back are the two
        # sanctioned, annotated crossings
        with obs.span("score.stream") as root, transfer_sanitizer(
            "score.stream"
        ):
            # phase-boundary censuses: what is live on device at stream
            # start/end (model tables should be the whole bill; batches
            # must NOT accumulate) — host metadata only, never a sync
            obs.memory.census("stream_start")
            producer.start()
            pending = None
            failure: BaseException | None = None
            try:
                while True:
                    item = self._next_item(q, producer)
                    if isinstance(item, _Failure):
                        failure = item.exc
                        break
                    if item is _DONE:
                        break
                    with staged.lock:
                        staged.value -= 1
                    chunk = item.chunk
                    t_pickup = time.perf_counter()
                    # stage walls for this batch's lifecycle: decode
                    # measured by the producer, queue = hand-off wait
                    # (double-buffer backpressure included)
                    stages = {
                        "decode": item.decode_s,
                        "queue": t_pickup - item.decoded_t,
                    }
                    if stats.batches == 0 and not stats.batch_walls_s:
                        # ingest provenance on the stream root: "cache"
                        # chunks came from the mmap replay (zero decode)
                        prov = getattr(chunk, "provenance", None)
                        if prov:
                            root.set(ingest=prov.get("source"))
                    with obs.span("score.ingest", rows=chunk.num_samples):
                        host_batch = self._host_batch(chunk)
                        key = self._shape_key(host_batch)
                        stats.padded_rows += (
                            self.batch_rows - chunk.num_samples
                        )
                        obs.counter(
                            "score.padded_rows",
                            self.batch_rows - chunk.num_samples,
                        )
                    stages["assemble"] = time.perf_counter() - t_pickup
                    tr = (
                        item.trace
                        if item.trace is not None
                        else causal.null()
                    )
                    # assemble slice on the consumer track; the queue
                    # wait rides as an arg (a queue slice would partially
                    # overlap the previous batch's consumer slices) and
                    # the flow arrow from the decode slice shows the
                    # hand-off gap visually
                    tr.event(
                        "score.assemble", t_pickup, stages["assemble"],
                        cat="score", rows=chunk.num_samples,
                        queue_s=round(stages["queue"], 6),
                    )
                    tr.flow("t", t_pickup)

                    # per-batch retry-with-requeue: the decoded chunk is
                    # still on host, so a transient H2D/dispatch failure
                    # re-stages and re-dispatches THIS batch instead of
                    # killing the stream (util/retry.py classifier:
                    # non-transient errors propagate on attempt 1)
                    tries = 0
                    h2d_acc = [0.0]

                    def run_batch(
                        host_batch=host_batch, key=key, h2d_acc=h2d_acc
                    ):
                        nonlocal tries
                        tries += 1
                        # chaos hook: a transient fault here exercises
                        # the requeue path end to end
                        faults.fault_point("scoring.batch")
                        t_h0 = time.perf_counter()
                        with obs.span("score.h2d"), sanctioned_transfers(
                            "scoring H2D staging — the batch pytree is "
                            "placed whole, explicitly, once per batch"
                        ):
                            # phl-ok: PHL007 single-host scoring engine: the batch is placed on the default device; a mesh-sharded scorer must pass shardings here
                            batch_dev = jax.device_put(host_batch)
                            # ingest choke point: the batch's H2D bill
                            # (placed-handle metadata — free, gated no-op)
                            obs.memory.count_h2d(
                                obs.memory.tree_device_bytes(batch_dev)
                            )
                        h2d_acc[0] += time.perf_counter() - t_h0
                        return self._dispatch(batch_dev, key)

                    t_dispatch = time.perf_counter()
                    # trace active through the retry scope so injected
                    # scoring.batch faults attach to THIS chunk's chain
                    with tr.active():
                        dev_scores = retry_call(
                            run_batch,
                            policy=BATCH_RETRY_POLICY,
                            classify=is_transient,
                            label="score_batch",
                        )
                    # stage split: h2d = the placement walls (across
                    # retries); dispatch = everything else in the retry
                    # path — the async enqueue, injected pre-H2D faults,
                    # and retry backoff sleeps all charge here
                    stages["h2d"] = h2d_acc[0]
                    stages["dispatch"] = (
                        time.perf_counter() - t_dispatch
                    ) - h2d_acc[0]
                    # contiguous approximation of the measured walls:
                    # H2D then dispatch, from the dispatch stamp
                    tr.event(
                        "score.h2d", t_dispatch, stages["h2d"],
                        cat="score",
                    )
                    tr.event(
                        "score.dispatch", t_dispatch + stages["h2d"],
                        stages["dispatch"], cat="score", tries=tries,
                    )
                    if tries > 1:
                        stats.batch_retries += tries - 1
                        obs.counter("score.batch_retries", tries - 1)
                    # double buffer: batch i's read-back happens only
                    # after batch i+1 is enqueued, so H2D + host assembly
                    # of the next batch overlap the device compute of
                    # this one
                    if pending is not None:
                        finish(pending)
                    pending = (
                        dev_scores, item, t_dispatch, stages,
                        time.perf_counter(),
                    )
                if pending is not None and failure is None:
                    finish(pending)
            finally:
                # a consumer-side exception (batch assembly, dispatch, or
                # the caller's sink) must not leave the producer blocked
                # on a full queue holding decoded chunks: signal, drain,
                # reap — the thread and its staged memory are released
                # even on the failure path
                stop.set()
                while True:
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        break
                producer.join(timeout=5.0)
                if producer.is_alive():
                    # mid-decode of a large part file; it will see
                    # ``stop`` after the decode and exit, touching only
                    # this stream's (dead) stage counter
                    logger.warning(
                        "score-decode producer still draining after 5 s; "
                        "detaching"
                    )
            if failure is not None:
                raise failure
            stats.compiles = compile_watch.delta(cw_start)
            stats.wall_s = time.perf_counter() - t_start
            root.set(batches=stats.batches, samples=stats.samples)
            obs.memory.census("stream_end")
        return StreamResult(
            scores=(
                np.concatenate(collected)
                if collected
                else (np.zeros(0) if collect_scores else None)
            ),
            stats=stats,
        )

    def score_data(self, data: GameData) -> np.ndarray:
        """Score an in-memory GameData through the full streaming pipeline
        (chunked at ``batch_rows``) — the parity-testable entry point."""
        n = data.num_samples

        def gen():
            for lo in range(0, n, self.batch_rows):
                yield slice_game_data(data, lo, min(lo + self.batch_rows, n))

        return self.stream(gen()).scores
