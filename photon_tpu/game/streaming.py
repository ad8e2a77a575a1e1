"""Out-of-core streaming training: host-resident data, double-buffered
host→device chunk pipeline through the SAME traced solve/score bodies the
materialized coordinates compile.

The materialized path (game/coordinate.py) places the whole resolved
dataset on device before the first sweep — ROADMAP's "last structural
scale wall": ``n`` is capped by device memory. This module removes the
cap the way Snap ML's hierarchical pipeline does (PAPERS.md): the
dataset stays HOST-resident (the cache reader's mmap columns / the
built entity blocks), and each sweep streams fixed-shape chunks through
a two-deep host→device double buffer so chunk ``k+1``'s H2D transfer
overlaps chunk ``k``'s compute. Peak device residency is bounded at
**2 chunks + tables**, ledger-verified by an armed
:class:`photon_tpu.obs.memory.ResidencyGuard`.

Bit-parity contract (the property every streaming test pins): a
streaming fit produces coefficients BIT-IDENTICAL to the materialized
fit on the same data and seeds. This holds by construction, not by
tolerance:

- the chunk programs are the SAME traced bodies (``GLMProblem.solve``
  vmapped over entity lanes; ``einsum("md,md->m")`` score rows; the
  fixed-effect ``_score_body`` matvec) applied to row/lane slices —
  every output row of these bodies depends only on its own input row,
  so row-chunking cannot change any per-row reduction;
- the solve-chunk entity batch is clamped to the bucket's entity count
  (``ec = min(chunk_rows // rows, E)``), so any bucket that fits in one
  chunk solves with EXACTLY the materialized ``[E, rows, d]`` program.
  This clamp is load-bearing: XLA lowers the vmapped L-BFGS differently
  per batch size (identical lanes at batch 1 vs batch 4 differ in the
  last ulp on CPU), so buckets large enough to NEED multiple solve
  chunks — the out-of-core regime the materialized path cannot run
  anyway — are deterministic and bit-stable per chunk geometry, but not
  ulp-comparable to a hypothetical materialized fit;
- the host-side residual gather ``res_pad[min(sample_pos, N)]`` and the
  f32 elementwise adds (``offsets + extra``, ``residual + new_score``)
  are IEEE-identical to the device's versions of the same ops;
- the host score scatter writes each kept sample exactly once per
  bucket (the build renumbers flat pad rows past ``num_samples``), so
  ``out[pos] += s`` equals the device's score (each sample's one sum,
  laid in sample order by the materialized coordinate's build).

What streaming mode does NOT cover (validated loudly at fit entry, not
discovered mid-sweep): trainable fixed-effect coordinates (the global
L-BFGS needs every row per iteration — a locked FE coordinate streams
its score and is fully supported), matrix-factorization coordinates,
device validation scorers, per-coefficient variances, and in-process
device meshes (meshed fits keep the materialized path; multi-PROCESS
sharded ingest composes naturally — each process streams only its
disjoint ``ingest_shard`` slice of the cache).

Health caveat: the per-sweep loss/gnorm health scalars are host-summed
in chunk order, so their floating-point association differs from the
materialized single-reduction values in the last ulp. Health is
observability (divergence detection uses only finiteness); the
COEFFICIENTS are bit-exact.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import queue
import threading
import time
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu import obs
from photon_tpu.game.coordinate import (
    TRACE_COUNTERS,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
    _make_sweep_jits,
)
from photon_tpu.game.config import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.data import GameData, RandomEffectDataset
from photon_tpu.game.model import (
    BucketCoefficients,
    FixedEffectModel,
    RandomEffectModel,
)
from photon_tpu.game.scoring import (
    ProducerDiedError,
    StreamStallError,
    stream_watchdog_s,
)
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.glm import model_for_task
from photon_tpu.obs import causal
from photon_tpu.obs import memory as obs_memory
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.optimize.common import one_solve_a_lane
from photon_tpu.optimize.problem import GLMProblem
from photon_tpu.types import LabeledBatch
from photon_tpu.util import dispatch_count, faults
from photon_tpu.util.sanitize import sanctioned_transfers
from photon_tpu.util.target import donation_enabled

logger = logging.getLogger(__name__)

__all__ = [
    "StreamConfig",
    "StreamTelemetry",
    "StreamingFixedEffectCoordinate",
    "StreamingModeError",
    "StreamingRandomEffectCoordinate",
    "stream_chunk_rows",
]

DEFAULT_CHUNK_ROWS = 8192


class StreamingModeError(ValueError):
    """A fit/config combination streaming mode does not support — raised
    at fit entry (or model export), never silently degraded."""


def stream_chunk_rows(config_value: int | None = None) -> int:
    """Rows per training chunk: ``PHOTON_STREAM_CHUNK_ROWS`` env >
    CLI/config value > :data:`DEFAULT_CHUNK_ROWS`."""
    env = os.environ.get("PHOTON_STREAM_CHUNK_ROWS", "").strip()
    if env:
        v = int(env)
    elif config_value is not None:
        v = int(config_value)
    else:
        return DEFAULT_CHUNK_ROWS
    if v < 1:
        raise ValueError(f"stream chunk rows must be >= 1, got {v}")
    return v


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Knobs of the streaming training pipeline.

    ``chunk_rows`` is the chunk-shape policy's single input: fixed-effect
    score chunks and flat RE score chunks carry ``chunk_rows`` sample
    rows; an RE solve chunk carries ``max(1, chunk_rows // bucket_rows)``
    entity lanes of its bucket's ``[rows, d]`` level (so every chunk
    moves ~the same number of sample rows regardless of bucket shape,
    and buckets sharing a level share ONE compiled chunk program). Final
    partial chunks are zero-padded to the fixed shape — zero steady-state
    compiles, one program per (level, chunk) shape.
    """

    chunk_rows: int = DEFAULT_CHUNK_ROWS
    #: producer→consumer queue depth; 2 = the double buffer (one chunk
    #: staged behind the one in flight)
    queue_depth: int = 2
    #: producer watchdog seconds (``PHOTON_STREAM_WATCHDOG_S`` wins; 0
    #: disables) — same contract as the streaming scorer
    watchdog_s: float | None = None
    #: arm the memory-ledger residency guard: fail loudly when live
    #: device bytes exceed baseline + 2 x chunk_bytes + tables + slack
    assert_residency: bool = True
    #: allowance for allocator slop, the reg scalar, and per-chunk
    #: program outputs on top of the structural 2-chunk bound
    residency_slack_bytes: int = 8 << 20

    @staticmethod
    def resolve(value) -> "StreamConfig":
        """Coerce a fit()/CLI streaming request into a StreamConfig:
        an int is chunk_rows, True means env/default, a StreamConfig
        passes through (env still wins on chunk_rows)."""
        if isinstance(value, StreamConfig):
            return dataclasses.replace(
                value, chunk_rows=stream_chunk_rows(value.chunk_rows)
            )
        if value is True:
            return StreamConfig(chunk_rows=stream_chunk_rows())
        if isinstance(value, int) and not isinstance(value, bool):
            return StreamConfig(chunk_rows=stream_chunk_rows(value))
        raise TypeError(
            f"stream must be a StreamConfig, an int chunk size, or True; "
            f"got {value!r}"
        )


class StreamTelemetry:
    """Per-fit accumulator for the chunk pipeline's stage waterfall —
    the PR 15 stage-walls idiom applied to training: queue wait, H2D
    placement, program dispatch, read-back, and the H2D-overlap split
    the bench gate reads (H2D walls spent while a previous chunk's
    program was in flight, i.e. genuinely overlapped with compute).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.stage_s: dict[str, float] = {}
        self.chunks = 0
        self.streams = 0
        self.h2d_bytes = 0
        self.overlapped_h2d_s = 0.0
        self.overlapped_h2d_bytes = 0
        #: armed by the estimator when assert_residency is on
        self.guard: obs_memory.ResidencyGuard | None = None

    def record_stage(self, stage: str, seconds: float) -> None:
        with self._lock:
            self.stage_s[stage] = self.stage_s.get(stage, 0.0) + seconds
        obs.histogram(f"train.stream.stage_seconds.{stage}", seconds)

    def record_chunk(
        self, nbytes: int, h2d_s: float, overlapped: bool
    ) -> None:
        with self._lock:
            self.chunks += 1
            self.h2d_bytes += int(nbytes)
            if overlapped:
                self.overlapped_h2d_s += h2d_s
                self.overlapped_h2d_bytes += int(nbytes)
        obs_memory.count_h2d(int(nbytes))

    def overlap_fraction(self) -> float:
        """Fraction of H2D wall spent while a chunk program was in
        flight: every placement except each stream's FIRST overlaps the
        previous chunk's compute, so a k-chunk sweep approaches
        (k-1)/k."""
        total = self.stage_s.get("h2d", 0.0)
        if total <= 0.0:
            return 0.0
        return self.overlapped_h2d_s / total

    def report(self) -> dict:
        with self._lock:
            out = {
                "chunks": self.chunks,
                "streams": self.streams,
                "h2d_bytes": self.h2d_bytes,
                "overlapped_h2d_bytes": self.overlapped_h2d_bytes,
                "stage_seconds": {
                    k: round(v, 6) for k, v in sorted(self.stage_s.items())
                },
                "overlapped_h2d_seconds": round(self.overlapped_h2d_s, 6),
            }
        out["h2d_overlap_fraction"] = round(self.overlap_fraction(), 4)
        if self.guard is not None:
            out["residency"] = self.guard.report()
        return out


# -- the double-buffered chunk pipeline -------------------------------------

_DONE = object()


class _Failure:
    def __init__(self, exc: BaseException):
        self.exc = exc


def _produce(
    chunk_iter: Iterator, q: queue.Queue, stop: threading.Event
) -> None:
    """Producer thread: assemble host chunks and hand them off through
    the bounded queue. Mirrors the streaming scorer's producer contract
    (game/scoring.py): the ``train.stream.producer`` chaos hook sits
    OUTSIDE the try, so an injected ``error`` kills the thread with no
    sentinel and no _Failure — abrupt death, exactly what the consumer's
    watchdog must convert into :class:`ProducerDiedError`; the per-chunk
    ``train.stream.chunk`` hook reports through the normal _Failure
    hand-off. Every put is bounded by ``stop`` so a failed consumer
    never leaves this thread blocked on a full queue."""
    faults.fault_point("train.stream.producer")

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
            # one causal trace per training chunk (obs/causal.py),
            # minted before assembly so an injected chunk fault lands
            # inside this chunk's chain; the consumer receives
            # (trace, item) pairs — sentinels travel bare
            ctx = causal.mint("train.chunk", kind="train")
            with ctx.active():
                faults.fault_point("train.stream.chunk")
                item = next(chunk_iter, _DONE)
            if item is _DONE:
                put(_DONE)
                return
            t_done = time.perf_counter()
            ctx.event("train.produce", t_pull, t_done - t_pull, cat="train")
            ctx.flow("s", t_pull)
            if not put((ctx, item)):
                return
    except BaseException as e:  # propagate into the consumer loop
        ctx.finish("error")
        put(_Failure(e))


def _next_item(q: queue.Queue, producer: threading.Thread, watchdog_s: float):
    """Watchdog-guarded hand-off read (same two silent-wedge conversions
    as the streaming scorer): dead producer + empty queue →
    :class:`ProducerDiedError`; alive but silent for the watchdog window
    → :class:`StreamStallError`."""
    waited = 0.0
    poll = 0.5 if watchdog_s == 0 else min(0.5, watchdog_s)
    while True:
        try:
            return q.get(timeout=poll)
        except queue.Empty:
            pass
        if not producer.is_alive():
            try:  # it may have put + exited between timeout and check
                return q.get_nowait()
            except queue.Empty:
                obs.counter("train.stream.producer_deaths")
                raise ProducerDiedError(
                    "training chunk producer thread died without "
                    "reporting a result or an error; the streaming sweep "
                    "cannot make progress"
                ) from None
        waited += poll
        if watchdog_s and waited >= watchdog_s:
            obs.counter("train.stream.stalls")
            raise StreamStallError(
                f"training chunk producer produced nothing for "
                f"{waited:.0f}s (watchdog "
                f"PHOTON_STREAM_WATCHDOG_S={watchdog_s:g}); treating the "
                "stream as hung"
            )


def run_stream(
    host_iter: Iterator,
    put_fn: Callable,
    run_fn: Callable,
    sink_fn: Callable,
    *,
    telemetry: StreamTelemetry,
    stream: StreamConfig,
    label: str,
) -> int:
    """Drive one stream of host chunks through the two-deep host→device
    double buffer. Per chunk, in order:

    1. pull the next host chunk from the producer queue (``queue`` wall);
    2. explicitly ``device_put`` it (``h2d`` wall) — while the PREVIOUS
       chunk's program is still in flight, so the transfer overlaps its
       compute (the overlap the telemetry splits out);
    3. retire the previous chunk: fetch its outputs (``readback`` wall —
       this is where device compute is actually waited on) and run the
       host write-back;
    4. dispatch this chunk's program (``dispatch`` wall — enqueue only).

    At any instant at most TWO chunks' device buffers are live (the one
    in flight and the one just placed) — the residency bound the armed
    guard samples right after each placement, at the peak.

    ``put_fn(item) -> (dev_item, nbytes)`` must use explicit placement
    (the sweep runs under the transfer sanitizer); ``run_fn(item,
    dev_item) -> out`` dispatches without blocking; ``sink_fn(item,
    out)`` owns the sanctioned read-back. Returns the chunk count.
    """
    causal.ensure_from_env()
    q: queue.Queue = queue.Queue(maxsize=max(1, stream.queue_depth))
    stop = threading.Event()
    watchdog = stream_watchdog_s(stream.watchdog_s)
    producer = threading.Thread(
        target=_produce,
        args=(host_iter, q, stop),
        name=f"train-stream-{label}",
        daemon=True,
    )
    producer.start()
    telemetry.streams += 1
    n_chunks = 0
    pending = None  # (host_item, dev_out) awaiting read-back
    t_stream = time.perf_counter()
    def retire(held) -> None:
        """Read back + write back the held chunk and close its trace:
        the flow FINISH lands inside the read-back slice, so chunk k's
        closing arrow visibly crosses chunk k+1's H2D slice — the
        two-deep overlap, auditable in Perfetto instead of asserted."""
        ctx, item, out = held
        t2 = time.perf_counter()
        sink_fn(item, out)
        rb_s = time.perf_counter() - t2
        telemetry.record_stage("readback", rb_s)
        ctx.event("train.readback", t2, rb_s, cat="train")
        ctx.flow("f", t2)
        ctx.finish("ok")

    try:
        while True:
            t0 = time.perf_counter()
            item = _next_item(q, producer, watchdog)
            queue_s = time.perf_counter() - t0
            telemetry.record_stage("queue", queue_s)
            if isinstance(item, _Failure):
                raise item.exc
            if item is _DONE:
                break
            ctx, item = item
            try:
                with ctx.active():
                    faults.fault_point("train.stream.h2d")
            except BaseException:
                ctx.finish("fault")
                raise
            t1 = time.perf_counter()
            dev_item, nbytes = put_fn(item)
            h2d_s = time.perf_counter() - t1
            telemetry.record_stage("h2d", h2d_s)
            telemetry.record_chunk(nbytes, h2d_s, overlapped=pending is not None)
            ctx.event(
                "train.h2d", t1, h2d_s, cat="train",
                nbytes=int(nbytes), queue_s=round(queue_s, 6),
            )
            ctx.flow("t", t1)
            if telemetry.guard is not None:
                # sampled at the residency PEAK: the just-placed chunk
                # plus the previous chunk still in flight
                telemetry.guard.sample()
            if pending is not None:
                retire(pending)
            t3 = time.perf_counter()
            dispatch_count.record(1)
            with ctx.active():
                out = run_fn(item, dev_item)
            dispatch_s = time.perf_counter() - t3
            telemetry.record_stage("dispatch", dispatch_s)
            ctx.event("train.dispatch", t3, dispatch_s, cat="train")
            ctx.flow("t", t3)
            pending = (ctx, item, out)
            n_chunks += 1
        if pending is not None:
            retire(pending)
            pending = None
    finally:
        stop.set()
        producer.join(timeout=10.0)
    telemetry.record_stage("pipeline", time.perf_counter() - t_stream)
    return n_chunks


def _np_dtype(dtype) -> np.dtype:
    return np.dtype(jnp.dtype(dtype))


def _pad_rows(arr: np.ndarray, rows: int, fill=0) -> np.ndarray:
    """Zero-pad (or ``fill``-pad) the leading axis up to ``rows`` —
    the fixed-shape promise that keeps chunk programs AOT-stable."""
    if arr.shape[0] == rows:
        return arr
    out = np.full((rows,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


# -- streaming fixed effect (locked: score stream only) ---------------------


@dataclasses.dataclass(eq=False)
class StreamingFixedEffectCoordinate(FixedEffectCoordinate):
    """Locked fixed-effect coordinate whose [N] score column is computed
    by streaming dense row chunks of the HOST CSR shard through the same
    ``_score_body`` the materialized coordinate jit-compiles. The [N, D]
    feature block never materializes on device (or even on host — each
    chunk densifies from CSR in the producer thread); state and score
    live as host numpy and ride descent unchanged (``util/force``
    passes host leaves through every barrier/fetch).

    Training is NOT supported: the fixed-effect L-BFGS is a global
    reduction over every row per iteration, which a bit-exact chunk
    pipeline cannot reproduce without cross-chunk optimizer state.
    Streaming fits therefore require FE coordinates to be locked — the
    daily-retrain scenario's shape (yesterday's FE model scores; today's
    random effects train).
    """

    shard_csr: object = None  # host CSRMatrix (mmap views under the cache)
    num_samples: int = 0
    stream: StreamConfig = None
    telemetry: StreamTelemetry = None

    @staticmethod
    def build_streaming(
        data: GameData,
        config: FixedEffectCoordinateConfig,
        normalization: NormalizationContext = NormalizationContext(),
        dtype=jnp.float32,
        stream: StreamConfig = None,
        telemetry: StreamTelemetry = None,
    ) -> "StreamingFixedEffectCoordinate":
        shard = data.feature_shards[config.feature_shard]
        problem = GLMProblem.build(
            config.optimization.with_regularization_weight(
                config.regularization_weights[0]
            ),
            normalization,
        )
        return StreamingFixedEffectCoordinate(
            config=config,
            feature_shard=config.feature_shard,
            batch=None,  # never materialized — the point of this class
            normalization=normalization,
            problem=problem,
            dtype=dtype,
            num_features=shard.num_cols,
            mesh=None,
            shard_csr=shard,
            num_samples=int(data.num_samples),
            stream=stream or StreamConfig(),
            telemetry=telemetry if telemetry is not None else StreamTelemetry(),
        )

    # -- state placement: host numpy ------------------------------------

    def initial_state(self) -> np.ndarray:
        return np.zeros((self.num_features,), dtype=_np_dtype(self.dtype))

    def place_state(self, state) -> np.ndarray:
        with sanctioned_transfers(
            "streaming FE state host placement (warm start / resume)"
        ):
            return np.array(state, dtype=_np_dtype(self.dtype))

    # -- the chunk program ----------------------------------------------

    def _dense_rows(self, lo: int, hi: int) -> np.ndarray:
        """Densify CSR rows [lo, hi) into a fixed-shape [chunk_rows, D]
        block (tail rows zero) — the same per-element dtype conversion
        ``CSRMatrix.to_dense`` performs, sliced."""
        m = self.shard_csr
        cr = self.stream.chunk_rows
        feat_dtype = jnp.bfloat16 if self.config.bf16_features else self.dtype
        out = np.zeros((cr, self.num_features), dtype=_np_dtype(feat_dtype))
        nz_lo, nz_hi = int(m.indptr[lo]), int(m.indptr[hi])
        rows = np.repeat(
            np.arange(hi - lo), np.diff(np.asarray(m.indptr[lo : hi + 1]))
        )
        out[rows, m.indices[nz_lo:nz_hi]] = m.values[nz_lo:nz_hi]
        return out

    def _iter_score_chunks(self) -> Iterator:
        cr = self.stream.chunk_rows
        for lo in range(0, self.num_samples, cr):
            hi = min(lo + cr, self.num_samples)
            yield (lo, hi, self._dense_rows(lo, hi))

    def _stream_score_body(self, features, norm_args, state):
        TRACE_COUNTERS["stream_fe_score"] += 1
        z = jnp.zeros((features.shape[0],), dtype=self.dtype)
        batch = LabeledBatch(features=features, labels=z, offsets=z, weights=z)
        return self._score_body(batch, norm_args, state)

    _stream_score_jit, _stream_score_jit_nodonate = _make_sweep_jits(
        _stream_score_body, static_argnums=0, donate_argnums=(1,),
        name="stream_fe_score",
    )

    def score(self, state) -> np.ndarray:
        n = self.num_samples
        out = np.zeros((n,), dtype=_np_dtype(self.dtype))
        norm_args = self._norm_args()
        with sanctioned_transfers("streaming FE state placement per score"):
            state_dev = jax.device_put(
                jnp.asarray(np.asarray(state), dtype=self.dtype)
            )
        d = donation_enabled()
        # class-attribute access: the UNBOUND jit pair (self rides as the
        # explicit static arg, like the materialized sweep pair)
        exe = (
            type(self)._stream_score_jit
            if d
            else type(self)._stream_score_jit_nodonate
        )
        key = ("stream_score", self.stream.chunk_rows, d)

        def put_fn(item):
            lo, hi, block = item
            return jax.device_put(block), block.nbytes

        def run_fn(item, dev_block):
            res = self._aot_call(key, dev_block, norm_args, state_dev)
            if res is None:
                res = exe(self, dev_block, norm_args, state_dev)
            return res

        def sink_fn(item, res):
            lo, hi, _ = item
            with sanctioned_transfers("streaming FE score read-back"):
                host = np.asarray(res)
            out[lo:hi] = host[: hi - lo]

        with obs.span(
            "train.stream.fe_score", cat="stream", coordinate=self.feature_shard
        ):
            run_stream(
                self._iter_score_chunks(), put_fn, run_fn, sink_fn,
                telemetry=self.telemetry, stream=self.stream,
                label="fe-score",
            )
        return out

    def max_chunk_device_bytes(self) -> int:
        feat_dtype = jnp.bfloat16 if self.config.bf16_features else self.dtype
        cr = self.stream.chunk_rows
        itemsize = int(jnp.dtype(feat_dtype).itemsize)
        out_bytes = cr * int(jnp.dtype(self.dtype).itemsize)
        return cr * self.num_features * itemsize + out_bytes

    # -- unsupported-in-streaming entry points --------------------------

    def train(self, residual_scores, state):
        raise StreamingModeError(
            "streaming fits require fixed-effect coordinates to be locked "
            "(the global L-BFGS cannot train bit-exactly from chunks); "
            "train the FE coordinate materialized, then stream with it "
            "locked"
        )

    def sweep_step(self, total, score, state, donate=None):
        self.train(None, state)  # raises

    def precompile_specs(
        self, donate=None, include_sweep=True, include_score=True
    ) -> list:
        out = []
        if include_score:
            d = bool(donate) if donate is not None else donation_enabled()
            feat_dtype = (
                jnp.bfloat16 if self.config.bf16_features else self.dtype
            )
            sds = jax.ShapeDtypeStruct(
                (self.stream.chunk_rows, self.num_features), feat_dtype
            )
            exe = (
                type(self)._stream_score_jit
                if d
                else type(self)._stream_score_jit_nodonate
            )
            out.append(
                (
                    ("stream_score", self.stream.chunk_rows, d),
                    "stream_score",
                    exe.lower(self, sds, self._norm_args(), self._state_sds()),
                )
            )
        return out

    def to_model(self, state):
        if self.problem.config.variance_computation.value != "NONE":
            raise StreamingModeError(
                "streaming fits do not compute coefficient variances; "
                "set variance_computation=NONE"
            )
        w = self.normalization.model_to_original_space(
            jnp.asarray(state, dtype=self.dtype)
        )
        glm = model_for_task(
            self.config.optimization.task, Coefficients(means=w, variances=None)
        )
        return FixedEffectModel(model=glm, feature_shard=self.feature_shard)


# -- streaming random effect ------------------------------------------------


@dataclasses.dataclass(eq=False)
class _HostBucket:
    """One size bucket's HOST-resident blocks, dtype-converted once at
    build so every chunk slice device_puts with zero conversion (pure
    placement — the values the device sees are byte-identical to what
    the materialized build would have placed)."""

    features: np.ndarray  # [E, n, d]
    labels: np.ndarray  # [E, n]
    offsets: np.ndarray  # [E, n]
    train_weights: np.ndarray  # [E, n]
    sample_pos: np.ndarray  # [E, n] int32 (num_samples ⇒ pad)
    score_feats: np.ndarray  # [M, d]
    score_slot: np.ndarray  # [M] int32
    score_pos: np.ndarray  # [M] int32
    entity_ids: np.ndarray
    col_index: np.ndarray
    ec: int  # entity lanes per solve chunk (chunk-shape policy)

    @property
    def num_entities(self) -> int:
        return self.features.shape[0]

    @property
    def rows(self) -> int:
        return self.features.shape[1]

    @property
    def dim(self) -> int:
        return self.features.shape[2]


@dataclasses.dataclass(eq=False)
class StreamingRandomEffectCoordinate(RandomEffectCoordinate):
    """Random-effect coordinate that trains by streaming entity-lane
    chunks of its host buckets through the SAME vmapped
    ``GLMProblem.solve`` body the materialized fused sweep traces, and
    scores by streaming flat score-row chunks through the same
    ``einsum("md,md->m")``. Coefficient tables, the [N] score/total
    columns, and the residual all live as host numpy; only the two
    in-flight chunks occupy device memory.

    One sweep = two chained streams: (A) the SOLVE stream walks every
    bucket's entity chunks (host residual gather → device vmapped solve
    → coefficient write-back into the host table), then (B) the SCORE
    stream walks flat score-row chunks (host coefficient-row gather →
    device einsum → host scatter into the new score column). Both keep
    the double buffer full across bucket boundaries, and all chunk
    programs compile in sweep 0 (final chunks are zero-padded to the
    fixed shape) — zero steady-state compiles, and descent's one
    read-back barrier per sweep becomes a no-op fetch of host scalars.
    """

    stream: StreamConfig = None
    telemetry: StreamTelemetry = None

    @staticmethod
    def build_streaming(
        dataset: RandomEffectDataset,
        config: RandomEffectCoordinateConfig,
        dtype=jnp.float32,
        stream: StreamConfig = None,
        telemetry: StreamTelemetry = None,
    ) -> "StreamingRandomEffectCoordinate":
        stream = stream or StreamConfig()
        coord = StreamingRandomEffectCoordinate(
            config=config,
            dataset=dataset,
            device_buckets=[],  # nothing device-resident — the point
            problem_config=config.optimization.with_regularization_weight(
                config.regularization_weights[0]
            ),
            num_samples=int(dataset.num_samples),
            dtype=dtype,
            mesh=None,
            stream=stream,
            telemetry=telemetry if telemetry is not None else StreamTelemetry(),
        )
        dt = _np_dtype(dtype)
        host_buckets = []
        for b in dataset.buckets:
            rows = max(int(b.padded_samples), 1)
            # chunk-shape policy: ~chunk_rows sample rows per solve chunk,
            # so buckets sharing a (rows, d) level share ONE program —
            # clamped to the bucket's entity count so a bucket that fits
            # in a single chunk solves with EXACTLY the materialized
            # [E, rows, d] batch shape. XLA lowers the vmapped solver
            # differently per batch size (last-ulp reassociation), so the
            # clamp is what makes single-chunk buckets bit-exact against
            # the materialized path; multi-chunk buckets are bit-stable
            # per chunk geometry instead (see the module docstring).
            ec = min(
                max(1, stream.chunk_rows // rows),
                max(int(b.num_entities), 1),
            )
            host_buckets.append(
                _HostBucket(
                    features=np.asarray(b.features, dtype=dt),
                    labels=np.asarray(b.labels, dtype=dt),
                    offsets=np.asarray(b.offsets, dtype=dt),
                    train_weights=np.asarray(b.weights, dtype=dt),
                    sample_pos=np.asarray(b.sample_pos, dtype=np.int32),
                    score_feats=np.asarray(b.score_feats, dtype=dt),
                    score_slot=np.asarray(b.score_slot, dtype=np.int32),
                    score_pos=np.asarray(b.score_pos, dtype=np.int32),
                    entity_ids=b.entity_ids,
                    col_index=b.col_index,
                    ec=ec,
                )
            )
        coord._host_buckets = host_buckets
        return coord

    # -- state: host numpy tables ---------------------------------------

    def initial_state(self) -> list:
        dt = _np_dtype(self.dtype)
        return [
            np.zeros((hb.num_entities, hb.dim), dtype=dt)
            for hb in self._host_buckets
        ]

    def place_state(self, state: list) -> list:
        dt = _np_dtype(self.dtype)
        with sanctioned_transfers(
            "streaming RE state host placement (warm start / resume)"
        ):
            return [np.array(w, dtype=dt) for w in state]

    # -- chunk programs (the same traced bodies, chunk-shaped) ----------

    def _solve_chunk_body(
        self, features, labels, offsets_eff, train_weights, w0, reg_weight
    ):
        """Vmapped per-entity solve over ONE chunk of entity lanes — the
        exact ``solve_one`` body ``_solve_bucket`` vmaps, minus the
        residual gather (done on host, IEEE-identically) and minus the
        mesh branch (streaming is per-process). Returns the chunk's
        coefficients plus per-lane loss/grad-norm² for the host-summed
        health fold."""
        TRACE_COUNTERS["stream_re_solve"] += 1
        problem = GLMProblem.build(self.problem_config)

        def solve_one(f, l, o, w, w0_e):
            batch = LabeledBatch(features=f, labels=l, offsets=o, weights=w)
            return problem.solve(batch, w0_e, reg_weight)

        with one_solve_a_lane():
            res = jax.vmap(solve_one)(
                features, labels, offsets_eff, train_weights, w0
            )
        gsq = jnp.sum(jnp.square(res.gradient.astype(jnp.float32)), axis=-1)
        return res.x, res.value.astype(jnp.float32), gsq

    _solve_chunk_jit, _solve_chunk_jit_nodonate = _make_sweep_jits(
        _solve_chunk_body, static_argnums=0, donate_argnums=(1, 2, 3, 4, 5),
        name="stream_re_solve",
    )

    def _score_chunk_body(self, score_feats, coef_rows):
        """One flat score-row chunk: feature rows dotted with their
        HOST-gathered coefficient rows — the ``einsum`` of
        ``RandomEffectCoordinate._rescore_rows`` with the slot gather and
        the way to sample order moved to host (gather: same values;
        positions: unique, so the host fancy ``+=`` adds each sample's one
        sum to zero, as the materialized coordinate's merged block does)."""
        TRACE_COUNTERS["stream_re_score"] += 1
        c = coef_rows.astype(score_feats.dtype)
        return jnp.einsum("md,md->m", score_feats, c)

    _score_chunk_jit, _score_chunk_jit_nodonate = _make_sweep_jits(
        _score_chunk_body, static_argnums=0, donate_argnums=(1, 2),
        name="stream_re_score",
    )

    def _chunk_exes(self, donate=None):
        # class-attribute access: the UNBOUND jit pairs (self rides as the
        # explicit static arg, like the materialized sweep pair)
        d = bool(donate) if donate is not None else donation_enabled()
        cls = type(self)
        solve = cls._solve_chunk_jit if d else cls._solve_chunk_jit_nodonate
        score = cls._score_chunk_jit if d else cls._score_chunk_jit_nodonate
        return d, solve, score

    # -- the score stream -----------------------------------------------

    def _iter_score_chunks(self, state: list) -> Iterator:
        mc = self.stream.chunk_rows
        for bi, hb in enumerate(self._host_buckets):
            m = hb.score_feats.shape[0]
            coefs = state[bi]
            for m0 in range(0, m, mc):
                real = min(mc, m - m0)
                feats = _pad_rows(hb.score_feats[m0 : m0 + real], mc)
                # host coefficient-row gather (same values the device
                # gather reads); pad rows dot zero features anyway
                crows = _pad_rows(coefs[hb.score_slot[m0 : m0 + real]], mc)
                pos = hb.score_pos[m0 : m0 + real]
                yield (bi, real, feats, crows, pos)

    def _stream_score(self, state: list, donate=None) -> np.ndarray:
        out = np.zeros((self.num_samples,), dtype=_np_dtype(self.dtype))
        d, _, score_exe = self._chunk_exes(donate)
        mc = self.stream.chunk_rows
        reg_label = self.config.random_effect_type

        def put_fn(item):
            bi, real, feats, crows, pos = item
            dev = (jax.device_put(feats), jax.device_put(crows))
            return dev, feats.nbytes + crows.nbytes

        def run_fn(item, dev):
            feats_d, crows_d = dev
            key = ("stream_score", mc, int(feats_d.shape[1]), d)
            res = self._aot_call(key, feats_d, crows_d)
            if res is None:
                res = score_exe(self, feats_d, crows_d)
            return res

        def sink_fn(item, res):
            bi, real, _, _, pos = item
            with sanctioned_transfers("streaming RE score read-back"):
                s = np.asarray(res)[:real]
            valid = pos < self.num_samples
            # positions are unique per bucket (build renumbers flat pad
            # rows past num_samples), so fancy += is an exact scatter-add
            out[pos[valid]] += s[valid]

        with obs.span(
            "train.stream.re_score", cat="stream", coordinate=reg_label
        ):
            run_stream(
                self._iter_score_chunks(state), put_fn, run_fn, sink_fn,
                telemetry=self.telemetry, stream=self.stream,
                label="re-score",
            )
        return out

    def score(self, state: list) -> np.ndarray:
        return self._stream_score(state)

    # -- the solve stream + the fused sweep ------------------------------

    def _iter_solve_chunks(self, state: list, res_pad: np.ndarray) -> Iterator:
        n_res = res_pad.shape[0] - 1
        for bi, hb in enumerate(self._host_buckets):
            ec = hb.ec
            e = hb.num_entities
            coefs = state[bi]
            for e0 in range(0, e, ec):
                real = min(ec, e - e0)
                sl = slice(e0, e0 + real)
                # host residual gather + fold — the same clamp-to-sentinel
                # gather and f32 elementwise add `_solve_bucket` traces,
                # value-identical on host
                extra = res_pad[np.minimum(hb.sample_pos[sl], n_res)]
                oeff = (hb.offsets[sl] + extra).astype(hb.offsets.dtype)
                yield (
                    bi,
                    e0,
                    real,
                    _pad_rows(hb.features[sl], ec),
                    _pad_rows(hb.labels[sl], ec),
                    _pad_rows(oeff, ec),
                    _pad_rows(hb.train_weights[sl], ec),
                    _pad_rows(
                        hb.sample_pos[sl], ec, fill=self.num_samples
                    ),  # kept for shape symmetry; pad lanes train to zero
                    _pad_rows(coefs[sl], ec),
                )

    def sweep_step(self, total, score, state, donate=None):
        residual = np.asarray(total) - np.asarray(score)
        res_pad = np.concatenate(
            [residual, np.zeros((1,), dtype=residual.dtype)]
        )
        d, solve_exe, _ = self._chunk_exes(donate)
        reg_w = self._reg_scalar(self.problem_config.regularization_weight)
        new_state = [np.empty_like(w) for w in state]
        loss_sum = np.float32(0.0)
        gsq_sum = np.float32(0.0)
        n_chunks = 0

        def put_fn(item):
            bi, e0, real, f, l, o, tw, sp, w0 = item
            dev = tuple(
                jax.device_put(a) for a in (f, l, o, tw, w0)
            )
            return dev, sum(a.nbytes for a in (f, l, o, tw, w0))

        def run_fn(item, dev):
            f_d = dev[0]
            key = (
                "stream_solve",
                int(f_d.shape[0]), int(f_d.shape[1]), int(f_d.shape[2]), d,
            )
            res = self._aot_call(key, *dev, reg_w)
            if res is None:
                res = solve_exe(self, *dev, reg_w)
            return res

        def sink_fn(item, res):
            nonlocal loss_sum, gsq_sum
            bi, e0, real, *_ = item
            with sanctioned_transfers("streaming RE solve read-back"):
                x = np.asarray(res[0])
                val = np.asarray(res[1])
                gq = np.asarray(res[2])
            new_state[bi][e0 : e0 + real] = x[:real]
            loss_sum += val[:real].sum(dtype=np.float32)
            gsq_sum += gq[:real].sum(dtype=np.float32)

        with obs.span(
            "train.stream.re_solve", cat="stream",
            coordinate=self.config.random_effect_type,
        ):
            n_chunks = run_stream(
                self._iter_solve_chunks(state, res_pad), put_fn, run_fn,
                sink_fn, telemetry=self.telemetry, stream=self.stream,
                label="re-solve",
            )

        new_score = self._stream_score(new_state, donate=donate)
        new_total = residual + new_score
        gnorm = np.sqrt(np.float32(gsq_sum))
        finite = (
            np.isfinite(loss_sum)
            and np.isfinite(gnorm)
            and all(np.isfinite(w).all() for w in new_state)
        )
        # host floats ride descent's one barrier fetch unchanged
        # (util/force.fetch_scalars passes non-device scalars through);
        # loss/gnorm are host-summed in chunk order — last-ulp association
        # vs the materialized single reduction, observability only
        health = {
            "loss": float(loss_sum),
            "gnorm": float(gnorm),
            "finite": float(finite),
        }
        info = {"streamed": True, "chunks": int(n_chunks)}
        return new_state, new_score, new_total, info, health

    def train(self, residual_scores, state):
        raise NotImplementedError(
            "streaming RE coordinates train through sweep_step (the "
            "chunked solve stream); the standalone train() entry is a "
            "materialized-path API"
        )

    # -- AOT + accounting -----------------------------------------------

    def precompile_specs(
        self, donate=None, include_sweep=True, include_score=True
    ) -> list:
        d, solve_exe, score_exe = self._chunk_exes(donate)
        out = []
        seen = set()
        mc = self.stream.chunk_rows

        def sds(shape):
            return jax.ShapeDtypeStruct(shape, self.dtype)

        for hb in self._host_buckets:
            if include_sweep:
                key = ("stream_solve", hb.ec, hb.rows, hb.dim, d)
                if key not in seen:
                    seen.add(key)
                    f = sds((hb.ec, hb.rows, hb.dim))
                    v = sds((hb.ec, hb.rows))
                    w0 = sds((hb.ec, hb.dim))
                    out.append(
                        (
                            key,
                            "stream_solve",
                            solve_exe.lower(
                                self, f, v, v, v, w0, self._scalar_sds()
                            ),
                        )
                    )
            if include_score:
                key = ("stream_score", mc, hb.dim, d)
                if key not in seen:
                    seen.add(key)
                    rows = sds((mc, hb.dim))
                    out.append(
                        (key, "stream_score", score_exe.lower(self, rows, rows))
                    )
        return out

    def max_chunk_device_bytes(self) -> int:
        """Worst-case device bytes ONE chunk occupies (inputs + outputs)
        — the unit of the `2 x chunk_bytes + tables` residency bound."""
        itemsize = int(jnp.dtype(self.dtype).itemsize)
        worst = 0
        for hb in self._host_buckets:
            solve_in = (
                hb.ec * hb.rows * hb.dim  # features
                + 3 * hb.ec * hb.rows  # labels/offsets/weights
                + hb.ec * hb.dim  # w0
            ) * itemsize
            solve_out = (hb.ec * hb.dim + 2 * hb.ec) * 4
            score = (
                2 * self.stream.chunk_rows * hb.dim * itemsize
                + self.stream.chunk_rows * itemsize
            )
            worst = max(worst, solve_in + solve_out, score)
        return worst

    def to_model(self, state: list) -> RandomEffectModel:
        if self.problem_config.variance_computation.value != "NONE":
            raise StreamingModeError(
                "streaming fits do not compute coefficient variances; "
                "set variance_computation=NONE"
            )
        dt = _np_dtype(self.dtype)
        buckets = []
        for hb, coefs in zip(self._host_buckets, state):
            buckets.append(
                BucketCoefficients(
                    entity_ids=hb.entity_ids,
                    col_index=hb.col_index,
                    coefficients=np.array(coefs, dtype=dt),  # snapshot
                    variances=None,
                )
            )
        return RandomEffectModel(
            random_effect_type=self.config.random_effect_type,
            feature_shard=self.config.feature_shard,
            task=self.problem_config.task,
            vocab=self.dataset.vocab,
            buckets=tuple(buckets),
            num_features=self.dataset.num_features,
            projection_matrix=self.dataset.projection_matrix,
        )
