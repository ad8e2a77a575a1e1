"""GAME coordinates: device-resident training + scoring units.

Reference parity: photon-lib algorithm/Coordinate.scala (updateModel = train
on residual-offset data :61-63), photon-api algorithm/FixedEffectCoordinate
.scala:35-166 and RandomEffectCoordinate.scala:104-200, plus
CoordinateFactory.scala:55-111 (config → coordinate dispatch).

TPU design:
- A FixedEffectCoordinate keeps the shard's dense [N, D] feature block on
  device; training is one jit-compiled L-BFGS/OWLQN/TRON solve with the
  residual scores folded into offsets; scoring is one matmul. Under pjit
  with the batch sharded, gradient reductions become psum (the reference's
  per-iteration treeAggregate + broadcast loop disappears).
- A RandomEffectCoordinate keeps size-bucketed padded entity blocks; training
  is one vmapped solve per bucket (thousands of independent L-BFGS in one
  SPMD program — the reference's per-entity JVM loops); scoring is one
  einsum over the kept rows laid out in sample order at placement (the
  reference's RDD join, done once by the build).
"""
from __future__ import annotations

import collections
import dataclasses
import logging
from functools import partial, wraps

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.game.config import (
    FeatureRepresentation,
    FixedEffectCoordinateConfig,
    MatrixFactorizationCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.data import GameData, RandomEffectDataset
from photon_tpu.game.model import (
    BucketCoefficients,
    FixedEffectModel,
    MatrixFactorizationModel,
    RandomEffectModel,
)
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.glm import model_for_task
from photon_tpu.obs import memory as obs_memory
from photon_tpu.obs.health import sweep_health
from photon_tpu.obs.scopes import scope
from photon_tpu.ops import gather
from photon_tpu.ops.losses import POSITIVE_RESPONSE_THRESHOLD
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.data.dataset import choose_sparse
from photon_tpu.ops.objective import matvec
from photon_tpu.optimize.common import one_solve_a_lane
from photon_tpu.optimize.problem import GLMProblem, GLMProblemConfig
from photon_tpu.types import Array, LabeledBatch, SparseBatch
from photon_tpu.util import dispatch_count
from photon_tpu.util.target import donation_enabled


def _fetch_global(x) -> np.ndarray:
    """Host copy of a possibly process-spanning array (model-export
    boundary; see ``parallel.distributed.fetch_global``)."""
    from photon_tpu.parallel.distributed import fetch_global

    return fetch_global(x)

logger = logging.getLogger(__name__)

#: Per-program TRACE counters: the Python bodies below bump these, and
#: Python side effects run only when jit traces — so a steady-state sweep
#: that retraces shows up as a counter > 1. The fused-sweep dispatch
#: regression test pins them.
TRACE_COUNTERS: collections.Counter = collections.Counter()

#: Device bytes one bucket's vmapped solve may hold in temporaries. A
#: bucket that would need more is solved as a loop over entity chunks
#: inside the same program (``RandomEffectCoordinate._solve_bucket_body``).
#: 2**29: the per-user coordinate of the ``glmix_ctr`` cell (1.9 M
#: single-row entities in one bucket) then runs in 8 chunks (9 until
#: PR 36 priced an entity's rows as vectors) with ~0.4 GB
#: of temporaries where the whole bucket at once asks for 3.5 GB (compiled
#: for a described v5e, PERF.md PR 31), and every bucket the tests and
#: ``chip_smoke.py`` build stays one chunk.
RE_SOLVE_BYTES = 1 << 29

#: a chunk is whole tiles of entities: the compiler lays the entity axis
#: of these arrays on the 128 lanes, 8 sublanes deep
_CHUNK_MULTIPLE = 1024

#: Device bytes a score block's rescoring may hold in temporaries on the
#: PLAIN path: the ``coefs[score_slot]`` gather lays each kept row's d
#: coefficients on the 128 lanes (``rescore_row_bytes``). A block with
#: more rows than fit is rescored as a loop over row chunks inside the same
#: program (``RandomEffectCoordinate._rescore_rows``). 2**32, 4 194 304
#: rows of a 16-wide table. Since PR 40 it governs only the blocks whose
#: table is not fetched packed (``_packs_table``: not a program for a TPU,
#: a mesh, a width that does not divide 128, a packed table past
#: ``ops/gather._PACKED_TABLE_BYTES``): of the benchmark's coordinates the
#: per-user one of ``glmix_ctr.sweeps`` (2**21 + 1 entities, one chunk of
#: 2**22 rows). The packed path cuts its rows by ``gather.segment_plan``
#: and holds ~0.07 GB whatever the block (PERF.md, PR 40).
RE_RESCORE_BYTES = 1 << 32


def solve_entity_bytes(
    rows: int, d: int, optimizer_config, itemsize: int = 4
) -> int:
    """Device bytes one entity of a ``[E, rows, d]`` bucket holds in
    temporaries while its L-BFGS solve runs: six vectors of ``rows`` (the
    margins, the direction's, the trial point's, the loss derivative and
    the loops' second copies of what they carry), the curvature history,
    2 x m vectors of d going into a loop and coming out, and a dozen
    vectors of d (point, gradient, direction, trial point, the line
    search's brackets). The feature block itself is an argument and is
    never copied. Against the compiler's own report (compiled for a
    described v5e: the growth of the sweep program's temporaries per
    entity between the two largest entity counts compiled, past what fast
    memory absorbs; PERF.md PR 36), at d = 16:

    ====================== ============== ===========
    bucket, iterations     compiler       this
    ====================== ============== ===========
    [E, 1, 16], 5          1.9 - 2.6 KB   2.1 KB
    [E, 1, 16], 10         4.1 - 4.9 KB   3.4 KB
    [E, 256, 16], 10       9.2 KB         9.5 KB
    [E, 1024, 16], 10      23.5 KB        27.9 KB
    [E, 4096, 16], 10      93.7 KB        101.6 KB
    ====================== ============== ===========

    Until PR 36 the rows were priced ``2 x rows x (d + 5)``, as if the
    block were carried through the loops: 175 KB and 691 KB an entity at
    1024 and 4096 rows, seven times the compiler's. The history is still
    under-read at ten pairs (the two-loop recursion's loops then carry
    copies of their own); pricing it higher moves the chunk count of
    ``glmix_ctr.sweeps``' one-row bucket and wants a chip reading."""
    m = max(
        1,
        min(optimizer_config.num_corrections, optimizer_config.max_iterations),
    )
    return itemsize * (6 * rows + (4 * m + 12) * d)


def solve_chunk_entities(
    entities: int, rows: int, d: int, optimizer_config, itemsize: int = 4
) -> int:
    """How many of a ``[entities, rows, d]`` bucket's entities one vmapped
    solve takes at a time: all of them where their temporaries fit
    ``RE_SOLVE_BYTES``, else the largest whole number of tiles that do,
    and where less than one tile fits, just the entities that do: a chunk
    is never rounded UP past the budget."""
    fit = RE_SOLVE_BYTES // solve_entity_bytes(
        rows, d, optimizer_config, itemsize
    )
    if fit >= entities:
        return entities
    if fit < _CHUNK_MULTIPLE:
        return max(1, fit)
    return fit // _CHUNK_MULTIPLE * _CHUNK_MULTIPLE


def rescore_row_bytes(d: int, itemsize: int = 4) -> int:
    """Device bytes one kept row holds in temporaries while its block is
    rescored by the PLAIN gather (``_rescore_rows``; the packed fetch
    holds a segment's rows and no more): its entity's d coefficients
    gathered onto the 128 lanes, and the product with the row's features
    before its sum, as wide. The compiler reports 1025 B a row at d = 16
    (a described v5e: 1.076 GB at 2**20 rows, 2.150 GB at 2**21)."""
    return 2 * itemsize * 128 * -(-d // 128)


def rescore_chunk_rows(rows: int, d: int, itemsize: int = 4) -> int:
    """How many of a block's ``rows`` kept rows one step of the PLAIN
    rescoring takes: all of them where their temporaries fit ``RE_RESCORE_BYTES``,
    else the rows split evenly over the fewest chunks that fit, each a
    whole number of tiles."""
    fit = RE_RESCORE_BYTES // rescore_row_bytes(d, itemsize)
    if fit >= rows:
        return rows
    chunks = -(-rows // fit)
    per = -(-rows // chunks)
    return min(fit, -(-per // _CHUNK_MULTIPLE) * _CHUNK_MULTIPLE)


def _make_sweep_jits(body, static_argnums, donate_argnums, name):
    """The fused sweep step compiles as a (donating, non-donating) pair;
    ``Coordinate._active_sweep_jit`` picks per backend. One construction
    site so a future donation quirk (like the XLA:CPU corruption that
    motivated the split) lands in one place. ``name`` names the compiled
    program (HLO module ``jit_<name>``): a device trace tells a fixed
    effect's sweep from a random effect's by it."""

    @wraps(body)
    def named(*args):
        return body(*args)

    named.__name__ = named.__qualname__ = name
    return (
        partial(
            jax.jit, static_argnums=static_argnums,
            donate_argnums=donate_argnums,
        )(named),
        partial(jax.jit, static_argnums=static_argnums)(named),
    )


def _use_sparse(
    representation: FeatureRepresentation, shard, dtype, bf16_features=False
) -> bool:
    if representation == FeatureRepresentation.SPARSE:
        return True
    if representation == FeatureRepresentation.DENSE:
        return False
    # the AUTO threshold tracks the actual dense footprint: bf16 storage
    # halves it
    itemsize = 2 if bf16_features else jnp.dtype(dtype).itemsize
    return choose_sparse(
        shard.num_rows, shard.num_cols, len(shard.values), itemsize=itemsize
    )


class Coordinate:
    """Train/score interface shared by both coordinate kinds."""

    def initial_state(self):
        raise NotImplementedError

    def train(self, residual_scores: Array, state):
        """→ (new_state, OptimizeResult-like info)"""
        raise NotImplementedError

    def score(self, state) -> Array:
        raise NotImplementedError

    def sweep_step(self, total: Array, score: Array, state, donate=None):
        """One coordinate-descent step: residual = total − own score, train
        on it, rescore, fold the new score back into the total.
        ``donate`` pins the buffer-donation choice for the whole descent
        run (descent decides ONCE and threads it through, so the copy
        discipline and the actual donation can never diverge mid-run);
        ``None`` falls back to ``donation_enabled()``.

        → ``(new_state, new_score, new_total, info, health)``, where
        ``health`` is the per-coordinate loss/gnorm/isfinite triple of
        0-d device scalars (photon_tpu/obs/health.py) computed from the
        step's own outputs — inside the fused program on the subclass
        paths (zero extra dispatches; descent reads it back AS the sweep
        barrier), eagerly here. ``None`` where the fold would add
        collectives (entity-sharded RE states under a mesh).

        This base implementation is the UNFUSED reference sequence — the
        same dispatches the descent loop used to issue one by one (kept as
        the fused-vs-unfused parity oracle and profiling A/B). Subclasses
        override it with a single jit-compiled program that donates
        ``total``, ``score``, and ``state``, so the [N] residual/score
        temporaries and the coefficient block reuse their input buffers
        instead of being fresh allocations every step.
        """
        residual = total - score
        new_state, info = self.train(residual, state)
        new_score = self.score(new_state)
        new_total = residual + new_score
        dispatch_count.record(2)  # the two eager elementwise [N] updates
        health = (
            sweep_health(new_state, info) if self.mesh is None else None
        )
        return new_state, new_score, new_total, info, health

    #: (donating, non-donating) fused-step pair, set per subclass via
    #: ``_make_sweep_jits``
    _sweep_jit = None
    _sweep_jit_nodonate = None

    @classmethod
    def _active_sweep_jit(cls, donate=None):
        if donate is None:
            donate = donation_enabled()
        return cls._sweep_jit if donate else cls._sweep_jit_nodonate

    # -- AOT precompile support (descent.precompile_coordinates) --------
    #
    # ``jit(...).lower(...).compile()`` does NOT feed the jit call cache
    # on this jax — an AOT-compiled program is only useful if the hot
    # path actually dispatches it. So precompile stores the Compiled
    # executables here and ``sweep_step``/``score`` consult the cache
    # before falling back to the jit path. Keys: ("sweep", donate_bool)
    # and ("score",). λ rides as a traced argument, so one executable
    # serves the whole regularization grid.

    def aot_executables(self) -> dict:
        cache = getattr(self, "_aot_cache", None)
        if cache is None:
            cache = self._aot_cache = {}
        return cache

    def _aot_call(self, key, *args):
        """Run the precompiled executable for ``key`` on ``args``; None
        when absent. ONLY call-time argument rejections (aval/sharding
        mismatch — TypeError/ValueError raised BEFORE execution, so
        donated buffers survive) drop the executable and fall back to
        the jit path. Anything else (e.g. a mid-execution runtime error
        AFTER donation consumed the inputs) propagates — a fallback
        would re-execute on deleted buffers and mask the real error."""
        exe = self.aot_executables().get(key)
        if exe is None:
            return None
        try:
            return exe(*args)
        except (TypeError, ValueError) as e:
            self.aot_executables().pop(key, None)
            logger.warning(
                "precompiled %s program rejected its inputs (%s: %s); "
                "falling back to the jit path",
                key, type(e).__name__, e,
            )
            return None

    def precompile_specs(
        self, donate=None, include_sweep=True, include_score=True
    ) -> list:
        """(cache_key, label, Lowered) for every hot-path program a fit
        dispatches on this coordinate — the enumeration the parallel
        precompile pass compiles. Lowering happens here (traced once, on
        the calling thread); the expensive backend compile is the
        caller's to schedule."""
        out = []
        if include_sweep:
            d = bool(donate) if donate is not None else donation_enabled()
            out.append((("sweep", d), "sweep", self._sweep_lowered(d)))
        if include_score:
            out.append((("score",), "score", self._score_lowered()))
        return out

    def _sweep_lowered(self, donate: bool):
        raise NotImplementedError

    def _score_lowered(self):
        raise NotImplementedError

    def _row_sds(self, n, template=None):
        """ShapeDtypeStruct of a per-sample [n] vector, carrying the
        template's sharding (an AOT executable is specialized to input
        shardings, so lowering must see the layout the run will use)."""
        sharding = (
            template.sharding if isinstance(template, jax.Array) else None
        )
        return jax.ShapeDtypeStruct((n,), self.dtype, sharding=sharding)

    #: overridden by the mesh-aware subclasses; the base default keeps
    #: mesh-free coordinate kinds (MF) working without a field
    mesh = None

    def _reg_scalar(self, value):
        """λ as a device scalar, CACHED per value: the steady-state sweep
        must not pay (or, under ``PHOTON_SANITIZE=transfers``, trip on) a
        fresh implicit host→device transfer of the same Python float
        every step. λ-grid reweights change the value and simply miss
        the one-entry cache. Off-mesh the array stays uncommitted (plain
        ``jnp.asarray``) so both the AOT executables and the jit path
        accept it unchanged; ON a mesh it is explicitly committed
        replicated — an uncommitted scalar entering a meshed dispatch is
        an implicit device-to-device broadcast EVERY STEP (the sanitizer
        caught exactly this on the first end-to-end meshed fit), and
        ``_scalar_sds`` lowers the AOT programs against the same
        placement so they accept it."""
        cached = getattr(self, "_reg_scalar_cache", None)
        # phl-ok: PHL002 λ is a host config float (the cache key), never a device value
        v = float(value)
        if cached is not None and cached[0] == v:
            return cached[1]
        from photon_tpu.util.sanitize import sanctioned_transfers

        with sanctioned_transfers(
            "per-λ scalar placement — once per reweight, cached for the "
            "steady state"
        ):
            dev = jnp.asarray(value, self.dtype)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                dev = jax.device_put(dev, NamedSharding(self.mesh, P()))
        self._reg_scalar_cache = (v, dev)
        return dev

    def _scalar_sds(self):
        """ShapeDtypeStruct of a replicated 0-d scalar argument (λ),
        carrying the mesh placement ``_reg_scalar`` commits to so the
        AOT executables lower against the layout the run will use."""
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(self.mesh, P())
        return jax.ShapeDtypeStruct((), self.dtype, sharding=sharding)

    def spmd_contract(self):
        """Declared SPMD contract (photon_tpu/analysis/spmd.py) for this
        coordinate's hot-path programs — what the program auditor holds
        every AOT executable to. The base default is the strictest one:
        single-device, collective-free, no sharding claims. Mesh-aware
        subclasses declare their allowances (FE: bounded d-vector
        all-reduces; RE: collective-free WITH entity-sharded tables)."""
        from photon_tpu.analysis import spmd

        return spmd.SpmdContract()

    def place_state(self, state):
        """Re-place a host/single-device state onto this coordinate's
        DECLARED sharding (the layout ``initial_state`` and the state
        ShapeDtypeStructs pin). Checkpoint resume and warm starts load
        plain host arrays; handing them to the first meshed sweep as-is
        would be an implicit reshard at dispatch (a transfer the
        sanitizer flags) AND would reject the AOT executable on input
        shardings — so the estimator routes every loaded state through
        here. No-op off-mesh; subclasses override the mesh path."""
        return state

    def to_model(self, state):
        raise NotImplementedError


@dataclasses.dataclass(eq=False)
class FixedEffectCoordinate(Coordinate):
    config: FixedEffectCoordinateConfig
    feature_shard: str
    batch: LabeledBatch | SparseBatch  # device, offsets = raw data offsets
    normalization: NormalizationContext
    problem: GLMProblem
    dtype: object
    num_features: int
    #: set when the batch rows are sharded over a device mesh — the fused
    #: sweep step then pins its [N] residual/total chain to the row
    #: sharding (parallel/mesh.constrain_rows)
    mesh: object = None

    @staticmethod
    def build(
        data: GameData,
        config: FixedEffectCoordinateConfig,
        normalization: NormalizationContext = NormalizationContext(),
        dtype=jnp.float32,
        seed: int = 0,
        mesh=None,
    ) -> "FixedEffectCoordinate":
        shard = data.feature_shards[config.feature_shard]
        weights = data.weights
        rate = config.optimization.down_sampling_rate
        if 0.0 < rate < 1.0:
            # Mask-based down-sampling: rows keep their slot (static shapes
            # for XLA) but dropped rows get weight 0 (reference
            # runWithSampling:145-160 drops RDD rows instead). For
            # classification only negatives are sampled, survivors
            # re-weighted by 1/rate so expected gradients are unchanged.
            rng = np.random.default_rng(seed)
            keep_draw = rng.uniform(size=data.num_samples) < rate
            weights = np.asarray(weights, dtype=np.float64).copy()
            if config.optimization.task.is_classification:
                neg = data.labels <= POSITIVE_RESPONSE_THRESHOLD
                weights[neg & ~keep_draw] = 0.0
                weights[neg & keep_draw] /= rate
            else:
                weights[~keep_draw] = 0.0
        # numpy handles bfloat16 via ml_dtypes, so one host-side conversion
        # covers every supported dtype
        if _use_sparse(
            config.representation, shard, dtype, config.bf16_features
        ):
            # bf16 value storage halves the dominant HBM stream (indices
            # stay int32); products/accumulation promote to f32 on read,
            # matching the dense bf16 path's f32-accumulation contract
            ell_dtype = jnp.bfloat16 if config.bf16_features else dtype
            ell_idx, ell_val = shard.to_ell(dtype=np.dtype(ell_dtype))
            from photon_tpu.ops.sparse_windows import maybe_build_windows

            batch = SparseBatch(
                indices=ell_idx,
                values=ell_val,
                labels=np.asarray(data.labels, dtype=dtype),
                offsets=np.asarray(data.offsets, dtype=dtype),
                weights=np.asarray(weights, dtype=dtype),
                windows=maybe_build_windows(
                    ell_idx, ell_val, shard.num_cols,
                    host=mesh is not None,
                ),
            )
        else:
            feat_dtype = jnp.bfloat16 if config.bf16_features else dtype
            batch = LabeledBatch(
                features=shard.to_dense(dtype=feat_dtype),
                labels=np.asarray(data.labels, dtype=dtype),
                offsets=np.asarray(data.offsets, dtype=dtype),
                weights=np.asarray(weights, dtype=dtype),
            )
        if mesh is not None:
            from photon_tpu.parallel.mesh import shard_batch

            # Rows over every mesh device; in-jit gradient reductions become
            # psum over ICI (the reference's treeAggregate, SURVEY §5.8).
            # device_put straight from host numpy so no single device ever
            # holds the whole [N, D] block. Column windows shard EXPLICITLY
            # on the instance axis (shard_batch drops them — GSPMD cannot
            # partition the windowed pass's loop); the objective then runs
            # the shard_map reduction in parallel/sparse.py.
            windows = getattr(batch, "windows", None)
            batch = shard_batch(batch, mesh)
            if windows is not None:
                from photon_tpu.parallel.sparse import shard_windows

                batch = batch._replace(
                    windows=shard_windows(windows, mesh, shard.num_cols)
                )
        else:
            # preserve integer leaves (sparse ELL indices) and an explicit
            # bfloat16 feature block as-is; leaves already on device (the
            # ColumnWindows layout) must NOT round-trip through host numpy
            def _to_device(x):
                if isinstance(x, jax.Array):
                    return x
                a = np.asarray(x)
                if np.issubdtype(a.dtype, np.integer) or a.dtype == jnp.bfloat16:
                    return jnp.asarray(a)
                return jnp.asarray(a, dtype=dtype)

            batch = jax.tree_util.tree_map(_to_device, batch)
        # placement choke point: the batch block is the coordinate's H2D
        # bill (ledger no-op unless obs + PHOTON_OBS_MEM are live)
        obs_memory.count_h2d(obs_memory.tree_device_bytes(batch))
        problem = GLMProblem.build(
            config.optimization.with_regularization_weight(
                config.regularization_weights[0]
            ),
            normalization,
            mesh=mesh if getattr(batch, "windows", None) is not None else None,
        )
        return FixedEffectCoordinate(
            config=config,
            feature_shard=config.feature_shard,
            batch=batch,
            normalization=normalization,
            problem=problem,
            dtype=dtype,
            num_features=shard.num_cols,
            mesh=mesh,
        )

    def with_regularization_weight(self, w: float) -> "FixedEffectCoordinate":
        """λ-grid reweighting IN PLACE: the jit cache for ``_train_jit`` is
        keyed on this object's identity (static self), and λ enters the
        compiled program as a traced scalar — so a 5-point grid compiles the
        train program exactly once (reference mutable reg weight,
        DistributedOptimizationProblem.scala:62-73; VERDICT r1 weak #3)."""
        self.problem = GLMProblem.build(
            self.config.optimization.with_regularization_weight(w),
            self.normalization,
            mesh=self.problem.objective.mesh,  # keep the sharded backward
        )
        return self

    def initial_state(self) -> Array:
        z = jnp.zeros((self.num_features,), dtype=self.dtype)
        # place replicated ON THE MESH (the layout _state_sds declares):
        # a single-device zeros state would be implicitly resharded at
        # the first sweep dispatch (a transfer the sanitizer flags) and
        # would reject the AOT sweep executable's input shardings
        return self.place_state(z)

    def place_state(self, state: Array) -> Array:
        if self.mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            jnp.asarray(state, dtype=self.dtype),
            NamedSharding(self.mesh, P()),
        )

    def _norm_args(self) -> tuple:
        """Normalization factors/shifts as TRACED jit arguments. Reading
        them through static self would lower the length-D device arrays as
        HLO literal constants (~4-8 MB per program at d=2²⁰) — the same
        constant-embedding class the batch-as-argument rule exists for."""
        return (self.normalization.factors, self.normalization.shifts)

    def _norm_ctx(self, norm_args) -> NormalizationContext:
        """NormalizationContext over the traced arrays (pytree structure —
        which of factors/shifts is None — stays static, so jit control flow
        is unchanged). Single reconstruction point for train AND score: the
        two paths must never drift back onto static self arrays."""
        factors, shifts = norm_args
        if factors is None and shifts is None:
            return self.normalization
        return dataclasses.replace(
            self.normalization, factors=factors, shifts=shifts
        )

    def _traced_problem(self, norm_args) -> GLMProblem:
        ctx = self._norm_ctx(norm_args)
        if ctx is self.normalization:
            return self.problem
        return dataclasses.replace(
            self.problem,
            objective=dataclasses.replace(
                self.problem.objective, normalization=ctx
            ),
        )

    @partial(jax.jit, static_argnums=0)
    def _train_jit(
        self, batch, norm_args, residual_scores: Array, w0: Array,
        reg_weight: Array,
    ):
        # NOTE: only structural attrs of (static) self may be read here —
        # anything λ-dependent must arrive as a traced argument, or a later
        # in-place reweight would silently reuse the stale traced value.
        # The batch AND the normalization arrays ride as ARGUMENTS, never
        # through static self: a trace-time constant lowers as HLO
        # literals: a multi-hundred-MB module body that the compiler has
        # to parse, hash for the cache and keep, once per program.
        res = self._traced_problem(norm_args).solve(
            batch, w0, reg_weight, extra_offsets=residual_scores
        )
        return res

    def train(self, residual_scores: Array, state: Array):
        dispatch_count.record(1)
        res = self._train_jit(
            self.batch,
            self._norm_args(),
            residual_scores,
            state,
            self._reg_scalar(self.problem.config.regularization_weight),
        )
        return res.x, res

    def _score_body(
        self, batch, norm_args, state: Array, product: Array | None = None
    ) -> Array:
        """The score at ``state``; over ``product`` (the feature product at
        that point, ``GLMObjective.product``) where the caller holds it."""
        ctx = self._norm_ctx(norm_args)
        if product is None:
            product = matvec(batch, ctx.effective_coefficients(state))
        s = product
        if ctx.shifts is not None:
            s = s + ctx.margin_shift(state)
        return s

    @partial(jax.jit, static_argnums=0)
    def _score_jit(self, batch, norm_args, state: Array) -> Array:
        return self._score_body(batch, norm_args, state)

    def score(self, state: Array) -> Array:
        """x·(w .* factor) + margin shift — the coordinate's contribution,
        exclusive of data offsets (FixedEffectCoordinate.score:158-166)."""
        dispatch_count.record(1)
        out = self._aot_call(("score",), self.batch, self._norm_args(), state)
        if out is not None:
            return out
        return self._score_jit(self.batch, self._norm_args(), state)

    def _sweep_body(
        self, batch, norm_args, total, score, state, reg_weight
    ):
        """Whole CD step as ONE program: residual = total − score, solve on
        the residual offsets, rescore, total update. Compiled as
        ``_sweep_jit`` (total/score/state DONATED — the [N] temporaries
        and the coefficient block reuse their input buffers every
        steady-state step; the solve's history buffers remain its own) and
        ``_sweep_jit_nodonate`` (XLA:CPU — see util/target.donation_enabled)."""
        TRACE_COUNTERS["fe_sweep"] += 1
        from photon_tpu.parallel.mesh import constrain_rows

        with scope("photon.descent.residual"):
            residual = constrain_rows(total - score, self.mesh)
        res = self._traced_problem(norm_args).solve_keeping_product(
            batch, state, reg_weight, extra_offsets=residual
        )
        with scope("photon.descent.rescore"):
            # the solve's last exact evaluation made X·x one line earlier:
            # the score takes that product and reads the block no more
            new_score = self._score_body(
                batch, norm_args, res.x, product=res.product
            )
            new_total = constrain_rows(residual + new_score, self.mesh)
        res = res._replace(product=None)  # an [n] array: not the row's to keep
        # health scalars fold into THIS program (coefficients and the
        # solve outputs are replicated under a mesh, so the reductions
        # stay collective-free); descent reads them back as the barrier
        return res.x, new_score, new_total, res, sweep_health(res.x, res)

    _sweep_jit, _sweep_jit_nodonate = _make_sweep_jits(
        _sweep_body, static_argnums=0, donate_argnums=(3, 4, 5),
        name="fe_sweep",
    )

    def _state_sds(self):
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(self.mesh, P())  # coefficients replicate
        return jax.ShapeDtypeStruct(
            (self.num_features,), self.dtype, sharding=sharding
        )

    def _sweep_lowered(self, donate: bool):
        n = self.batch.labels.shape[0]
        row = self._row_sds(n, self.batch.labels)
        return self._active_sweep_jit(donate).lower(
            self, self.batch, self._norm_args(), row, row,
            self._state_sds(), self._scalar_sds(),
        )

    def _score_lowered(self):
        # class-attribute access: the UNBOUND jit function (self rides as
        # the explicit static arg, like the sweep pair)
        return type(self)._score_jit.lower(
            self, self.batch, self._norm_args(), self._state_sds()
        )

    def sweep_step(self, total: Array, score: Array, state: Array,
                   donate=None):
        dispatch_count.record(1)
        args = (
            self.batch,
            self._norm_args(),
            total,
            score,
            state,
            self._reg_scalar(self.problem.config.regularization_weight),
        )
        d = bool(donate) if donate is not None else donation_enabled()
        out = self._aot_call(("sweep", d), *args)
        if out is not None:
            return out
        return self._active_sweep_jit(d)(self, *args)

    def spmd_contract(self):
        """Fixed-effect programs on a mesh MAY reduce — the sharded
        matvec/solve psums ONE d-vector gradient (plus scalar loss /
        convergence reductions) per L-BFGS iteration, the distributed-
        matvec pattern of "Large Scale Distributed Linear Algebra With
        TPUs" (PAPERS.md). The allowance prices exactly that; anything
        bigger (an accidental per-row gather-back, a replicated batch) is
        a regression. Off-mesh programs stay collective-free."""
        from photon_tpu.analysis import spmd

        if self.mesh is None:
            return spmd.SpmdContract()
        itemsize = int(jnp.dtype(self.dtype).itemsize)
        d_vec = (self.num_features + 16) * itemsize
        return spmd.SpmdContract(
            comm=spmd.CommAllowance(
                ops=("all-reduce",),
                max_bytes_per_site=d_vec,
                reason=(
                    "FE sharded solve: one d-vector gradient reduce "
                    "(+ scalar loss/convergence reduces) per iteration"
                ),
            ),
            sharding=spmd.ShardingContract(
                on_mesh=True,
                # legitimately replicated: the [D] coefficient state and
                # normalization vectors; the [N,*] batch must not be
                replicated_bytes_limit=2 * d_vec,
                partitioned_params=True,
                partitioned_results=True,
            ),
        )

    def to_model(self, state: Array) -> FixedEffectModel:
        w = self.normalization.model_to_original_space(state)
        variances = self.problem.variances(self.batch, state)
        glm = model_for_task(
            self.config.optimization.task,
            Coefficients(
                means=w,
                variances=None if variances is None else jnp.asarray(variances),
            ),
        )
        return FixedEffectModel(model=glm, feature_shard=self.feature_shard)


@dataclasses.dataclass(eq=False)
class _DeviceBucket:
    features: Array  # [E, n_act, d] ACTIVE rows only
    labels: Array
    offsets: Array
    train_weights: Array  # data weights of active rows (0 on padding)
    sample_pos: Array  # [E, n_act] int32, ≥ num_samples ⇒ padding (gather
    #   clamps to the residual's zero sentinel — never scattered)
    score_feats: Array  # the kept rows its scores are made from: the
    #   ``_ScoreBlock.feats`` of its width, shared by every bucket of it
    entity_ids: np.ndarray
    col_index: np.ndarray


@dataclasses.dataclass(eq=False)
class _ScoreBlock:
    """The kept rows (active AND passive) of every bucket of one width,
    merged at placement into ascending sample position: what a sweep
    dots with the entities' coefficients is already where its sums go.

    ``pos`` is ``None`` where the block IS sample order: row i is sample
    i, and a sample no bucket keeps is a zero row. Otherwise the block
    holds only the samples its buckets keep and ``pos`` their ascending,
    distinct positions (those past ``num_samples`` are the zero rows that
    pad the block to the mesh: the scatter drops them)."""

    feats: Array  # [M, d]
    slot: Array  # [M] row of the concatenated tables of ``buckets``; the
    #   row past their end is the zero row the program appends
    pos: Array | None  # [M] ascending sample positions
    buckets: tuple  # whose coefficient tables the slots count through


def _merge_score_rows(host_buckets, table_rows, n: int, row_multiple: int):
    """The host buckets' flat score rows as one block per bucket WIDTH in
    ascending sample position: yields (feats, slot, pos | None, members).

    ``table_rows[i]`` is the height of bucket i's placed coefficient
    table; a row's slot counts through the tables of its width's buckets
    laid end to end, and the row after the last table is a zero row: a
    sample that no bucket keeps points there with zero features, so a
    diverged entity's Inf never meets its 0. A kept sample lies in one
    bucket, so the rows are written to their positions and nothing is
    sorted. Where every bucket has the same width the block is the whole
    of sample order (``pos`` None, M = n up to the mesh's padding); with
    several widths each block keeps the samples it covers alone."""
    widths: dict[int, list[int]] = {}
    for i, b in enumerate(host_buckets):
        widths.setdefault(b.score_feats.shape[1], []).append(i)
    for d, members in widths.items():
        zero_row = sum(table_rows[i] for i in members)
        feats = np.zeros((n, d), host_buckets[members[0]].score_feats.dtype)
        slot = np.full(n, zero_row, np.int32)
        base = 0
        for i in members:
            b = host_buckets[i]
            feats[b.score_pos] = b.score_feats
            slot[b.score_pos] = b.score_slot + base
            base += table_rows[i]
        pos = None
        if len(widths) > 1:
            pos = np.flatnonzero(slot != zero_row).astype(np.int32)
            feats, slot = feats[pos], slot[pos]
        pad = -len(slot) % row_multiple
        if pad:
            feats = np.pad(feats, [(0, pad), (0, 0)])
            slot = np.pad(slot, (0, pad), constant_values=zero_row)
            if pos is not None:
                pos = np.concatenate([pos, n + np.arange(pad, dtype=np.int32)])
        yield feats, slot, pos, tuple(members)


@dataclasses.dataclass(eq=False)
class RandomEffectCoordinate(Coordinate):
    config: RandomEffectCoordinateConfig
    dataset: RandomEffectDataset
    device_buckets: list
    problem_config: GLMProblemConfig
    num_samples: int
    dtype: object
    #: set when the coordinate's blocks are entity-sharded over a mesh —
    #: training then runs as shard_map with per-shard independent
    #: while-loops (zero collectives; see _train_bucket)
    mesh: object = None
    #: the kept rows merged per bucket width into sample order at
    #: placement (``_merge_score_rows``): what the rescoring reads
    score_blocks: list = dataclasses.field(default_factory=list)

    @staticmethod
    def build(
        data: GameData,
        dataset: RandomEffectDataset,
        config: RandomEffectCoordinateConfig,
        dtype=jnp.float32,
        mesh=None,
    ) -> "RandomEffectCoordinate":
        entity_shards = 1
        mesh_devices = 1
        put_entities = lambda x: x  # noqa: E731
        put_rows = lambda x: x  # noqa: E731
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from photon_tpu.parallel.mesh import (
                ENTITY_AXIS,
                pad_rows_to_multiple,
                shard_entities,
            )

            entity_shards = mesh.shape[ENTITY_AXIS]
            mesh_devices = mesh.size
            put_entities = lambda x: shard_entities(x, mesh)  # noqa: E731
            axes = tuple(mesh.axis_names)

            def put_rows(x):  # noqa: F811
                p = P(axes, *([None] * (x.ndim - 1)))
                return jax.device_put(x, NamedSharding(mesh, p))

        n_total = dataset.num_samples
        # placement wrapped against a transient UNAVAILABLE: one flaky
        # put must not kill a multi-minute coordinate build. The fault
        # point sits INSIDE the retried thunk, so an injected UNAVAILABLE
        # exercises the real retry path (util/faults.py; each retry
        # re-counts the occurrence)
        from photon_tpu.util import faults
        from photon_tpu.util.device_retry import put_with_retry

        # The flat score rows first: every bucket holds its width's block.
        # They are padded to divide the WHOLE mesh (they shard over every
        # device like the fixed-effect batch); the entity axis of a table
        # is padded below, so a slot counts through the PLACED heights.
        e_pads = [
            0
            if entity_shards == 1
            else pad_rows_to_multiple(b.num_entities, entity_shards)
            - b.num_entities
            for b in dataset.buckets
        ]
        score_blocks = [
            put_with_retry(
                lambda feats=feats, slot=slot, pos=pos, members=members: (
                    _ScoreBlock(
                        feats=put_rows(jnp.asarray(feats, dtype=dtype)),
                        slot=put_rows(jnp.asarray(slot)),
                        pos=None
                        if pos is None
                        else put_rows(jnp.asarray(pos)),
                        buckets=members,
                    )
                )
            )
            for feats, slot, pos, members in _merge_score_rows(
                dataset.buckets,
                [b.num_entities + p for b, p in zip(dataset.buckets, e_pads)],
                n_total,
                mesh_devices,
            )
        ]
        block_of = {
            i: blk for blk in score_blocks for i in blk.buckets
        }
        device_buckets = []
        for i, (b, e_pad) in enumerate(zip(dataset.buckets, e_pads)):
            # Pad the entity axis so it divides the mesh's entity dimension;
            # padded lanes carry zero weights and the OOB sample slot, so
            # they train to zero instantly.
            def pad_e(x, fill=0, e_pad=e_pad):
                if e_pad == 0:
                    return x
                widths = [(0, e_pad)] + [(0, 0)] * (x.ndim - 1)
                return np.pad(x, widths, constant_values=fill)

            device_buckets.append(
                put_with_retry(
                    lambda b=b, pad_e=pad_e, i=i: (
                        faults.fault_point("coordinate.placement"),
                        _DeviceBucket(
                            features=put_entities(
                                jnp.asarray(pad_e(b.features), dtype=dtype)
                            ),
                            labels=put_entities(
                                jnp.asarray(pad_e(b.labels), dtype=dtype)
                            ),
                            offsets=put_entities(
                                jnp.asarray(pad_e(b.offsets), dtype=dtype)
                            ),
                            # blocks hold active rows only, where
                            # active_mask ≡ 1 — the data weights ARE the
                            # train weights (0 on padding rows)
                            train_weights=put_entities(
                                jnp.asarray(pad_e(b.weights), dtype=dtype)
                            ),
                            sample_pos=put_entities(
                                jnp.asarray(
                                    pad_e(b.sample_pos, fill=n_total)
                                )
                            ),
                            score_feats=block_of[i].feats,
                            entity_ids=b.entity_ids,
                            col_index=b.col_index,
                        ),
                    )[1]
                )
            )
        # placement choke point: every bucket's device-resident blocks
        # and the coordinate's score blocks
        obs_memory.count_h2d(
            obs_memory.tree_device_bytes(
                (
                    [
                        (
                            db.features, db.labels, db.offsets,
                            db.train_weights, db.sample_pos,
                        )
                        for db in device_buckets
                    ],
                    [(blk.feats, blk.slot, blk.pos) for blk in score_blocks],
                )
            )
        )
        return RandomEffectCoordinate(
            config=config,
            dataset=dataset,
            device_buckets=device_buckets,
            problem_config=config.optimization.with_regularization_weight(
                config.regularization_weights[0]
            ),
            num_samples=dataset.num_samples,
            dtype=dtype,
            mesh=mesh,
            score_blocks=score_blocks,
        )

    @property
    def score_layout(self) -> str:
        """How the new scores reach sample order, decided by the build
        from the buckets' widths: ``sample_order`` (one width: the block
        is sample order and its sums are the score) or ``sorted_scatter``
        (a block a width, each added at its ascending positions)."""
        if all(blk.pos is None for blk in self.score_blocks):
            return "sample_order"
        return "sorted_scatter"

    def _block_tables(self) -> list[tuple[int, int]]:
        """(rows, width) of every score block's coefficient table: its
        buckets' placed tables laid end to end, and the zero row."""
        return [
            (
                sum(self.device_buckets[i].features.shape[0] for i in blk.buckets)
                + 1,
                blk.feats.shape[1],
            )
            for blk in self.score_blocks
        ]

    @property
    def table_fetch(self) -> str:
        """How a rescored row gets its entity's coefficients in a program
        traced now (``_packs_table``): ``packed_rows`` (the 128-lane row
        fetch from the packed table, every block), ``plain``
        (``coefs[slot]``, every block), ``mixed`` across widths."""
        packs = {self._packs_table(e, d) for e, d in self._block_tables()}
        if packs == {True}:
            return "packed_rows"
        return "mixed" if True in packs else "plain"

    @property
    def packed_table_bytes(self) -> int:
        """Bytes of the blocks' coefficient tables packed ``128 // d``
        entities to a lane row, whichever fetch they get: what
        ``table_fetch`` was decided on (0 for a width with no such view)."""
        itemsize = jnp.dtype(self.dtype).itemsize
        return sum(
            gather.packed_table_bytes(e, d, itemsize)
            for e, d in self._block_tables()
        )

    def with_regularization_weight(self, w: float) -> "RandomEffectCoordinate":
        """In-place λ reweight — see FixedEffectCoordinate: keeps the per-
        bucket compiled programs (static self) valid across the λ grid."""
        self.problem_config = self.config.optimization.with_regularization_weight(w)
        return self

    def initial_state(self) -> list[Array]:
        # entity-sharded like the live buckets and the state sds —
        # single-device zeros would be implicitly resharded at the
        # first sweep dispatch and reject the AOT executable
        return self.place_state(
            [
                jnp.zeros(
                    (b.features.shape[0], b.features.shape[2]),
                    dtype=self.dtype,
                )
                for b in self.device_buckets
            ]
        )

    def place_state(self, state: list[Array]) -> list[Array]:
        if self.mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_tpu.parallel.mesh import ENTITY_AXIS

        sh = NamedSharding(self.mesh, P(ENTITY_AXIS, None))
        return [
            jax.device_put(jnp.asarray(w, dtype=self.dtype), sh)
            for w in state
        ]

    def _solve_bucket(
        self,
        features: Array,
        labels: Array,
        offsets: Array,
        train_weights: Array,
        sample_pos: Array,
        w0: Array,
        res_pad: Array,
        reg_weight: Array,
    ):
        """One vmapped solve over all entities of one size bucket (traced
        body shared by the legacy per-bucket jit and the fused multi-bucket
        programs). ``res_pad`` is the residual with its zero sentinel
        already appended — the fused programs build it ONCE per sweep
        instead of once per bucket.

        Under a mesh the solve runs as ``shard_map`` over the entity axis
        with PER-SHARD INDEPENDENT while-loops: per-entity solves share
        nothing, so the plain GSPMD lowering's only collective — the
        vmapped while-loop's cross-device ``any(continue)`` reduce, one
        all-reduce per optimizer iteration — is pure overhead. On real
        chips that is an ICI sync per iteration for no information; on
        the virtual CPU mesh it is fatal (XLA:CPU's in-process rendezvous
        hard-aborts at 40 s when 8 device threads time-slice one core —
        observed at the 10⁹-coefficient north star). Per-lane numerics
        are loop-length independent (the while-loop batching rule freezes
        converged lanes), asserted by the sharded==unsharded parity
        tests.
        """
        with scope("photon.re.solve"):
            return self._solve_bucket_body(
                features, labels, offsets, train_weights, sample_pos, w0,
                res_pad, reg_weight,
            )

    def _solve_bucket_body(
        self, features, labels, offsets, train_weights, sample_pos, w0,
        res_pad, reg_weight,
    ):
        problem = GLMProblem.build(self.problem_config)
        n_res = res_pad.shape[0] - 1
        # Residual fold OUTSIDE the unchecked region (VERDICT r5 weak #2):
        # a gather of the replicated residual by shard-varying sample
        # positions plus an elementwise add partitions fine under plain
        # GSPMD, so it stays where the compiler's own checks apply.
        with scope("photon.re.fetch"):
            extra = res_pad[jnp.minimum(sample_pos, n_res)]
        offsets_eff = offsets + extra

        def solve_all(features, labels, offsets_eff, train_weights, w0,
                      reg_weight):
            def solve_one(f, l, o, w, w0_e):
                batch = LabeledBatch(
                    features=f, labels=l, offsets=o, weights=w
                )
                return problem.solve(batch, w0_e, reg_weight)

            with one_solve_a_lane():
                return jax.vmap(solve_one)(
                    features, labels, offsets_eff, train_weights, w0
                )

        def vmapped_solve(features, labels, offsets_eff, train_weights,
                          w0, reg_weight):
            """The bucket's solves, ``chunk`` entities at a time where the
            whole bucket's temporaries would pass ``RE_SOLVE_BYTES``: one
            loop inside the program, its buffers reused from chunk to
            chunk. The last chunk is moved back to end on the last entity,
            so every chunk has one shape and the entities it shares with
            the chunk before are solved twice, to the same result: a
            lane's arithmetic does not depend on the lanes beside it (the
            while-loop batching rule freezes a lane that has stopped)."""
            lanes = (features, labels, offsets_eff, train_weights, w0)
            e = features.shape[0]
            chunk = solve_chunk_entities(
                e, features.shape[1], features.shape[2],
                self.problem_config.optimizer_config,
                jnp.dtype(features.dtype).itemsize,
            )
            if chunk >= e:
                return solve_all(*lanes, reg_weight)

            def one_chunk(i, out):
                start = jnp.minimum(i * chunk, e - chunk)
                with scope("photon.re.chunk"):
                    res = solve_all(
                        *(
                            jax.lax.dynamic_slice_in_dim(a, start, chunk, 0)
                            for a in lanes
                        ),
                        reg_weight,
                    )
                return jax.tree_util.tree_map(
                    lambda o, r: jax.lax.dynamic_update_slice_in_dim(
                        o, r, start, 0
                    ),
                    out, res,
                )

            shapes = jax.eval_shape(
                solve_all, *(a[:chunk] for a in lanes), reg_weight
            )
            out = jax.tree_util.tree_map(
                lambda sd: jnp.zeros((e,) + sd.shape[1:], sd.dtype), shapes
            )
            return jax.lax.fori_loop(0, -(-e // chunk), one_chunk, out)

        if self.mesh is None:
            return vmapped_solve(
                features, labels, offsets_eff, train_weights, w0, reg_weight
            )
        from jax.sharding import PartitionSpec as P

        from photon_tpu.parallel.mesh import ENTITY_AXIS, shard_map_unchecked

        ent = P(ENTITY_AXIS)  # leading axis entity-sharded, rest replicated
        rep = P()  # λ is replicated on every shard
        # the unchecked region is EXACTLY the vmapped while-loop solve —
        # the smallest sub-function the checker mis-handles (this jax has
        # no replication rule for `while`, and the optimizer carries mix
        # shard-varying state with constant-initialized history buffers);
        # test_re_train_program_has_no_collectives is the real contract
        return shard_map_unchecked(
            vmapped_solve,
            mesh=self.mesh,
            in_specs=(ent, ent, ent, ent, ent, rep),
            out_specs=ent,  # every OptimizeResult leaf is per-lane [E, ...]
        )(features, labels, offsets_eff, train_weights, w0, reg_weight)

    @partial(jax.jit, static_argnums=(0,))
    def _train_bucket(
        self,
        features: Array,
        labels: Array,
        offsets: Array,
        train_weights: Array,
        residual: Array,
        sample_pos: Array,
        w0: Array,
        reg_weight: Array,
    ):
        """Legacy single-bucket entry (kept for the no-collectives and
        no-const-embedding contracts and ad-hoc probing); the descent hot
        path dispatches all buckets as one program (`_train_all_jit` /
        `_sweep_jit`)."""
        res_pad = jnp.concatenate([residual, jnp.zeros((1,), residual.dtype)])
        return self._solve_bucket(
            features, labels, offsets, train_weights, sample_pos, w0,
            res_pad, reg_weight,
        )

    def _train_args(self) -> tuple:
        return tuple(
            (db.features, db.labels, db.offsets, db.train_weights,
             db.sample_pos)
            for db in self.device_buckets
        )

    @partial(jax.jit, static_argnums=0)
    def _train_all_jit(self, bucket_args, residual, state, reg_weight):
        """All size buckets in ONE compiled program (buckets ride as pytree
        leaves). The per-bucket vmapped solves are independent, so the
        fusion is free parallelism for XLA — and one dispatch replaces the
        former one-jit-call-per-bucket serial chain. λ stays traced: the
        whole λ grid reuses this single program per coordinate."""
        TRACE_COUNTERS["re_train_all"] += 1
        res_pad = jnp.concatenate([residual, jnp.zeros((1,), residual.dtype)])
        infos = [
            self._solve_bucket(f, l, o, tw, sp, w0, res_pad, reg_weight)
            for (f, l, o, tw, sp), w0 in zip(bucket_args, state)
        ]
        return [r.x for r in infos], infos

    def train(self, residual_scores: Array, state: list[Array]):
        dispatch_count.record(1)
        reg_w = self._reg_scalar(self.problem_config.regularization_weight)
        return self._train_all_jit(
            self._train_args(), residual_scores, state, reg_w
        )

    def _score_blocks_body(self, score_args, state, plan) -> Array:
        """[n]: the coordinate's score at ``state``, one einsum a score
        block: each kept row (active AND passive) dotted with its entity's
        row of its width's tables laid end to end, a zero row after them
        for the samples no bucket keeps. The build put the rows in sample
        order, so where the buckets share one width the sums ARE the
        score: nothing is zero-filled, sorted, scattered or added. With
        several widths each block's sums are added at its own ascending,
        distinct positions (a scatter the compiler need not sort; it
        drops the positions past ``num_samples``, the block's padding to
        the mesh). A kept sample lies in one block and ``x + 0`` is exact:
        every score is the per-bucket scatter's, bit for bit. Weight-0
        rows were zeroed at build, so no mask here."""
        total = None
        for (feats, slot, *pos), members in zip(score_args, plan):
            with scope("photon.re.rescore"):
                tables = [state[i] for i in members]
                if self.mesh is not None:
                    # every device's rows may name any entity: the tables
                    # are gathered whole, each by its own all-gather, and
                    # laid end to end on the device (left to GSPMD, the
                    # concatenation of entity-sharded tables is a chain
                    # of all-to-alls)
                    from jax.sharding import NamedSharding, PartitionSpec as P

                    whole = NamedSharding(self.mesh, P())
                    tables = [
                        jax.lax.with_sharding_constraint(t, whole)
                        for t in tables
                    ]
                table = jnp.concatenate(
                    tables + [jnp.zeros((1, feats.shape[1]), tables[0].dtype)]
                )
                s = self._rescore_rows(feats, slot, table)
                if pos:
                    part = jnp.zeros((self.num_samples,), s.dtype).at[
                        pos[0]
                    ].add(s, indices_are_sorted=True, unique_indices=True)
                else:
                    part = s[: self.num_samples]
            total = part if total is None else total + part
        if total is None:  # a coordinate without a bucket
            return jnp.zeros((self.num_samples,), dtype=self.dtype)
        return total

    def _packs_table(self, entities: int, d: int) -> bool:
        """Whether a score block's rows fetch their coefficients from the
        packed table (``ops/gather.packs_table``: a program for a TPU, a
        width that divides 128, a packed table that stays in fast memory).
        Never on a mesh: there every device scores its share at once."""
        return self.mesh is None and gather.packs_table(
            entities, d, jnp.dtype(self.dtype).itemsize
        )

    def _rescore_rows(self, score_feats, score_slot, coefs) -> Array:
        """[M]: every row of a score block dotted with its entity's
        coefficients, by one of two fetches (``_packs_table``).

        Packed: the table is viewed ``128 // d`` entities to a lane row,
        and the rows go through ``gather.map_segments`` in the segments of
        ``gather.segment_plan``, as a forward sparse pass cuts its rows: a
        segment fetches its entities' lane rows, keeps each row's d lanes
        (``gather.fetch_select_rows``) and dots them with the features.
        The coefficients are ``coefs[slot]`` bit for bit and the einsum is
        the plain path's. ``RE_RESCORE_BYTES`` decides nothing here.

        Plain: ``coefs[slot]``, ``rescore_chunk_rows`` rows at a time
        where the whole block's gather would pass ``RE_RESCORE_BYTES``:
        one loop inside the program, its buffers reused from chunk to
        chunk. The last chunk is moved back to end on the last row, as the
        solves' last chunk is: the rows it shares with the chunk before
        are scored twice, to the same numbers (a row's sum does not depend
        on the rows beside it). On a mesh the kept rows are already split
        over the devices and every device scores its share at once."""
        m, d = score_feats.shape
        packed = self._packs_table(coefs.shape[0], d)
        t2 = gather.pack_table(coefs) if packed else None

        def rows_of(feats, slot):
            c = gather.fetch_select_rows(t2, slot, d) if packed else coefs[slot]
            return jnp.einsum("md,md->m", feats, c.astype(feats.dtype))

        if packed:
            # four lane rows of fast memory a row: the fetched one, its copy
            # with the rows on the lanes (the feature block's layout) and
            # room beside them. Read on the chip (PERF.md section 6, PR 40):
            # 32 768 and 65 536 rows a segment cost the same within 3 %,
            # 131 072 a third more (the copy goes to HBM); at 32 768 the
            # compiled dot adds a row's products in the plain path's order.
            # A flat stream is tiled 1024 to a row of 8 x 128.
            plan = gather.segment_plan(m, 4, jnp.dtype(coefs.dtype).itemsize, 1024)
            return gather.map_segments(rows_of, (score_feats, score_slot), plan, 0)

        chunk = rescore_chunk_rows(
            m, d, jnp.dtype(score_feats.dtype).itemsize
        )
        if chunk >= m or self.mesh is not None:
            return rows_of(score_feats, score_slot)

        def one_chunk(i, out):
            start = jnp.minimum(i * chunk, m - chunk)
            s = rows_of(
                jax.lax.dynamic_slice_in_dim(score_feats, start, chunk, 0),
                jax.lax.dynamic_slice_in_dim(score_slot, start, chunk, 0),
            )
            return jax.lax.dynamic_update_slice_in_dim(out, s, start, 0)

        return jax.lax.fori_loop(
            0, -(-m // chunk), one_chunk,
            jnp.zeros((m,), score_feats.dtype),
        )

    def _score_args(self) -> tuple:
        return tuple(
            (blk.feats, blk.slot) + (() if blk.pos is None else (blk.pos,))
            for blk in self.score_blocks
        )

    def _score_plan(self) -> tuple:
        """What of the score blocks a program is specialised on: per block
        the buckets whose tables its slots count through."""
        return tuple(blk.buckets for blk in self.score_blocks)

    @partial(jax.jit, static_argnums=(0, 3))
    def _score_all_jit(self, score_args, state, plan) -> Array:
        TRACE_COUNTERS["re_score_all"] += 1
        from photon_tpu.parallel.mesh import constrain_rows

        # pin the [N] result to the row sharding: left to GSPMD a
        # scatter-built total compiles REPLICATED (every device holds the
        # full [N] — the SPMD auditor's partitioned-results check caught
        # exactly this), which at north-star N is an O(N) per-device
        # footprint for a vector the mesh should split
        return constrain_rows(
            self._score_blocks_body(score_args, state, plan), self.mesh
        )

    def score(self, state: list[Array]) -> Array:
        dispatch_count.record(1)
        out = self._aot_call(("score",), self._score_args(), state)
        if out is not None:
            return out
        return self._score_all_jit(
            self._score_args(), state, self._score_plan()
        )

    def _sweep_body(
        self, bucket_args, score_args, total, score, state, plan,
        reg_weight,
    ):
        """Whole CD step for ALL buckets as ONE program: residual, every
        bucket's vmapped solve, the rescoring in sample order, total update.
        Compiled as ``_sweep_jit`` (total/score/state DONATED — the [N]
        temporaries and each bucket's coefficient block reuse their input
        buffers) and ``_sweep_jit_nodonate`` (XLA:CPU — see
        donation_enabled). The residual's zero-sentinel pad is built
        once, not per bucket."""
        TRACE_COUNTERS["re_sweep"] += 1
        from photon_tpu.parallel.mesh import constrain_rows

        with scope("photon.descent.residual"):
            residual = constrain_rows(total - score, self.mesh)
            res_pad = jnp.concatenate(
                [residual, jnp.zeros((1,), residual.dtype)]
            )
        infos = [
            self._solve_bucket(f, l, o, tw, sp, w0, res_pad, reg_weight)
            for (f, l, o, tw, sp), w0 in zip(bucket_args, state)
        ]
        new_state = [r.x for r in infos]
        with scope("photon.descent.rescore"):
            # same row-sharding pin as _score_all_jit: GSPMD otherwise
            # replicates scatter-built [N] outputs across the mesh
            new_score = constrain_rows(
                self._score_blocks_body(score_args, new_state, plan),
                self.mesh,
            )
            new_total = constrain_rows(residual + new_score, self.mesh)
        # health fold only off-mesh: reducing entity-SHARDED per-bucket
        # values/gradients to replicated scalars would put an all-reduce
        # into the RE sweep program, breaking the no-collectives contract
        # (analysis/hlo.audit_coordinates scopes it to RE programs)
        health = (
            sweep_health(new_state, infos) if self.mesh is None else None
        )
        return new_state, new_score, new_total, infos, health

    _sweep_jit, _sweep_jit_nodonate = _make_sweep_jits(
        _sweep_body, static_argnums=(0, 6), donate_argnums=(3, 4, 5),
        name="re_sweep",
    )

    def _state_sds_list(self) -> list:
        ent_sh = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from photon_tpu.parallel.mesh import ENTITY_AXIS

            ent_sh = NamedSharding(self.mesh, P(ENTITY_AXIS, None))
        return [
            jax.ShapeDtypeStruct(
                (db.features.shape[0], db.features.shape[2]),
                self.dtype,
                sharding=ent_sh,
            )
            for db in self.device_buckets
        ]

    def _total_sds(self):
        sharding = None
        if self.mesh is not None:
            from photon_tpu.parallel.mesh import row_sharding

            sharding = row_sharding(self.mesh)
        return jax.ShapeDtypeStruct(
            (self.num_samples,), self.dtype, sharding=sharding
        )

    def _sweep_lowered(self, donate: bool):
        row = self._total_sds()
        return self._active_sweep_jit(donate).lower(
            self,
            self._train_args(),
            self._score_args(),
            row,
            row,
            self._state_sds_list(),
            self._score_plan(),
            self._scalar_sds(),
        )

    def _score_lowered(self):
        return type(self)._score_all_jit.lower(
            self, self._score_args(), self._state_sds_list(),
            self._score_plan(),
        )

    def spmd_contract(self):
        """The random-effect SOLVES are collective-free BY CONSTRUCTION —
        per-entity solves share nothing (PAPER §L4/L5; photon-ml's whole
        design), so any collective inside the train program is pure
        overhead on ICI and fatal straggle on the virtual CPU mesh
        (PERF.md r5; pinned at jaxpr/lowered/compiled level on the train
        program). The fused sweep/score programs additionally FOLD the
        per-entity scores into the row-sharded total — bounded, not
        zero, communication: gathers of one bucket's table/positions and
        reduces of one [n]-row vector per site. The allowance prices
        exactly those; an accidental gather of the whole dataset or an
        unbounded all-to-all fails. On a mesh the entity tables must
        also STAY entity-sharded: a table compiled or placed fully
        replicated keeps the numerics and silently spends O(devices)
        memory — the failure that kills the hundreds-of-billions-of-
        coefficients capacity claim."""
        from photon_tpu.analysis import spmd

        if self.mesh is None:
            return spmd.SpmdContract()
        itemsize = max(int(jnp.dtype(self.dtype).itemsize), 4)
        rows = self.num_samples + self.mesh.size + 64
        tables = [
            int(db.features.shape[0]) * int(db.features.shape[2])
            for db in self.device_buckets
        ]
        per_block = max(
            (
                max(
                    # a width's tables laid end to end, and their zero row
                    sum(tables[i] for i in blk.buckets)
                    + int(blk.feats.shape[1]),
                    int(blk.slot.shape[0]),
                )
                for blk in self.score_blocks
            ),
            default=1,
        )
        fold = spmd.CommAllowance(
            ops=(
                "all-reduce", "all-gather", "reduce-scatter",
                "collective-permute",
            ),
            max_bytes_per_site=max(rows, per_block + 64) * itemsize,
            reason=(
                "RE score fold: one width's tables and its block's "
                "positions gathered, one [n]-row reduce per site (solves "
                "themselves are collective-free, pinned on the train "
                "program)"
            ),
        )
        return spmd.SpmdContract(
            comm=spmd.COLLECTIVE_FREE,
            sharding=spmd.ShardingContract(
                on_mesh=True,
                # only λ and other scalars may replicate; every entity
                # block and every per-sample column is sharded
                replicated_bytes_limit=4 * 1024,
                partitioned_params=True,
                partitioned_results=True,
            ),
            comm_overrides={"sweep": fold, "score": fold},
        )

    def sweep_step(self, total: Array, score: Array, state: list[Array],
                   donate=None):
        dispatch_count.record(1)
        reg_w = self._reg_scalar(self.problem_config.regularization_weight)
        d = bool(donate) if donate is not None else donation_enabled()
        out = self._aot_call(
            ("sweep", d), self._train_args(), self._score_args(), total,
            score, state, reg_w,
        )
        if out is not None:
            return out
        return self._active_sweep_jit(d)(
            self,
            self._train_args(),
            self._score_args(),
            total,
            score,
            state,
            self._score_plan(),
            reg_w,
        )

    def to_model(self, state: list[Array]) -> RandomEffectModel:
        buckets = []
        for db, coefs, host_bucket in zip(
            self.device_buckets, state, self.dataset.buckets
        ):
            problem = GLMProblem.build(self.problem_config)
            variances = None
            if problem.config.variance_computation.value != "NONE":
                def var_one(f, l, o, w, w_opt):
                    batch = LabeledBatch(features=f, labels=l, offsets=o, weights=w)
                    return problem.variances(batch, w_opt)

                # same export-boundary rule as the coefficients below:
                # under jax.distributed the vmapped result is
                # entity-sharded across processes and must all-gather
                variances = _fetch_global(
                    jax.vmap(var_one)(
                        db.features, db.labels, db.offsets, db.train_weights, coefs
                    )
                )
            e_real = len(host_bucket.entity_ids)  # drop mesh-padding lanes
            buckets.append(
                BucketCoefficients(
                    entity_ids=host_bucket.entity_ids,
                    col_index=host_bucket.col_index,
                    # snapshot, not view: np.asarray of the solve output
                    # on XLA:CPU aliases the device buffer, and the state
                    # is donated to the next fused sweep — an exported
                    # model would silently track the live buffers.
                    # fetch_global: under jax.distributed the entity
                    # axis spans non-addressable devices and the export
                    # must all-gather (parallel/distributed.py)
                    coefficients=_fetch_global(coefs)[:e_real].copy(),
                    variances=None if variances is None else variances[:e_real],
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


@dataclasses.dataclass(eq=False)
class MatrixFactorizationCoordinate(Coordinate):
    """Latent-factor coordinate: score = ⟨u_row, v_col⟩ (config docstring
    for design; MatrixFactorizationCoordinateConfig).

    State is the pair of dense factor tables ``(U [R,k], V [C,k])``; one
    training step is a jit-compiled joint L-BFGS over both tables with the
    task's pointwise loss on margin = offset + residual + ⟨u, v⟩ and
    λ/2·(‖U‖² + ‖V‖²) regularization. Gather/scatter of per-sample factor
    rows is XLA's autodiff of the table indexing — no joins, no hogwild.
    """

    config: object
    row_vocab: np.ndarray
    col_vocab: np.ndarray
    row_idx: Array  # [N] int32
    col_idx: Array  # [N] int32
    labels: Array
    offsets: Array
    weights: Array
    l2_weight: float
    dtype: object
    seed: int
    #: set when the per-sample columns are row-sharded over a device mesh
    #: (the factor tables replicate) — declared in ``spmd_contract``
    mesh: object = None

    @staticmethod
    def build(
        data: GameData,
        config,
        dtype=jnp.float32,
        mesh=None,
        seed: int = 0,
    ):
        from photon_tpu.game.data import PAD_ENTITY_KEY, entity_row_indices

        r_keys = np.asarray(data.id_tags[config.row_entity_type])
        c_keys = np.asarray(data.id_tags[config.col_entity_type])
        row_vocab = np.unique(r_keys[r_keys != PAD_ENTITY_KEY])
        col_vocab = np.unique(c_keys[c_keys != PAD_ENTITY_KEY])
        r_index = {k: i for i, k in enumerate(row_vocab)}
        c_index = {k: i for i, k in enumerate(col_vocab)}
        # padding rows point at factor row 0 but carry weight 0
        row_idx = entity_row_indices(r_index, r_keys, 0).astype(np.int32)
        col_idx = entity_row_indices(c_index, c_keys, 0).astype(np.int32)
        arrays = {
            "row_idx": row_idx,
            "col_idx": col_idx,
            "labels": np.asarray(data.labels, dtype=dtype),
            "offsets": np.asarray(data.offsets, dtype=dtype),
            "weights": np.asarray(data.weights, dtype=dtype),
        }
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            rows = NamedSharding(mesh, P(tuple(mesh.axis_names)))
            arrays = {
                k: jax.device_put(v, rows) for k, v in arrays.items()
            }
        else:
            arrays = {k: jnp.asarray(v) for k, v in arrays.items()}
        # placement choke point: the per-sample index/label/weight columns
        obs_memory.count_h2d(obs_memory.tree_device_bytes(arrays))
        return MatrixFactorizationCoordinate(
            config=config,
            row_vocab=row_vocab,
            col_vocab=col_vocab,
            l2_weight=float(config.regularization_weights[0]),
            dtype=dtype,
            seed=seed,
            mesh=mesh,
            **arrays,
        )

    def with_regularization_weight(self, w: float):
        """In-place λ reweight — see FixedEffectCoordinate: λ is a traced
        argument of ``_train_jit``, so the compiled program survives."""
        self.l2_weight = float(w)
        return self

    def initial_state(self) -> tuple[Array, Array]:
        k = self.config.num_factors
        rng = np.random.default_rng(self.seed)
        scale = self.config.init_scale / np.sqrt(k)
        u = rng.normal(scale=scale, size=(len(self.row_vocab), k))
        v = rng.normal(scale=scale, size=(len(self.col_vocab), k))
        # factor tables replicate ON THE MESH (see spmd_contract) —
        # matching the per-sample columns' placement up front avoids
        # an implicit reshard at the first sweep dispatch
        return self.place_state(
            (jnp.asarray(u, dtype=self.dtype), jnp.asarray(v, dtype=self.dtype))
        )

    def place_state(self, state: tuple[Array, Array]) -> tuple[Array, Array]:
        if self.mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        return tuple(
            jax.device_put(jnp.asarray(x, dtype=self.dtype), rep)
            for x in state
        )

    def _train_body(
        self,
        data,
        residual_scores: Array,
        u0: Array,
        v0: Array,
        l2_weight: Array,
    ):
        # data = (row_idx, col_idx, offsets, weights, labels) as ARGUMENTS,
        # not via static self: trace-time constants lower as HLO literals
        # and oversize the module at scale (see
        # FixedEffectCoordinate._train_jit).
        row_idx, col_idx, base_offsets, weights, labels = data
        from photon_tpu.ops.losses import loss_for_task
        from photon_tpu.optimize.lbfgs import minimize_lbfgs

        loss = loss_for_task(self.config.optimization.task)
        shapes = (u0.shape, v0.shape)
        sizes = (u0.size, v0.size)

        def unpack(x):
            u = x[: sizes[0]].reshape(shapes[0])
            v = x[sizes[0] :].reshape(shapes[1])
            return u, v

        offsets = base_offsets + residual_scores

        def value_and_grad(x):
            def value(x):
                u, v = unpack(x)
                margin = offsets + jnp.einsum(
                    "nk,nk->n", u[row_idx], v[col_idx]
                )
                data_term = jnp.sum(weights * loss.loss(margin, labels))
                reg = 0.5 * l2_weight * jnp.sum(x * x)
                return data_term + reg

            return jax.value_and_grad(value)(x)

        x0 = jnp.concatenate([u0.ravel(), v0.ravel()])
        res = minimize_lbfgs(
            value_and_grad, x0, self.config.optimization.optimizer_config
        )
        u, v = unpack(res.x)
        return u, v, res

    @partial(jax.jit, static_argnums=0)
    def _train_jit(
        self,
        data,
        residual_scores: Array,
        u0: Array,
        v0: Array,
        l2_weight: Array,
    ):
        return self._train_body(data, residual_scores, u0, v0, l2_weight)

    def _data_args(self):
        return (
            self.row_idx,
            self.col_idx,
            self.offsets,
            self.weights,
            self.labels,
        )

    def train(self, residual_scores: Array, state):
        dispatch_count.record(1)
        u, v, res = self._train_jit(
            self._data_args(),
            residual_scores,
            state[0],
            state[1],
            self._reg_scalar(self.l2_weight),
        )
        return (u, v), res

    def _score_body(self, row_idx, col_idx, weights, state) -> Array:
        u, v = state
        s = jnp.einsum("nk,nk->n", u[row_idx], v[col_idx])
        return jnp.where(weights > 0, s, 0.0)

    @partial(jax.jit, static_argnums=0)
    def _score_jit(self, row_idx, col_idx, weights, state) -> Array:
        return self._score_body(row_idx, col_idx, weights, state)

    def score(self, state) -> Array:
        dispatch_count.record(1)
        out = self._aot_call(
            ("score",), self.row_idx, self.col_idx, self.weights, state
        )
        if out is not None:
            return out
        return self._score_jit(
            self.row_idx, self.col_idx, self.weights, state
        )

    def _sweep_body(self, data, total, score, state, l2_weight):
        """Fused CD step (see FixedEffectCoordinate._sweep_body): the joint
        L-BFGS over both factor tables plus rescore and total update in one
        program; ``_sweep_jit`` donates ``total``/``score``/``(U, V)``,
        ``_sweep_jit_nodonate`` is the XLA:CPU variant (see
        donation_enabled)."""
        TRACE_COUNTERS["mf_sweep"] += 1
        row_idx, col_idx, _, weights, _ = data
        residual = total - score
        u, v, res = self._train_body(
            data, residual, state[0], state[1], l2_weight
        )
        new_score = self._score_body(row_idx, col_idx, weights, (u, v))
        new_total = residual + new_score
        # factor tables and the joint solve outputs are replicated, so
        # the health reductions are collective-free mesh or no mesh
        return (u, v), new_score, new_total, res, sweep_health((u, v), res)

    _sweep_jit, _sweep_jit_nodonate = _make_sweep_jits(
        _sweep_body, static_argnums=0, donate_argnums=(2, 3, 4),
        name="mf_sweep",
    )

    def _state_sds_pair(self):
        k = self.config.num_factors
        return (
            jax.ShapeDtypeStruct((len(self.row_vocab), k), self.dtype),
            jax.ShapeDtypeStruct((len(self.col_vocab), k), self.dtype),
        )

    def _sweep_lowered(self, donate: bool):
        row = self._row_sds(self.labels.shape[0], self.labels)
        return self._active_sweep_jit(donate).lower(
            self, self._data_args(), row, row, self._state_sds_pair(),
            self._scalar_sds(),
        )

    def _score_lowered(self):
        return type(self)._score_jit.lower(
            self, self.row_idx, self.col_idx, self.weights,
            self._state_sds_pair(),
        )

    def spmd_contract(self):
        """MF on a mesh data-parallelizes the sample axis while both
        factor tables replicate, so the joint L-BFGS psums ONE packed
        (R·k + C·k) gradient per iteration — allowance priced at exactly
        that; the replicated limit covers the two factor tables riding as
        (replicated) state parameters."""
        from photon_tpu.analysis import spmd

        if self.mesh is None:
            return spmd.SpmdContract()
        itemsize = int(jnp.dtype(self.dtype).itemsize)
        k = int(self.config.num_factors)
        packed = (len(self.row_vocab) + len(self.col_vocab)) * k + 16
        return spmd.SpmdContract(
            comm=spmd.CommAllowance(
                ops=("all-reduce",),
                max_bytes_per_site=packed * itemsize,
                reason=(
                    "MF joint solve: one packed (R·k + C·k) factor "
                    "gradient reduce per iteration"
                ),
            ),
            sharding=spmd.ShardingContract(
                on_mesh=True,
                replicated_bytes_limit=2 * packed * itemsize,
                partitioned_params=True,
                partitioned_results=True,
            ),
        )

    def sweep_step(self, total: Array, score: Array, state, donate=None):
        dispatch_count.record(1)
        args = (
            self._data_args(),
            total,
            score,
            state,
            self._reg_scalar(self.l2_weight),
        )
        d = bool(donate) if donate is not None else donation_enabled()
        out = self._aot_call(("sweep", d), *args)
        if out is not None:
            return out
        return self._active_sweep_jit(d)(self, *args)

    def to_model(self, state) -> MatrixFactorizationModel:
        return MatrixFactorizationModel(
            row_entity_type=self.config.row_entity_type,
            col_entity_type=self.config.col_entity_type,
            row_vocab=self.row_vocab,
            col_vocab=self.col_vocab,
            # np.array, not np.asarray: under a float64 fit the dtype
            # conversion is a no-op and asarray would alias the live
            # factor buffers, which the MF sweep program DONATES — the
            # exported model must be a snapshot
            row_factors=np.array(state[0], dtype=np.float64),
            col_factors=np.array(state[1], dtype=np.float64),
        )


def build_coordinate(
    data: GameData,
    config,
    *,
    normalization: NormalizationContext = NormalizationContext(),
    re_dataset: RandomEffectDataset | None = None,
    dtype=jnp.float32,
    mesh=None,
    seed: int = 0,
) -> Coordinate:
    """Config → coordinate dispatch (reference CoordinateFactory.build)."""
    if isinstance(config, FixedEffectCoordinateConfig):
        return FixedEffectCoordinate.build(
            data, config, normalization, dtype, mesh=mesh
        )
    if isinstance(config, RandomEffectCoordinateConfig):
        if re_dataset is None:
            raise ValueError("random-effect coordinate needs a built dataset")
        return RandomEffectCoordinate.build(
            data, re_dataset, config, dtype, mesh=mesh
        )
    if isinstance(config, MatrixFactorizationCoordinateConfig):
        return MatrixFactorizationCoordinate.build(
            data, config, dtype, mesh=mesh, seed=seed
        )
    raise TypeError(f"unknown coordinate config {type(config)}")
