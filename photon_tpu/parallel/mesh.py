"""Device mesh and sharding helpers — the distributed communication backend.

This replaces the reference's Spark-RDD machinery (SURVEY.md §5.8): where
Photon-ML reduces gradients with ``RDD.treeAggregate(depth)`` and re-broadcasts
coefficients every evaluation (ValueAndGradientAggregator.scala:244-247,
DistributedGLMLossFunction.scala:64), the TPU build shards the batch axis of
the one jit-compiled program over a ``jax.sharding.Mesh`` and lets XLA insert
``psum`` over ICI (and over DCN for the pod-slice outer axis). The tree shape
is the compiler's problem — the reference's ``treeAggregateDepth`` parameter
has no equivalent because it is no longer needed.

Axes:
- ``data``  — batch rows (data parallelism; the reference's RDD partitions)
- ``entity`` — random-effect entities (the reference's entity partitioner,
  RandomEffectDataSetPartitioner.scala:113-147, becomes a static
  entity→shard assignment at dataset build)

Multi-host: under ``jax.distributed`` the same Mesh spans hosts; nothing in
this module changes — collectives ride ICI within a slice and DCN across
slices, which is exactly the scaling story the reference delegates to
Spark's shuffle service.
"""
from __future__ import annotations

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.types import LabeledBatch, PyTree, SparseBatch

BATCH_AXIS = "data"
ENTITY_AXIS = "entity"

def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with the varying-axis checker DISABLED. Scope it to the SMALLEST sub-function
    the checker provably mis-handles — today that is exactly the vmapped
    optimizer while-loop solve (this jax has no replication rule for
    ``while``, and the carries mix shard-varying state with constant-
    initialized history buffers); surrounding gathers/elementwise work
    belongs under plain GSPMD where the compiler's checks apply. The real
    contract is the no-collectives HLO regression test
    (tests/test_distributed.py::test_re_train_program_has_no_collectives)."""
    return shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )


def make_mesh(
    num_data: int | None = None,
    num_entity: int = 1,
    *,
    devices: list | None = None,
) -> Mesh:
    """Build a (data, entity) mesh over the available devices.

    Default: all devices on the data axis. ``num_data`` × ``num_entity``
    must equal the device count when both are given.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if num_data is None:
        num_data = n // num_entity
    if num_data * num_entity != n:
        raise ValueError(
            f"mesh {num_data}x{num_entity} does not cover {n} devices"
        )
    arr = np.asarray(devices).reshape(num_data, num_entity)
    return Mesh(arr, (BATCH_AXIS, ENTITY_AXIS))


def parse_mesh_spec(spec: str) -> tuple[int | None, int]:
    """``--mesh`` / ``PHOTON_MESH`` spec → ``(num_data, num_entity)``.

    Accepted forms (device counts, matching ``make_mesh``):

    - ``"DxE"``  — explicit (data, entity) factorization, e.g. ``1x8``;
    - ``"N"``    — N devices, all on the data axis (``num_entity=1``);
    - ``"auto"`` — every available device, all on the data axis
      (``num_data=None`` so ``make_mesh`` divides at call time);
    - ``""`` / ``"off"`` / ``"none"`` / ``"0"`` — no mesh (callers get
      ``None`` from :func:`resolve_mesh`).

    Raises ``ValueError`` on anything else — a typo'd mesh spec must be
    a loud config error, not a silent single-device run.
    """
    s = spec.strip().lower()
    if s in ("", "off", "none", "0"):
        raise ValueError("empty mesh spec (resolve_mesh handles disable)")
    if s == "auto":
        return None, 1
    if "x" in s:
        d_s, _, e_s = s.partition("x")
        try:
            d, e = int(d_s), int(e_s)
        except ValueError:
            raise ValueError(
                f"mesh spec must be 'DxE', 'N', or 'auto', got {spec!r}"
            ) from None
        if d < 1 or e < 1:
            raise ValueError(f"mesh factors must be >= 1, got {spec!r}")
        return d, e
    try:
        n = int(s)
    except ValueError:
        raise ValueError(
            f"mesh spec must be 'DxE', 'N', or 'auto', got {spec!r}"
        ) from None
    if n < 1:
        raise ValueError(f"mesh device count must be >= 1, got {spec!r}")
    return n, 1


def resolve_mesh(spec: str | None = None) -> Mesh | None:
    """The mesh a training run spans: ``PHOTON_MESH`` env > explicit
    ``spec`` (the ``--mesh`` flag) > no mesh (the repo-wide env-over-
    config knob precedence). ``off``/``none``/``0``/empty disable.
    Returns ``None`` off-mesh so callers thread it straight into
    ``GameEstimator(mesh=...)``."""
    import os

    env = os.environ.get("PHOTON_MESH", "").strip()
    s = env or (spec or "")
    if s.strip().lower() in ("", "off", "none", "0"):
        return None
    num_data, num_entity = parse_mesh_spec(s)
    return make_mesh(num_data=num_data, num_entity=num_entity)


def mesh_fingerprint(mesh: Mesh | None) -> tuple | None:
    """Stable topology description of a mesh for checkpoint fingerprints:
    axis names + per-axis device counts. A checkpoint written under one
    topology must not silently resume under another — the saved leaves'
    layouts (entity-sharded tables, row-sharded totals) are declared per
    topology, and a shape-compatible but differently-sharded resume
    would re-place every leaf mid-descent. ``None`` off-mesh."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(int(s) for s in mesh.devices.shape))


def shard_batch(batch, mesh: Mesh, put=None):
    """Place a batch with rows sharded over every mesh device (the feature
    dimension replicated). Rows spread over both axes so a fixed-effect solve
    uses the whole mesh, not just the data axis. Works for both layouts: a
    sparse batch's [N, K] index/value blocks shard on rows exactly like the
    dense [N, D] block; the scatter-add output ([D]) is replicated, with XLA
    inserting the psum.

    ``put(array, sharding)`` defaults to ``jax.device_put`` (single
    controller); the multi-host path passes a ``make_array_from_callback``
    placement instead (parallel/distributed.distribute_batch) so the field
    mapping lives in exactly one place."""
    if put is None:
        put = jax.device_put
    axes = tuple(mesh.axis_names)
    row_sharded = row_sharding(mesh)  # the layout constrain_rows pins to
    mat_sharded = NamedSharding(mesh, P(axes, None))
    if isinstance(batch, SparseBatch):
        return SparseBatch(
            indices=put(batch.indices, mat_sharded),
            values=put(batch.values, mat_sharded),
            labels=put(batch.labels, row_sharded),
            offsets=put(batch.offsets, row_sharded),
            weights=put(batch.weights, row_sharded),
        )
    return LabeledBatch(
        features=put(batch.features, mat_sharded),
        labels=put(batch.labels, row_sharded),
        offsets=put(batch.offsets, row_sharded),
        weights=put(batch.weights, row_sharded),
    )


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of a per-sample [N, ...] array with rows spread over every
    mesh device — the layout of batches, scores, and totals."""
    axes = tuple(mesh.axis_names)
    return NamedSharding(mesh, P(axes))


def constrain_rows(x, mesh: Mesh | None):
    """Pin a per-sample vector to the mesh's row sharding inside jit.

    The fused sweep step (game/coordinate.py ``_sweep_jit``) chains
    residual → solve → rescore → total inside ONE program; this constraint
    keeps the [N] temporaries row-sharded end to end instead of leaving
    GSPMD free to replicate the chain (at the north-star N that is the
    difference between an O(N/devices) and an O(N) per-device footprint).
    No-op off-mesh."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, row_sharding(mesh))


def shard_entities(tree: PyTree, mesh: Mesh, axis: int = 0) -> PyTree:
    """Shard leading (entity) axis of every leaf over the entity mesh axis —
    the random-effect table layout ([num_entities, ...] entity-sharded)."""
    def put(x):
        p = P(*([ENTITY_AXIS] + [None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, p))

    return jax.tree_util.tree_map(put, tree)


def replicate(tree: PyTree, mesh: Mesh) -> PyTree:
    """Fully replicate a pytree over the mesh (the reference's broadcast —
    but done once; jit keeps it on-device across iterations)."""
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def pad_rows_to_multiple(n: int, devices: int) -> int:
    """Round a row count up so it divides evenly across ``devices``."""
    return ((n + devices - 1) // devices) * devices
