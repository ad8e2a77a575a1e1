"""Multi-chip windowed sparse Xᵀr: instance-sharded prefix-sum reduction.

Completes the column-window story (ops/sparse_windows.py) for the mesh
case. Under plain GSPMD the windowed pass does not partition (its segment
loop carries sequential semantics), so ``parallel/mesh.shard_batch``
intentionally drops windows and the sharded ELL path falls back to
per-shard segment_sum — correct, but back on XLA:TPU's
serialized-scatter lowering, now per chip.

This module shards the layout EXPLICITLY instead, with ``shard_map``:

- window *instances* (the leading axis of rows/lcols/vals) are sharded
  across the mesh — each device owns a contiguous run of column windows'
  instances (instances are column-sorted, so this is a column-range
  partition of the gradient);
- the residual vector ``per_row`` is passed replicated — it is O(N) small
  (4 MB at n=2²⁰) next to the O(N·K) pair stream, the classic
  replicate-the-vector SpMV distribution;
- each device runs the SAME single-chip pass (``rmatvec_windows_prefix``)
  over its instances into a full [dim] partial that is zero
  outside its column ranges, and one ``psum`` over the mesh axes adds the
  disjoint partials — the reference's treeAggregate for the sparse
  gradient (ValueAndGradientAggregator.scala:244-247), ridden over ICI.

Padding instances added for shard divisibility carry value 0 / local col
w−1 / window id W−1 (and bounds that count every slot at w−1), preserving
both the algebra and the sorted order of ``inst2win``.
"""
from __future__ import annotations

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.ops.sparse_windows import (
    ColumnWindows,
    instance_multiple,
    rmatvec_windows_prefix,
)
from photon_tpu.types import Array


def pad_windows_for_mesh(
    windows: ColumnWindows, num_shards: int, num_features: int
) -> ColumnWindows:
    """Pad the instance axis with inert instances (vals 0, lcol w−1, last
    window id) to ``num_shards`` equal runs, each a multiple of the
    instances per consumer block of its shard's backward pass (whole
    segments of its loop: the build's own padding rule, per shard)."""
    w_inst, length = windows.rows.shape
    per_shard = -(-w_inst // num_shards)
    per_shard += (-per_shard) % instance_multiple(
        per_shard, length, np.dtype(windows.vals.dtype).itemsize
    )
    pad = per_shard * num_shards - w_inst
    if pad == 0:
        return windows
    w = windows.window
    num_windows = max(1, -(-num_features // w))

    def pad_leaf(x, fill):
        # stays HOST numpy: device_put shards straight from host, so the
        # padded stream never lands whole on one device
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return np.pad(np.asarray(x), widths, constant_values=fill)

    # an all-padding instance has every slot at lcol w−1: exclusive
    # prefix counts are 0 for c ≤ w−1 and `length` at c = w
    pad_bounds = np.zeros((pad, w + 1), dtype=np.int32)
    pad_bounds[:, -1] = length

    return ColumnWindows(
        rows=pad_leaf(windows.rows, 0),
        lcols=pad_leaf(windows.lcols, w - 1),
        vals=pad_leaf(windows.vals, 0),
        inst2win=pad_leaf(windows.inst2win, num_windows - 1),
        iota=windows.iota,
        bounds=np.concatenate([np.asarray(windows.bounds), pad_bounds]),
    )


def shard_windows(
    windows: ColumnWindows, mesh: Mesh, num_features: int
) -> ColumnWindows:
    """Place the instance axis sharded over every mesh axis (iota
    replicated). Call ``pad_windows_for_mesh`` first if the instance count
    may not divide the mesh."""
    from photon_tpu.util.device_retry import put_with_retry

    axes = tuple(mesh.axis_names)
    windows = pad_windows_for_mesh(
        windows, int(np.prod([mesh.shape[a] for a in axes])), num_features
    )
    inst_sharded = NamedSharding(mesh, P(axes))
    inst_mat = NamedSharding(mesh, P(axes, None))
    # placement wrapped against a transient UNAVAILABLE, like every
    # other multi-hundred-MB coordinate-build put (game/coordinate.py);
    # the chaos fault point rides inside the retried thunk
    from photon_tpu.util import faults

    put = lambda x, s: put_with_retry(  # noqa: E731
        lambda x=x, s=s: (
            faults.fault_point("sparse.placement"),
            jax.device_put(x, s),
        )[1]
    )
    return ColumnWindows(
        rows=put(windows.rows, inst_mat),
        lcols=put(windows.lcols, inst_mat),
        vals=put(windows.vals, inst_mat),
        inst2win=put(windows.inst2win, inst_sharded),
        iota=put(windows.iota, NamedSharding(mesh, P())),
        bounds=put(windows.bounds, inst_mat),
    )


def sharded_windowed_rmatvec(
    windows: ColumnWindows, per_row: Array, dim: int, mesh: Mesh
) -> Array:
    """Xᵀ·per_row over instance-sharded windows: per-shard single-chip
    pass + one psum of disjoint column-range partials."""
    axes = tuple(mesh.axis_names)

    def local(wins: ColumnWindows, r: Array) -> Array:
        partial = rmatvec_windows_prefix(wins, r, dim)
        return jax.lax.psum(partial, axes)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(
            ColumnWindows(
                rows=P(axes, None),
                lcols=P(axes, None),
                vals=P(axes, None),
                inst2win=P(axes),
                iota=P(),
                bounds=P(axes, None),
            ),
            P(),  # replicated residual vector
        ),
        out_specs=P(),
    )(windows, per_row)
