"""Column-windowed sparse layout: the TPU-native Xᵀr kernel for high-dim GLMs.

Why this exists: the padded-ELL backward pass (``ops/objective.py rmatvec``)
is a flat scatter-add of N·K contributions into a [D] gradient —
``jax.ops.segment_sum`` with D up to 2²⁰ segments. XLA:TPU lowers an
unsorted many-collisions scatter to a serialized update loop, which at
BASELINE config-3 scale (58M updates/eval) is minutes per evaluation —
the one pattern on the chip that must not go through XLA's default
lowering. (The reference never hits this cliff because its aggregator
accumulates into a per-executor dense array in JVM memory,
ValueAndGradientAggregator.scala:133-152; the TPU equivalent of that
"local dense accumulate" is exactly this module.)

The fix is a build-time layout that turns the scatter into dense work:

- **Build** (host, once — indices are static across every objective
  evaluation of a solve): sort the (row, col, val) triples by column and
  bucket them into windows of ``window`` consecutive columns. Pad each
  window to a common length L. Windows whose load exceeds L **spill** into
  multiple instances mapped to the same output range — essential under
  real feature skew (an intercept column alone holds N entries).
- **Scatter → prefix sums**: within an instance the local columns are
  non-decreasing (column sort), so per-column sums are differences of the
  contribution cumsum at build-time-static boundaries (``bounds``) — a
  fully dense path with no scatter and no custom kernel
  (:func:`rmatvec_windows_prefix`, the one rmatvec over a layout).
- **The gather side is the floor**: contrib = vals · r[rows] is a
  1-element gather from a [N] vector, which XLA:TPU serializes; it goes
  through ops/gather's row fetch and lane select and is 81 % of a
  backward pass (PERF.md §5). ``_over_instances`` runs it in the segment
  loop of the pass, on two block sizes (``_backward_cut``). A SEGMENT is a
  block of whole instances whose fetched rows fit fast memory
  (``gather.segment_plan``: 32 instances of 4096 slots); its body fetches,
  selects and multiplies by ``vals``. The CONSUMER (the centring, cumsum
  and bounds reads, [I, L] → [I, w]) runs once per 4 segments, on their
  128 instances together: with a lane's worth of instances in its block
  the cumsum scans with the instances on the lanes (0.0245 s a pass at the
  benchmark's sparse cell; 0.090 s on a segment's 32, where it scans across
  them: PERF.md §6, PR 35). The build pads the instance count to whole
  consumer blocks, so [W_inst, L] is read as it lies and no pass pads,
  slices or stacks a value per slot.

Instance partials combine with one [W_inst, w] → [W, w] sorted
segment-sum (thousands of rows, not millions — off the cliff).

Without a layout (the CPU, where ``maybe_build_windows`` builds none, and
row-sharded GSPMD batches: parallel/mesh.shard_batch drops the windows,
whose loop does not partition) Xᵀr is the ``segment_sum`` of
``ops/objective._rmatvec``, which is also the reference the tests compare
with. The multi-chip windowed path lives in ``parallel/sparse.py`` —
window instances sharded explicitly over the mesh with ``shard_map``
(column-range partials + one psum), running this module's pass per shard.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu import obs
from photon_tpu.obs.scopes import scope
from photon_tpu.types import Array
from photon_tpu.util import target

#: the TPU tiling rules: the last dim of a block lies on 128 lanes, the
#: second-to-last is a multiple of 8 sublanes
_LANES = 128
_SUBLANES = 8


class ColumnWindows(NamedTuple):
    """Static column-sorted instance layout (see module docstring).

    rows/lcols/vals: [W_inst, L]; ``inst2win``: [W_inst] window id per
    instance (non-decreasing); ``iota``: [w] = arange(window) — carried as
    an array so the window width rides a static *shape* through jit (an int
    leaf would be traced away).
    ``bounds``: [W_inst, w+1] exclusive prefix counts per local column
    (bounds[i, c] = #slots in instance i with lcol < c) — static segment
    boundaries for the prefix-sum rmatvec. Padding slots: row 0, local col
    w−1, value 0. W_inst is padded at build time (inert instances) to a
    multiple of 8 (the TPU sublane rule for a block of whole instances) and
    of the instances per consumer block of the backward pass
    (``instance_multiple``).
    """

    rows: Array
    lcols: Array
    vals: Array
    inst2win: Array
    iota: Array
    bounds: Array

    @property
    def window(self) -> int:
        return self.iota.shape[0]

    @property
    def instance_len(self) -> int:
        return self.rows.shape[1]


def _backward_cut(w_inst: int, length: int, itemsize: int):
    """How the backward pass cuts a layout of ``w_inst`` instances of
    ``length`` slots over a per-row table of ``itemsize``-byte entries:
    (ops/gather's ``SegmentPlan`` of whole instances, segments to a block of
    the consumer). The fetch's block is sized by fast memory (the plan); the
    consumer's by the lanes: the least whole number of segments that puts a
    lane's worth of instances (128) in its block, so that the prefix sums
    scan with the instances on the lanes. One segment to a block where a
    segment holds that many already, where the pass is one segment, and
    where the layout has no 128 instances."""
    from photon_tpu.ops.gather import segment_plan

    plan = segment_plan(w_inst, length, itemsize, _SUBLANES)
    if plan.steps == 1 or w_inst < _LANES:
        return plan, 1
    return plan, -(-_LANES // plan.per)


def instance_multiple(w_inst: int, length: int, itemsize: int) -> int:
    """What a layout's instance count is padded to a multiple of: the
    instances in a consumer block of the backward pass (``_backward_cut``:
    whole segments of its loop), or 8 where the pass is one segment."""
    plan, group = _backward_cut(
        w_inst + (-w_inst) % _SUBLANES, length, itemsize
    )
    return _SUBLANES if plan.steps == 1 else plan.per * group


def _native_histogram(arr_idx, arr_val, num_features):
    """Per-column nonzero histogram via the C++ counting-sort builder
    (native/window_builder.cpp) — O(nnz + d) vs numpy's comparison argsort.
    Returns (col_counts, nnz) or None when the fast path does not apply
    (non-f32 values, library unavailable)."""
    if arr_val.dtype != np.float32 or arr_idx.size == 0:
        return None
    from photon_tpu.data.native_index import _load_native_lib

    lib = _load_native_lib()
    if lib is None or not hasattr(lib, "win_col_histogram"):
        return None
    import ctypes

    lib.win_col_histogram.restype = ctypes.c_int64
    col_counts = np.zeros(num_features, dtype=np.int64)
    vals = np.ascontiguousarray(arr_val, dtype=np.float32)
    nnz = lib.win_col_histogram(
        arr_idx.ctypes.data_as(ctypes.c_void_p),
        vals.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(arr_idx.size),
        ctypes.c_int64(num_features),
        col_counts.ctypes.data_as(ctypes.c_void_p),
    )
    if nnz < 0:
        raise ValueError("sparse column index outside [0, num_features)")
    return col_counts, int(nnz), lib, vals


def _native_fill(
    lib, arr_idx, arr_val32, k, num_features, window, cap, length,
    col_counts, win_start, inst_base, rows, lcols, vals,
):
    import ctypes

    lib.win_fill.restype = ctypes.c_int64
    col_next = np.concatenate([[0], np.cumsum(col_counts)])[:-1].astype(
        np.int64
    )
    rc = lib.win_fill(
        arr_idx.ctypes.data_as(ctypes.c_void_p),
        arr_val32.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(arr_idx.size),
        ctypes.c_int64(k),
        ctypes.c_int64(num_features),
        ctypes.c_int64(window),
        ctypes.c_int64(cap),
        ctypes.c_int64(length),
        col_next.ctypes.data_as(ctypes.c_void_p),
        np.ascontiguousarray(win_start, dtype=np.int64).ctypes.data_as(
            ctypes.c_void_p
        ),
        np.ascontiguousarray(inst_base, dtype=np.int64).ctypes.data_as(
            ctypes.c_void_p
        ),
        rows.ctypes.data_as(ctypes.c_void_p),
        lcols.ctypes.data_as(ctypes.c_void_p),
        vals.ctypes.data_as(ctypes.c_void_p),
    )
    if rc != 0:
        raise ValueError(f"native window fill failed rc={rc}")


def build_column_windows(
    indices: np.ndarray,
    values: np.ndarray,
    num_features: int,
    *,
    window: int = 128,
    instance_cap: int = 4096,
    chunk: int = 1024,
    host: bool = False,
) -> ColumnWindows:
    """Host-side build from padded-ELL [N, K] arrays (vectorized numpy).

    ``instance_cap`` bounds L so one hot column (intercept!) spills across
    instances instead of inflating every window's padding. L is rounded up
    to a multiple of ``chunk`` (1024: whole 8 × 128 tiles) or to 8 for
    small layouts. ``host=True`` keeps the result as numpy — for mesh
    placement, where materializing the whole stream on one device first
    would be the exact single-device footprint the sharding avoids.
    """
    with obs.span(
        "windows.build", cat="build", num_features=num_features
    ) as sp:
        windows = _build_column_windows(
            indices, values, num_features, window, instance_cap, chunk, host
        )
        # what a TPU's backward pass makes of these shapes: the steps of its
        # loop, and the instances `photon.rmatvec.prefix` sees at once
        plan, group = _backward_cut(
            *windows.rows.shape, windows.vals.dtype.itemsize
        )
        sp.set(segments=plan.steps, consumer_block=plan.per * group)
        return windows


def _build_column_windows(
    indices, values, num_features, window, instance_cap, chunk, host
) -> ColumnWindows:
    arr_idx = np.ascontiguousarray(np.asarray(indices), dtype=np.int32)
    arr_val = np.asarray(values)
    n, k = arr_idx.shape
    num_windows = max(1, -(-num_features // window))

    with obs.span("windows.histogram", cat="build"):
        native = _native_histogram(arr_idx, arr_val, num_features)
        if native is not None:
            col_counts, nnz, nat_lib, nat_vals = native
            counts = np.add.reduceat(
                np.pad(col_counts, (0, num_windows * window - num_features)),
                np.arange(num_windows) * window,
            )
        else:
            flat_col = arr_idx.reshape(-1).astype(np.int64)
            flat_val = arr_val.reshape(-1)
            flat_row = np.repeat(np.arange(n, dtype=np.int64), k)
            keep = flat_val != 0.0  # ELL padding slots carry value 0
            flat_col, flat_val, flat_row = (
                flat_col[keep],
                flat_val[keep],
                flat_row[keep],
            )
            nnz = flat_col.size
            counts = np.bincount(flat_col // window, minlength=num_windows)

    # Round the spill cap itself to the instance length so FULL spill
    # instances carry zero padding: a window's stream stays sorted by
    # column across its instances, with padding (local col w−1) only at
    # the end of its last one.
    cap = int(min(counts.max() if nnz else 1, instance_cap))
    if cap > chunk:
        cap = -(-cap // chunk) * chunk
    else:
        cap = max(8, -(-cap // 8) * 8)
    length = cap
    n_inst = np.maximum(1, -(-counts // cap))
    w_inst = int(n_inst.sum())
    # Round the instance count with inert instances (vals 0 / lcol w−1 /
    # last window id) to a multiple of 8 (a block of instances meets the
    # TPU sublane-divisibility rule for any layout) and of the backward
    # pass's instances per consumer block, so that its loop runs over whole
    # blocks of whole segments and no pass pads or slices the streams.
    w_inst_pad = (-w_inst) % instance_multiple(
        w_inst, length, arr_val.dtype.itemsize
    )
    inst_base = np.concatenate([[0], np.cumsum(n_inst)])[:-1]
    win_start = np.concatenate([[0], np.cumsum(counts)])
    w_inst += w_inst_pad

    # the native fill, or the argsort and scatter where the library is absent
    with obs.span("windows.fill", cat="build", native=native is not None):
        rows = np.zeros(w_inst * length, dtype=np.int32)
        lcols = np.full(w_inst * length, window - 1, dtype=np.int32)
        if native is not None:
            vals = np.zeros(w_inst * length, dtype=np.float32)
            if nnz > 0:  # all-padding layout needs no fill pass
                _native_fill(
                    nat_lib, arr_idx, nat_vals, k, num_features, window,
                    cap, length, col_counts, win_start, inst_base, rows,
                    lcols, vals,
                )
        else:
            vals = np.zeros(w_inst * length, dtype=flat_val.dtype)
            order = np.argsort(flat_col, kind="stable")
            s_col, s_val, s_row = (
                flat_col[order],
                flat_val[order],
                flat_row[order],
            )
            s_win = s_col // window
            pos_in_win = np.arange(nnz, dtype=np.int64) - win_start[s_win]
            dest = (inst_base[s_win] + pos_in_win // cap) * length + (
                pos_in_win % cap
            )
            rows[dest] = s_row
            lcols[dest] = s_col % window
            vals[dest] = s_val

    inst2win = np.concatenate([
        np.repeat(np.arange(num_windows, dtype=np.int32), n_inst),
        np.full(w_inst_pad, num_windows - 1, dtype=np.int32),
    ])
    lcols2 = lcols.reshape(w_inst, length)
    # the hand-over to the device is asynchronous and is not waited for
    # here: the first five copies are in flight while the bounds are counted
    wrap = (lambda x: x) if host else jnp.asarray
    with obs.span("windows.place", cat="build", host=host):
        placed = dict(
            rows=wrap(rows.reshape(w_inst, length)),
            lcols=wrap(lcols2),
            vals=wrap(vals.reshape(w_inst, length)),
            inst2win=wrap(inst2win),
            iota=wrap(np.arange(window, dtype=np.int32)),
        )
    with obs.span("windows.bounds", cat="build"):
        bounds = _instance_bounds(lcols2, window)
    with obs.span("windows.place", cat="build", host=host):
        return ColumnWindows(**placed, bounds=wrap(bounds))


def _instance_bounds(lcols2: np.ndarray, window: int) -> np.ndarray:
    """[W_inst, w+1] exclusive prefix counts per local column, chunked so
    the combined-index temporary stays ~128 MB at config-3 scale."""
    w_inst, length = lcols2.shape
    bounds = np.zeros((w_inst, window + 1), dtype=np.int32)
    step = max(1, (1 << 24) // max(length, 1))
    for i0 in range(0, w_inst, step):
        blk = lcols2[i0 : i0 + step].astype(np.int64)
        k_blk = blk.shape[0]
        comb = blk + np.arange(k_blk, dtype=np.int64)[:, None] * window
        c2 = np.bincount(
            comb.ravel(), minlength=k_blk * window
        ).reshape(k_blk, window)
        bounds[i0 : i0 + k_blk, 1:] = np.cumsum(c2, axis=1)
    return bounds


# ---------------------------------------------------------------------------
# the rmatvec over a layout
# ---------------------------------------------------------------------------


def _combine(out_inst: Array, windows: ColumnWindows, dim: int) -> Array:
    """[W_inst, w] instance partials → [dim] gradient slice."""
    w = windows.window
    num_windows = max(1, -(-dim // w))
    with scope("photon.rmatvec.combine"):
        per_win = jax.ops.segment_sum(
            out_inst,
            windows.inst2win,
            num_segments=num_windows,
            indices_are_sorted=True,
        )
        return per_win.reshape(-1)[:dim]


def _over_instances(
    windows: ColumnWindows, per_row: Array, consumer, *streams: Array
) -> Array:
    """``consumer(contrib, *blocks)`` over the layout, ``contrib`` being
    vals · r[rows] — the gather-side product (padding rows hit r[0] with
    value 0, contributing nothing) — and ``blocks`` the same instances of
    each [W_inst, ·] array in ``streams``.

    This gather, not the scatter, is the floor of the windowed rmatvec,
    and what it costs is where its fetched rows land (ops/gather's module
    docstring). So where the layout's fetched rows pass one segment the
    backward pass runs the segment loop here, on the two block sizes of
    ``_backward_cut``. A segment is a block of whole instances sized by
    fast memory (``segment_plan``); its body fetches ``r[rows]``, selects
    and multiplies by ``vals``. ``consumer`` gets the [instances, L]
    contributions of ``group`` consecutive segments at once, a block sized
    by the lanes (at least 128 instances), and the loop stacks what it
    returns (``gather.map_segment_groups``; the build pads the instance
    count to whole consumer blocks). With ``group`` 1 the consumer sits in
    the segment's body. One segment, or the plain gather: ``consumer`` gets
    the whole layout at once."""
    from photon_tpu.ops import gather

    plan, group = _backward_cut(*windows.rows.shape, per_row.dtype.itemsize)
    if plan.steps == 1 or not gather.fetches_rows():
        contrib = windows.vals * gather.take_1d(per_row, windows.rows)
        return consumer(contrib, *streams)
    t2 = gather.lane_rows(per_row)

    def contributions(rows, vals):
        return vals * gather.fetch_select(t2, rows)

    return gather.map_segment_groups(
        contributions,
        consumer,
        (windows.rows, windows.vals),
        streams,
        plan,
        group,
    )


def rmatvec_windows_prefix(
    windows: ColumnWindows, per_row: Array, dim: int
) -> Array:
    """Prefix-sum rmatvec: within an instance lcols are NON-DECREASING (the
    build sorts by column), so the per-column sums are differences of the
    contribution prefix sum at build-time-static boundaries — a cumsum plus
    a [W_inst, w+1] gather. Fully dense, no scatter, no custom kernel: the
    lowering-proof TPU path. The algebra (``_prefix_partials``) runs per
    block of at least 128 instances inside the gather's segment loop, so
    what is stacked is [W_inst, w], not the [W_inst, L] contributions."""
    out_inst = _over_instances(
        windows, per_row, _prefix_partials, windows.bounds
    )
    return _combine(out_inst, windows, dim)


def _prefix_partials(contrib: Array, bounds: Array) -> Array:
    """[I, L] contributions and their [I, w+1] bounds → [I, w] column sums
    of those instances (rows are independent, so any block of whole
    instances gives the same numbers as the whole layout)."""
    # Mean-centering bounds the f32 cumsum drift: a segment sum becomes the
    # difference of two prefixes, whose rounding error scales with |prefix|.
    # For biased contributions (the variance path's d2 > 0) the raw prefix
    # grows linearly in L; centered, it grows ~√L. The exact correction
    # μ·count uses the static per-column counts (bounds differences).
    with scope("photon.rmatvec.prefix"):
        mu = jnp.mean(contrib, axis=1, keepdims=True)
        s = jnp.cumsum(contrib - mu, axis=1)
        s = jnp.concatenate(
            [jnp.zeros((s.shape[0], 1), s.dtype), s], axis=1
        )
    with scope("photon.rmatvec.bounds"):
        g = jnp.take_along_axis(s, bounds, axis=1)
        counts = (bounds[:, 1:] - bounds[:, :-1]).astype(contrib.dtype)
        return g[:, 1:] - g[:, :-1] + mu * counts


def windows_pay(num_features: int) -> bool:
    """The layout is worth its host-side sort and ~1.5× extra device memory
    only where the scatter cliff exists, a TPU, and at high dim."""
    return target.platform() == "tpu" and num_features >= 1024


def maybe_build_windows(
    indices: np.ndarray,
    values: np.ndarray,
    num_features: int,
    *,
    host: bool = False,
):
    """The layout where :func:`windows_pay`, else ``None``. Pass
    ``host=True`` when the result will be mesh-sharded
    (parallel/sparse.shard_windows) so the stream never lands whole on one
    device."""
    if jax.process_count() > 1:
        # multi-controller placement of the instance-sharded layout needs a
        # make_array_from_callback path (parallel/sparse.shard_windows uses
        # single-controller device_put); until that exists the sharded ELL
        # segment_sum path is the multi-host story
        return None
    if not windows_pay(num_features):
        return None
    return build_column_windows(indices, values, num_features, host=host)
