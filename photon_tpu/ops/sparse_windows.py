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

The fix is a build-time layout + an MXU trick:

- **Build** (host, once — indices are static across every objective
  evaluation of a solve): sort the (row, col, val) triples by column and
  bucket them into windows of ``window`` consecutive columns. Pad each
  window to a common length L. Windows whose load exceeds L **spill** into
  multiple instances mapped to the same output range — essential under
  real feature skew (an intercept column alone holds N entries).
- **Scatter → one-hot matmul**: within an instance, Xᵀr restricted to its
  w columns is ``contribᵀ · onehot(local_cols)`` — a [1,L]×[L,w] matmul.
  The Pallas kernel generates the one-hot **in VMEM** (never in HBM) and
  feeds the MXU, so HBM traffic is just the (row, lcol, val) stream. A
  pure-XLA ``lax.scan`` fallback computes the identical algebra for
  CPU/debug, and a flat pre-sorted ``segment_sum`` variant exists for
  comparison (padding uses local col w−1 so flat indices stay sorted).
- **The gather side is the floor**: contrib = vals · r[rows] is a
  1-element gather from a [N] vector, which XLA:TPU serializes; it goes
  through ops/gather's row fetch and lane select and is 75 % of a
  backward pass (PERF.md §5). ``_over_instances`` runs it in the segment
  loop of the pass: a segment is a block of whole instances whose fetched
  rows fit fast memory (``gather.segment_plan``: 32 instances of 4096
  slots), the loop's body carries the variant's consumer (the prefix
  variant's centring, cumsum and bounds reads, [I, L] → [I, w]), and the
  build pads the instance count to whole segments, so [W_inst, L] is
  read as it lies and no pass pads, slices or stacks a value per slot.

Instance partials combine with one [W_inst, w] → [W, w] sorted
segment-sum (thousands of rows, not millions — off the cliff).

Sharded batches (parallel/mesh.shard_batch) intentionally drop the
windows: under plain GSPMD row-sharding the scan/Pallas variants do not
partition, and the per-shard scatter is back on the segment_sum path.
The multi-chip windowed path lives in ``parallel/sparse.py`` instead —
window instances sharded explicitly over the mesh with ``shard_map``
(column-range partials + one psum), reusing this module's kernels
per shard.

- **Prefix-sum variant**: within an instance the local columns are
  non-decreasing (column sort), so per-column sums are differences of the
  contribution cumsum at build-time-static boundaries (``bounds``) — a
  fully dense gather-only path with no scatter and no custom kernel.

Selection: ``PHOTON_SPARSE_RMATVEC`` = auto (default) | prefix | pallas |
onehot | flat | segment. AUTO → prefix on TPU, onehot elsewhere.
"""
from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu import obs
from photon_tpu.obs.scopes import scope
from photon_tpu.types import Array

_ENV = "PHOTON_SPARSE_RMATVEC"

#: the TPU sublane rule: the second-to-last dim of a block is a multiple of 8
_SUBLANES = 8


class ColumnWindows(NamedTuple):
    """Static column-sorted instance layout (see module docstring).

    rows/lcols/vals: [W_inst, L]; ``inst2win``: [W_inst] window id per
    instance (non-decreasing); ``iota``: [w] = arange(window) — carried as
    an array so the window width rides a static *shape* through jit (an int
    leaf would be traced away) and doubles as the one-hot compare operand.
    ``bounds``: [W_inst, w+1] exclusive prefix counts per local column
    (bounds[i, c] = #slots in instance i with lcol < c) — static segment
    boundaries for the prefix-sum rmatvec; ``None`` on layouts built before
    the field existed. Padding slots: row 0, local col w−1, value 0.
    W_inst is padded at build time (inert instances) to a multiple of 8, so
    the Pallas block shape (8, L) satisfies the TPU sublane rule, and of
    the instances per segment of the backward pass (``instance_multiple``).
    """

    rows: Array
    lcols: Array
    vals: Array
    inst2win: Array
    iota: Array
    bounds: Array | None = None

    @property
    def window(self) -> int:
        return self.iota.shape[0]

    @property
    def instance_len(self) -> int:
        return self.rows.shape[1]


def instance_multiple(w_inst: int, length: int, itemsize: int) -> int:
    """What a layout's instance count is padded to a multiple of: the
    instances in a segment of the backward pass (ops/gather.segment_plan
    for a per-row table of ``itemsize``-byte entries), or 8 where the pass
    is one segment."""
    from photon_tpu.ops.gather import segment_plan

    w_inst += (-w_inst) % _SUBLANES
    plan = segment_plan(w_inst, length, itemsize, _SUBLANES)
    return _SUBLANES if plan.steps == 1 else plan.per


def _native_histogram(arr_idx, arr_val, num_features):
    """Per-column nonzero histogram via the C++ counting-sort builder
    (native/window_builder.cpp) — O(nnz + d) vs numpy's comparison argsort.
    Returns (col_counts, nnz) or None when the fast path does not apply
    (non-f32 values, library unavailable)."""
    if os.environ.get("PHOTON_NATIVE_WINDOWS", "1").strip().lower() in (
        "0",
        "off",
        "never",
    ):
        return None
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
    to a multiple of ``chunk`` (the kernel's VMEM one-hot chunk) or to 8
    for small layouts. ``host=True`` keeps the result as numpy — for mesh
    placement, where materializing the whole stream on one device first
    would be the exact single-device footprint the sharding avoids.
    """
    with obs.span("windows.build", cat="build", num_features=num_features):
        return _build_column_windows(
            indices, values, num_features, window, instance_cap, chunk, host
        )


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
    # instances carry zero padding — mid-stream padding (local col w−1
    # between two instances of the same window) would break the sorted
    # invariant rmatvec_windows_flat promises to XLA.
    cap = int(min(counts.max() if nnz else 1, instance_cap))
    if cap > chunk:
        cap = -(-cap // chunk) * chunk
    else:
        cap = max(8, -(-cap // 8) * 8)
    length = cap
    n_inst = np.maximum(1, -(-counts // cap))
    w_inst = int(n_inst.sum())
    # Round the instance count with inert instances (vals 0 / lcol w−1 /
    # last window id) to a multiple of 8, so the Pallas kernel's (8, L)
    # block shape meets the TPU sublane-divisibility rule for any layout,
    # and of the backward pass's instances per segment, so that its loop
    # runs over whole segments and no pass pads or slices the streams.
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
# rmatvec implementations (identical algebra, different lowering)
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

    This gather, not the scatter, is the floor of every windowed rmatvec
    variant, and what it costs is where its fetched rows land (ops/gather's
    module docstring). So where the layout's fetched rows pass one segment
    the backward pass runs the segment loop here: a segment is a block of
    whole instances (``segment_plan``; the build pads the instance count to
    a multiple of it), its body fetches ``r[rows]``, selects, multiplies by
    ``vals`` and hands the [instances, L] block to ``consumer``, and the
    loop stacks what the consumer returns. One segment, or the plain
    gather: ``consumer`` gets the whole layout at once, as before."""
    from photon_tpu.ops import gather

    plan = gather.segment_plan(
        *windows.rows.shape, per_row.dtype.itemsize, _SUBLANES
    )
    if plan.steps == 1 or gather.gather_strategy(per_row) != "chunked":
        contrib = windows.vals * gather.take_1d(per_row, windows.rows)
        return consumer(contrib, *streams)
    t2 = gather.lane_rows(per_row)

    def instances_block(rows, vals, *blocks):
        return consumer(vals * gather.fetch_select(t2, rows), *blocks)

    return gather.map_segments(
        instances_block, (windows.rows, windows.vals, *streams), plan, axis=0
    )


def _contrib(windows: ColumnWindows, per_row: Array) -> Array:
    """[W_inst, L] contributions, one per slot, for the variants that
    consume them whole (flat, pallas)."""
    return _over_instances(windows, per_row, lambda contrib: contrib)


def rmatvec_windows_flat(
    windows: ColumnWindows, per_row: Array, dim: int
) -> Array:
    """Pre-sorted flat segment_sum: padding local col w−1 keeps global
    indices non-decreasing, so XLA sees ``indices_are_sorted``."""
    w = windows.window
    gcols = (windows.lcols + windows.inst2win[:, None] * w).reshape(-1)
    num_windows = max(1, -(-dim // w))
    out = jax.ops.segment_sum(
        _contrib(windows, per_row).reshape(-1),
        gcols,
        num_segments=num_windows * w,
        indices_are_sorted=True,
    )
    return out[:dim]


def rmatvec_windows_onehot(
    windows: ColumnWindows, per_row: Array, dim: int
) -> Array:
    """Pure-XLA one-hot matmul, scanned one instance at a time (the scan
    keeps the [L, w] one-hot a fused per-step intermediate instead of a
    materialized [W_inst, L, w] monster)."""
    iota = windows.iota

    def body(_, xs):
        rows, lcols, vals = xs
        cb = vals * per_row[rows]
        onehot = (lcols[:, None] == iota[None, :]).astype(cb.dtype)
        return None, cb @ onehot

    _, out_inst = jax.lax.scan(
        body, None, (windows.rows, windows.lcols, windows.vals)
    )
    return _combine(out_inst, windows, dim)


def rmatvec_windows_prefix(
    windows: ColumnWindows, per_row: Array, dim: int
) -> Array:
    """Prefix-sum rmatvec: within an instance lcols are NON-DECREASING (the
    build sorts by column), so the per-column sums are differences of the
    contribution prefix sum at build-time-static boundaries — a cumsum plus
    a [W_inst, w+1] gather. Fully dense, no scatter, no custom kernel: the
    lowering-proof TPU path. The algebra (``_prefix_partials``) runs per
    block of instances inside the gather's segment loop, so what is stacked
    is [W_inst, w], not the [W_inst, L] contributions."""
    if windows.bounds is None:
        return rmatvec_windows_onehot(windows, per_row, dim)
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


#: instances per Pallas grid step — the TPU sublane rule requires the
#: second-to-last block dim be a multiple of 8 (block (1, L) fails to lower)
_PALLAS_BLK = _SUBLANES


def _pallas_kernel_factory(length: int, w: int, chunk: int):
    from jax.experimental import pallas as pl

    steps = max(1, length // chunk)

    def kernel(contrib_ref, lcols_ref, out_ref):
        for i in range(_PALLAS_BLK):

            def body(j, acc, i=i):  # bind: fori_loop runs within this i
                cb = contrib_ref[i, pl.ds(j * chunk, chunk)].astype(
                    jnp.float32
                )
                lc = lcols_ref[i, pl.ds(j * chunk, chunk)]
                onehot = (
                    lc[:, None]
                    == jax.lax.broadcasted_iota(jnp.int32, (chunk, w), 1)
                ).astype(jnp.float32)
                return acc + jnp.dot(
                    cb[None, :], onehot, preferred_element_type=jnp.float32
                )

            acc = jax.lax.fori_loop(
                0, steps, body, jnp.zeros((1, w), jnp.float32)
            )
            out_ref[i, :] = acc[0]

    return kernel


def rmatvec_windows_pallas(
    windows: ColumnWindows,
    per_row: Array,
    dim: int,
    *,
    interpret: bool = False,
) -> Array:
    """Pallas kernel: one grid step per instance; the one-hot lives only in
    VMEM and the multiply-accumulate runs on the MXU. HBM traffic is the
    (lcol, contrib) stream — the layout's point."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_inst, length = windows.rows.shape
    w = windows.window
    # The (blk=8, L) block residency is 8× the old (1, L) blocks: two
    # [8, L] 4-byte operands must fit VMEM alongside the [chunk, w] one-hot.
    # Past ~2^17 slots/instance (≈8 MB of operands) a real-TPU launch would
    # die in Mosaic with a VMEM error; fail loudly instead of silently
    # measuring a different implementation (interpret mode has no VMEM
    # limit and proceeds).
    if length * _PALLAS_BLK > (1 << 20) and not interpret:
        raise ValueError(
            f"pallas rmatvec: instance length {length} × {_PALLAS_BLK} "
            "sublanes exceeds the VMEM block budget; lower "
            "PHOTON_SPARSE_WINDOW_CAP or select "
            "PHOTON_SPARSE_RMATVEC=prefix"
        )
    # chunk must DIVIDE the instance length or the fori_loop drops the tail
    # (build rounds length to a multiple of its chunk arg, which need not be
    # this kernel's 1024 default) — pick the largest aligned divisor.
    chunk = length
    if length > 1024:
        for c in (1024, 512, 256, 128, 64, 32, 16, 8):
            if length % c == 0:
                chunk = c
                break
        else:
            # No aligned divisor (custom build chunk not a multiple of 8).
            # chunk=length would put a (length, w) one-hot in VMEM — fine
            # for modest lengths, a Mosaic VMEM blowup for big ones. Say
            # so: a caller who selected this kernel must not silently
            # measure another implementation.
            if length > 4096:
                raise ValueError(
                    f"pallas rmatvec: instance length {length} has no "
                    "divisor that is a multiple of 8; build the layout "
                    "with a chunk that is, or select "
                    "PHOTON_SPARSE_RMATVEC=prefix"
                )
    # f32 accumulation: the MXU path is TPU-only, where x64 is unsupported
    contrib = _contrib(windows, per_row).astype(jnp.float32)
    lcols = windows.lcols
    blk = _PALLAS_BLK
    pad = (-w_inst) % blk
    if pad:  # layouts from before the build-time 8-padding
        contrib = jnp.pad(contrib, ((0, pad), (0, 0)))
        lcols = jnp.pad(lcols, ((0, pad), (0, 0)), constant_values=w - 1)

    out_inst = pl.pallas_call(
        _pallas_kernel_factory(length, w, chunk),
        out_shape=jax.ShapeDtypeStruct((w_inst + pad, w), jnp.float32),
        grid=((w_inst + pad) // blk,),
        in_specs=[
            pl.BlockSpec(
                (blk, length), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (blk, length), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (blk, w), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(contrib, lcols)
    return _combine(out_inst[:w_inst], windows, dim)


def _env_int(name: str, default: int, *, lo: int, hi: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError as e:
        raise ValueError(f"{name}={raw!r} is not an integer") from e
    if not lo <= v <= hi:
        raise ValueError(f"{name}={v} outside [{lo}, {hi}]")
    return v


def maybe_build_windows(
    indices: np.ndarray,
    values: np.ndarray,
    num_features: int,
    *,
    host: bool = False,
):
    """Policy gate for the layout build: windows are worth their host-side
    sort + ~1.5× extra device memory only on TPU (where the scatter cliff
    exists) at high dim. ``PHOTON_SPARSE_WINDOWS`` = auto (default) | 1 | 0.
    Pass ``host=True`` when the result will be mesh-sharded
    (parallel/sparse.shard_windows) so the stream never lands whole on one
    device."""
    flag = os.environ.get("PHOTON_SPARSE_WINDOWS", "auto").strip().lower()
    if flag in ("0", "off", "never"):
        return None
    if jax.process_count() > 1:
        # multi-controller placement of the instance-sharded layout needs a
        # make_array_from_callback path (parallel/sparse.shard_windows uses
        # single-controller device_put); until that exists the sharded ELL
        # segment_sum path is the multi-host story
        return None
    if flag in ("1", "on", "always") or (
        jax.default_backend() == "tpu" and num_features >= 1024
    ):
        # tuning knobs (kernel-shape tradeoff: wider windows → fewer grid
        # steps but more one-hot compares; see PERF.md). Deliberately NOT
        # named PHOTON_SPARSE_WINDOW: one dropped character from the on/off
        # flag PHOTON_SPARSE_WINDOWS must not silently become a width of 1.
        window = _env_int("PHOTON_SPARSE_WINDOW_WIDTH", 128, lo=8, hi=8192)
        cap = _env_int("PHOTON_SPARSE_WINDOW_CAP", 4096, lo=64, hi=1 << 20)
        return build_column_windows(
            indices,
            values,
            num_features,
            window=window,
            instance_cap=cap,
            host=host,
        )
    return None


def windowed_rmatvec(
    windows: ColumnWindows, per_row: Array, dim: int
) -> Array:
    """Implementation dispatch (trace-time; see module docstring)."""
    impl = os.environ.get(_ENV, "auto").strip().lower()
    if impl == "auto":
        if jax.default_backend() == "tpu":
            # r4 on-chip measurement (PERF.md): prefix-sum beats the
            # one-hot kernels and every segment_sum lowering at config-3
            # scale; layouts without bounds fall back inside prefix.
            impl = "prefix"
        else:
            impl = "onehot"
    if impl == "prefix":
        return rmatvec_windows_prefix(windows, per_row, dim)
    if impl == "pallas":
        return rmatvec_windows_pallas(windows, per_row, dim)
    if impl == "onehot":
        return rmatvec_windows_onehot(windows, per_row, dim)
    if impl == "flat":
        return rmatvec_windows_flat(windows, per_row, dim)
    raise ValueError(f"unknown {_ENV}={impl!r}")
