"""1-D table gather for XLA:TPU, and the segment loop a sparse pass runs it in.

Reference parity: the gathers here implement the same per-datum feature
lookups the reference's aggregators stream row-by-row on CPU executors
(photon-lib function/glm/ValueAndGradientAggregator.scala:119-247); on
TPU the lookup itself is the bottleneck, not the FLOPs.

**The gather.** XLA:TPU's 1-element gather is serialized (PERF.md §6,
PR 30, has this jaxlib's rate). :func:`fetch_select` views the table as
[rows, 128] lanes, FETCHES the whole 128-lane row each element lives in
(``photon.gather.fetch``) and SELECTS the element's lane with a ``where``
and a sum over the lanes (``photon.gather.select``): exact, one nonzero
term per sum, so the result is bit-identical to ``table[idx]``, and a
non-finite entry does not reach its 127 neighbours.

**Where the time goes** (v5e, 235 M slots a pass; PERF.md §6, PR 30).
The compiler copies a table of a few MB into fast memory once per pass,
so the fetch reads no HBM. Its OUTPUT is 128·itemsize bytes a slot. While
a segment's block of fetched rows fits fast memory the compiler keeps it
there (memory space 1 in the compiled text) and the fetch costs 0.355 s a
pass; a block too large for it goes out to HBM and the fetch costs 0.42 s;
a block under ~45 MB makes the compiler stage the TABLE from HBM on every
step instead (0.38-0.46 s, and 2 s with a 16 MB table). The select costs
0.187-0.197 s wherever the block lies: it is bound by its sum across the
lanes, one per slot, not by bytes. What the 2³⁰ B segments of PR 29 cost
besides was the walls around the loop: the gather took a flat index
stream and handed back a value per slot, so every pass relaid, padded and
stacked whole streams (0.10 s) and held 4.3 GB of temporaries.

**The loop.** So a pass is cut into segments of ``_SEG_BYTES`` of fetched
rows, and the PASS owns the loop (:func:`map_segments`): a segment's body
holds the pass's consumer, so the loop stacks what the consumer emits, not
a value per slot, and the streams are sliced in the shape they are stored
in, with no per-pass relayout. Forward (``ops/objective``) the body is
:func:`fetch_select_dot`: the K slots of a row are summed lane by lane
before the one sum across lanes, which takes the select from 0.187 to
0.132 s. Backward (``ops/sparse_windows``) it reduces a block of window
instances to its column sums. :func:`segment_plan` derives the units per
segment from ``_SEG_BYTES``, the table's itemsize and the tiling rule of
the sliced axis; where it says one segment there is no loop at all (the
per-entity solves under ``vmap``, the scorer's batches).

**Two block sizes in the one loop** (:func:`map_segment_groups`; PERF.md
§6, PR 35). The backward consumer's prefix sums want the window instances
on the 128 lanes, and a segment that fast memory sizes holds 32: on
[32, 4096] the compiler scans ACROSS the lanes (0.090 s a pass), on
[128, 4096] it lays the instances minor and the scan is plain vector adds
(0.0245 s). So the fetch and the select stay on segments, a buffer carried
through the loop collects what ``group`` consecutive segments emit (2 MB,
in fast memory), and the consumer runs on it once a group, under a
``lax.cond`` in the same loop: a backward pass 0.7312 → 0.6710 s. It is
ONE loop because the other forms lose the table: a loop of consumer blocks
around the loop of segments makes the table an HBM operand of the fetch
(compiled text, PR 35), and a 128-instance segment puts the fetched block
in HBM. Blocks of 256 and 512 instances read 0.6723 and 0.6802 s: the
least group that fills the lanes is the one to take.

Which gather a program gets is a fact about its platform
(:func:`fetches_rows`): the row fetch in a program for a TPU, the plain
``table[idx]`` elsewhere (CPU's native gather is faster than the 128x
traffic blow-up).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from photon_tpu.obs.scopes import scope
from photon_tpu.types import Array
from photon_tpu.util import target

__all__ = [
    "SegmentPlan",
    "chunked_take",
    "fetch_select",
    "fetch_select_dot",
    "fetches_rows",
    "lane_rows",
    "map_segment_groups",
    "map_segments",
    "segment_plan",
    "take_1d",
]

#: bytes of fetched rows (slots × 128 lanes × itemsize) in one segment, from
#: the curve on the chip (PERF.md §6, PR 30): at 2²⁶ the v5e's compiler keeps
#: the table AND the block in fast memory (the block goes out to HBM past
#: ~88 MB, the table is staged per step under ~45 MB), and the loop's steps
#: are as few as that allows (each costs ~20 µs of control)
_SEG_BYTES = 1 << 26


class SegmentPlan(NamedTuple):
    """How a pass over ``units`` is cut: ``segments`` loop steps of ``per``
    units each, then ``tail`` units (fewer than ``per``) outside the loop.
    ``segments == 1`` and ``tail == 0``: one block, no loop."""

    segments: int
    per: int
    tail: int

    @property
    def steps(self) -> int:
        """Bodies a pass runs: the loop's steps and the tail's one."""
        return self.segments + (1 if self.tail else 0)


def segment_plan(
    units: int, slots_per_unit: int, itemsize: int, align: int
) -> SegmentPlan:
    """The cut of a pass whose unit (a row with its K slots, a window
    instance with its L slots, one slot of a flat stream) fetches
    ``slots_per_unit`` 128-lane rows of ``itemsize``-byte entries: as many
    units to a segment as ``_SEG_BYTES`` of fetched rows hold, rounded
    down to a multiple of ``align`` (128 where the sliced axis lies on the
    lanes, 8 on the sublanes) and never under ``align``. Pure: shapes in,
    three integers out."""
    per_unit = slots_per_unit * 128 * itemsize
    if units * per_unit <= _SEG_BYTES:
        return SegmentPlan(1, units, 0)
    per = max(align, _SEG_BYTES // per_unit // align * align)
    if per >= units:
        return SegmentPlan(1, units, 0)
    return SegmentPlan(units // per, per, units % per)


def lane_rows(table: Array) -> Array:
    """The 1-D table as [rows, 128], zero-padded to whole rows."""
    (d,) = table.shape
    n_rows = -(-d // 128)
    padded = jnp.zeros((n_rows * 128,), table.dtype).at[:d].set(table)
    return padded.reshape(n_rows, 128)


def fetch_select(t2: Array, idx: Array) -> Array:
    """``table[idx]`` for one block of indices, ``t2 = lane_rows(table)``:
    the row fetch and the lane select, each under its own scope. The
    fetched [idx.size, 128] block is the intermediate that ``_SEG_BYTES``
    sizes; callers hand in a segment's block, not a whole stream."""
    flat = idx.reshape(-1)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    with scope("photon.gather"):
        with scope("photon.gather.fetch"):
            rows = t2[flat >> 7]
        with scope("photon.gather.select"):
            sel = (flat & 127)[:, None] == lane_iota
            out = jnp.sum(jnp.where(sel, rows, 0), axis=1)
    return out.reshape(idx.shape)


def fetch_select_dot(t2: Array, idx: Array, weights: Array) -> Array:
    """Σₖ weights[k, r] · table[idx[k, r]] over a [K, R] block: the forward
    pass's body. The fetch is :func:`fetch_select`'s. The select takes the
    K slots of a row together: ``where`` keeps each slot's lane of its
    fetched row, the slot's weight scales it, and the K masked rows are
    summed lane by lane BEFORE the one sum across lanes, so a row costs
    one cross-lane reduction and not K (0.187 → 0.132 s a pass at the
    benchmark's sparse cell, PERF.md §6, PR 30). The barrier holds that
    order: without it the compiler reduces across the lanes first. The K
    products of a row are the same; the order they are added in moves the
    last bits."""
    k, r = idx.shape
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 128), 2)
    with scope("photon.gather"):
        with scope("photon.gather.fetch"):
            rows = t2[idx.reshape(-1) >> 7].reshape(k, r, 128)
        with scope("photon.gather.select"):
            sel = (idx & 127)[..., None] == lane_iota
            by_lane = jnp.sum(
                jnp.where(sel, rows, 0) * weights[..., None], axis=0
            )
            return jnp.sum(jax.lax.optimization_barrier(by_lane), axis=1)


def map_segments(
    body: Callable[..., Array],
    streams: Sequence[Array],
    plan: SegmentPlan,
    axis: int,
) -> Array:
    """``body(*blocks)`` over every segment of ``plan``, a block being the
    segment's ``plan.per`` units of each stream along ``axis`` (a dynamic
    slice of the stream as it is stored: no stream is reshaped, padded or
    copied). ``body`` returns an array whose leading axis is the unit axis;
    the results come back concatenated along it. The ``plan.tail`` units
    after the last whole segment go through ``body`` once, by a static
    slice."""
    if plan.steps == 1:
        return body(*streams)
    segs, per, tail = plan

    def one(i):
        return body(
            *(
                jax.lax.dynamic_slice_in_dim(s, i * per, per, axis)
                for s in streams
            )
        )

    out = jax.lax.map(one, jnp.arange(segs, dtype=jnp.int32))
    out = out.reshape((segs * per,) + out.shape[2:])
    if tail:
        lo = segs * per
        rest = body(
            *(jax.lax.slice_in_dim(s, lo, lo + tail, axis=axis) for s in streams)
        )
        out = jnp.concatenate([out, rest])
    return out


def map_segment_groups(
    body: Callable[..., Array],
    consumer: Callable[..., Array],
    streams: Sequence[Array],
    consumer_streams: Sequence[Array],
    plan: SegmentPlan,
    group: int,
) -> Array:
    """``consumer(body(*blocks), *consumer_blocks)`` over the leading axis,
    with the two halves on blocks of their own size: ``body`` (the fetch
    and select) on every segment of ``plan``, ``consumer`` once per
    ``group`` consecutive segments, on their ``group * plan.per`` units
    together. Both hand back arrays whose leading axis is the unit axis,
    and every unit's result depends on that unit alone.

    ONE loop over the segments does it, not a loop in a loop (which takes
    the table out of fast memory: the module docstring): it carries a
    [group * per, ...] buffer of what ``body`` emitted and the result,
    writes its segment at ``(i % group) * per`` and runs ``consumer`` on
    the buffer under a ``lax.cond`` on every ``group``-th step. The units
    past the last whole group (none where the layout was padded to whole
    groups) go through :func:`map_segments`, a segment to a consumer
    block; so does everything where ``group`` is 1."""
    n_body = len(streams)

    def both(*blocks):
        return consumer(body(*blocks[:n_body]), *blocks[n_body:])

    everything = (*streams, *consumer_streams)
    segs, per, tail = plan
    if group == 1 or segs < group:
        return map_segments(both, everything, plan, 0)
    groups = segs // group
    block = group * per
    done = groups * block

    def segment(i, carry):
        buf, out = carry
        seg = body(
            *(jax.lax.dynamic_slice_in_dim(s, i * per, per, 0) for s in streams)
        )
        at = jax.lax.rem(i, group)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, seg, at * per, 0)

        def consume(out):
            lo = (i - at) * per
            res = consumer(
                buf,
                *(
                    jax.lax.dynamic_slice_in_dim(s, lo, block, 0)
                    for s in consumer_streams
                ),
            )
            return jax.lax.dynamic_update_slice_in_dim(out, res, lo, 0)

        return buf, jax.lax.cond(at == group - 1, consume, lambda o: o, out)

    def units(x, k):  # the shape of k units of x
        return jax.ShapeDtypeStruct((k,) + x.shape[1:], x.dtype)

    buf = units(jax.eval_shape(body, *(units(s, per) for s in streams)), block)
    out = units(
        jax.eval_shape(
            consumer, buf, *(units(s, block) for s in consumer_streams)
        ),
        done,
    )
    _, out = jax.lax.fori_loop(
        0,
        groups * group,
        segment,
        (jnp.zeros(buf.shape, buf.dtype), jnp.zeros(out.shape, out.dtype)),
    )
    left = segs * per + tail - done
    if left:
        rest = map_segments(
            both,
            [jax.lax.slice_in_dim(s, done, done + left) for s in everything],
            SegmentPlan(left // per, per, left % per),
            0,
        )
        out = jnp.concatenate([out, rest])
    return out


def chunked_take(table: Array, idx: Array) -> Array:
    """``table[idx]`` for a 1-D table and an index array of any shape via
    128-lane row fetches and a lane select, the flat index stream cut into
    segments by :func:`segment_plan`. Element-identical to the plain gather
    (the lane select uses ``where``, not multiply, so non-finite table
    entries do NOT poison their 128-lane neighbors through 0·Inf). The
    sparse passes do not come through here where they need a loop: they
    run :func:`map_segments` themselves, with their consumer in its body.

    Precondition: every index lies in [0, d). Out-of-range indices follow
    a DIFFERENT clamp than XLA's plain gather (block and lane clamp
    separately instead of the flat index), so an upstream indexing bug
    would produce backend-dependent values rather than a consistent
    clamp — all production index streams (ELL layouts, window rows) are
    built in-range by construction."""
    t2 = lane_rows(table)
    flat = idx.reshape(-1)
    # a flat stream is tiled 1024 to a row of 8 × 128
    plan = segment_plan(flat.size, 1, jnp.dtype(table.dtype).itemsize, 1024)
    out = map_segments(lambda ix: fetch_select(t2, ix), (flat,), plan, 0)
    return out.reshape(idx.shape)


def fetches_rows() -> bool:
    """Whether a 1-D gather in the program being traced is the 128-lane row
    fetch: in a program for a TPU, whose 1-element gather is serialized."""
    return target.platform() == "tpu"


def take_1d(table: Array, idx: Array) -> Array:
    """``table[idx]``: :func:`chunked_take` where :func:`fetches_rows`, the
    plain gather elsewhere."""
    if fetches_rows():
        return chunked_take(table, idx)
    with scope("photon.gather"):
        return table[idx]
