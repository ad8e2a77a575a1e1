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

**A table of rows** (:func:`pack_table`, :func:`fetch_select_rows`;
PERF.md §6, PR 40). The random effects' rescoring gathers ``[rows, d]``
out of an ``[entities, d]`` coefficient table, d = 16. Left to the
compiler the d coefficients of an entity lie on the 128 lanes, so the
table is 8 x its bytes and a gathered row 512 B: a table past ~14 MB of
that (27 279 entities read 1.8 ns a row, 65 537 and 262 145 read 9.9,
2 097 153 read 22.1) leaves fast memory and the gather runs at the
serialized element rate. Viewed ``128 // d`` entities to a lane row the
table is its own bytes, the fetch is the 128-lane row fetch above (1.5 ns
a row) and the select keeps an entity's d lanes by a ``where`` over the
row's entities and a sum over them. Read on the v5e, the whole rescoring
of a [2^23, 16] block (fetch, select, dot), ms, plain against packed, at
packed tables of 1.7 / 4.2 / 16.8 / 33.6 / 67.1 / 134 MB: plain 36.3 /
104.3 / 104.8 / 53.7 / 53.5 / 188.0; packed in segments of 131 072 rows
26.1 / 26.3 / 27.1 / 27.5 / 36.9 / 85.1. Up to 33.6 MB the table and
the fetched block stay in fast memory and a row costs the same; past it
the compiler reads the table from HBM (it does NOT stage it per step, as
PR 30's loop did). ``_PACKED_TABLE_BYTES`` = 2^26 is the ceiling of the
flat part. The packed fetch was ahead at every size read, 134 MB
included (10.2 against 22.5 ns a row): what lies past the constant is
left plain because packing such a table holds 8 x its bytes in
temporaries (1.2 GB at 134 MB), not because the loop loses there. The
rescoring's segments are SMALLER than a sparse pass's: the compiler turns
the fetched block rows-to-lanes for the select (the feature block lies
with its rows on the lanes), so a row holds two lane rows, and the caller
plans four to a row: at 32 768 and at 65 536 rows a segment both copies
stay in fast memory (20.0 / 20.1 / 20.4 and 19.5 / 19.7 / 20.5 ms at the
first three tables), at 131 072 the second goes to HBM (26-27 ms). At
32 768 (and 131 072) rows the compiled dot adds a row's 16 products in
the plain path's order and the scores are ``coefs[slot]``'s to the last
bit; at 65 536 the compiler picked another order.

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
    "fetch_select_rows",
    "fetches_rows",
    "lane_rows",
    "map_segment_groups",
    "map_segments",
    "pack_table",
    "packed_table_bytes",
    "packs_table",
    "segment_plan",
    "take_1d",
]

#: bytes of fetched rows (slots × 128 lanes × itemsize) in one segment, from
#: the curve on the chip (PERF.md §6, PR 30): at 2²⁶ the v5e's compiler keeps
#: the table AND the block in fast memory (the block goes out to HBM past
#: ~88 MB, the table is staged per step under ~45 MB), and the loop's steps
#: are as few as that allows (each costs ~20 µs of control)
_SEG_BYTES = 1 << 26

#: bytes of a packed coefficient table (:func:`pack_table`) up to which a
#: rescoring fetches lane rows from it, from the reading on the chip (module
#: docstring; PERF.md §6, PR 40): the largest table read that the v5e's
#: compiler keeps in fast memory is 33.6 MB (3.4 ns a row, as at 1.7 MB), the
#: smallest that it does not 67.1 MB (4.5 ns)
_PACKED_TABLE_BYTES = 1 << 26


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


def packed_table_bytes(entities: int, d: int, itemsize: int) -> int:
    """Bytes of an [entities, d] table viewed ``128 // d`` entities to a
    128-lane row (:func:`pack_table`); 0 where ``d`` does not divide 128
    and the table has no such view."""
    if d < 1 or 128 % d:
        return 0
    return -(-entities // (128 // d)) * 128 * itemsize


def packs_table(entities: int, d: int, itemsize: int) -> bool:
    """Whether ``table[slot]`` of an [entities, d] table is the packed row
    fetch (:func:`fetch_select_rows`) in the program being traced: a
    program for a TPU, a width that divides 128, and a packed table the
    compiler keeps in fast memory (``_PACKED_TABLE_BYTES``). Shapes and the
    platform in, nothing else."""
    return (
        fetches_rows()
        and 0 < packed_table_bytes(entities, d, itemsize) <= _PACKED_TABLE_BYTES
    )


def pack_table(table: Array) -> Array:
    """The [entities, d] table as [rows, 128], ``128 // d`` entities to a
    lane row, the last row filled up with zero entities: the row-major
    bytes of the table (the compiler keeps an [entities, 16] array with the
    entities on the lanes and relays it: 0.3 ms at 65 537 entities)."""
    e, d = table.shape
    pad = -e % (128 // d)
    if pad:
        table = jnp.concatenate([table, jnp.zeros((pad, d), table.dtype)])
    return table.reshape(-1, 128)


def fetch_select_rows(t2: Array, slot: Array, d: int) -> Array:
    """``table[slot]``, [slot.size, d], for one block of slots, ``t2 =
    pack_table(table)``: the 128-lane row that holds the entity is fetched
    (:func:`fetch_select`'s fetch) and the entity's ``d`` lanes are kept by
    a ``where`` over the row's ``128 // d`` entities and a sum over them:
    one nonzero term a sum, so every coefficient is the table's bit for
    bit, and a non-finite entity does not reach the entities beside it."""
    per = 128 // d
    flat = slot.reshape(-1)
    entity_iota = jax.lax.broadcasted_iota(jnp.int32, (1, per, 1), 1)
    with scope("photon.gather"):
        with scope("photon.gather.fetch"):
            # d divides 128: per is a power of two
            rows = t2[flat >> (per.bit_length() - 1)]
        with scope("photon.gather.select"):
            sel = (flat & (per - 1))[:, None, None] == entity_iota
            return jnp.sum(jnp.where(sel, rows.reshape(-1, per, d), 0), axis=1)


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
