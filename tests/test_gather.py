"""chunked_take: the TPU gather-cliff workaround (ops/gather.py).

The strategy must be BIT-identical to the plain gather (one-hot lane
select multiplies by exactly one 1.0), across table sizes that do and do
not divide the 128-lane row width, and through the production routes
(ELL matvec, windowed prefix rmatvec)."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import photon_tpu.ops.gather as gather_mod
from photon_tpu.ops.gather import (
    chunked_take,
    fetch_select,
    lane_rows,
    map_segments,
    segment_plan,
    take_1d,
)
from photon_tpu.ops.objective import matvec
from photon_tpu.ops.sparse_windows import (
    build_column_windows,
    rmatvec_windows_prefix,
)
from photon_tpu.types import SparseBatch
from photon_tpu.util import target


@pytest.mark.parametrize(
    "d,shape",
    [
        (7, (5,)),              # table smaller than one lane row
        (128, (64,)),           # exactly one row
        (1000, (17, 3)),        # non-multiple of 128, 2-D indices
        (1 << 14, (257, 9)),
        ((1 << 15) + 5, (4096,)),
    ],
)
def test_chunked_take_bit_identical(d, shape):
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    ix = jnp.asarray(rng.integers(0, d, size=shape).astype(np.int32))
    assert np.array_equal(
        np.asarray(chunked_take(t, ix)), np.asarray(t[ix])
    )


def test_chunked_take_under_jit_and_grad():
    rng = np.random.default_rng(1)
    t = jnp.asarray(rng.standard_normal(300).astype(np.float32))
    ix = jnp.asarray(rng.integers(0, 300, size=(41,)).astype(np.int32))

    f = jax.jit(lambda tt: jnp.sum(chunked_take(tt, ix) ** 2))
    g = jax.grad(f)(t)
    # d/dt sum(t[ix]^2) = 2 * segment_sum(t[ix]) scattered back
    expect = np.zeros(300, np.float32)
    np.add.at(expect, np.asarray(ix), 2.0 * np.asarray(t)[np.asarray(ix)])
    np.testing.assert_allclose(np.asarray(g), expect, rtol=1e-6)


def test_segment_plan_bounds_fetch_for_any_slot_count():
    # odd counts must segment too (a [slots, 128] f32 fetch at 31M odd
    # slots is ~16 GB — past a v5e's HBM if segmentation silently bailed)
    budget = gather_mod._SEG_BYTES
    for n in [1, 8, 56 << 20, (1 << 23) * 7, 1_000_001 * 31, 3 * 5 * 7]:
        plan = segment_plan(n, 1, 4, 1024)
        assert plan.segments * plan.per + plan.tail == n
        assert max(plan.per, plan.tail) * 512 <= max(budget, n and 512)
        assert plan.tail < plan.per or plan.steps == 1


def test_segment_plan_scales_with_table_itemsize():
    # per-slot fetch is 128 lanes x itemsize: a float64 table doubles the
    # fetched bytes past a 4-byte budget (must segment ~2x more), bf16
    # halves them (must not over-segment). ADVICE r4.
    budget = gather_mod._SEG_BYTES
    for n in [56 << 20, (1 << 23) * 7, 1_000_001 * 31]:
        steps = {}
        for itemsize in (2, 4, 8):
            plan = segment_plan(n, 1, itemsize, 1024)
            assert plan.per * 128 * itemsize <= budget
            steps[itemsize] = plan.steps
        # monotone in itemsize and within rounding of proportional
        assert steps[8] >= steps[4] >= steps[2]
        assert steps[8] <= 2 * steps[4] + 1


#: (units, slots per unit, itemsize, align) of the passes PERF.md quotes, and
#: their (segments, per, tail) at _SEG_BYTES = 2^26: the benchmark's sparse
#: cell forward ([2^22, 56] ELL) and backward (58 384 instances of 4096
#: slots, padded to 58 400), chip_smoke's FE coordinate (game_ctr_scale:
#: 2^18 rows x 25 slots; 2056 instances padded to 2080), one slot, a
#: float64 table, and a per-entity block that is one segment; since PR 35
#: the two cells' layouts are padded to whole consumer blocks of 4 segments
#: (58 496 and 25 216 instances: 457 and 197 blocks)
_PLANS = {
    "cell_forward": ((1 << 22, 56, 4, 128), (1820, 2304, 1024)),
    "cell_backward": ((58400, 4096, 4, 8), (1825, 32, 0)),
    "cell_backward_blocks": ((58496, 4096, 4, 8), (1828, 32, 0)),
    "glmix_backward_blocks": ((25216, 4096, 4, 8), (788, 32, 0)),
    "game_ctr_forward": ((1 << 18, 25, 4, 128), (51, 5120, 1024)),
    "game_ctr_backward": ((2080, 4096, 4, 8), (65, 32, 0)),
    "one_slot": ((1, 1, 4, 1024), (1, 1, 0)),
    "float64_table": ((1 << 22, 56, 8, 128), (3640, 1152, 1024)),
    "fits_one_segment": ((256, 16, 4, 128), (1, 256, 0)),
}


@pytest.mark.parametrize("case", sorted(_PLANS))
def test_segment_plan_at_quoted_shapes(case):
    args, expect = _PLANS[case]
    plan = segment_plan(*args)
    assert tuple(plan) == expect
    units, slots, itemsize, align = args
    assert plan.segments * plan.per + plan.tail == units
    if plan.steps > 1:
        assert plan.per % align == 0
        # the block the compiler is to keep in fast memory
        assert plan.per * slots * 128 * itemsize <= gather_mod._SEG_BYTES


@pytest.fixture
def small_segments(monkeypatch):
    """Segments of 4 KiB of fetched rows: 8 float32 slots."""
    monkeypatch.setattr(gather_mod, "_SEG_BYTES", 1 << 12)


@pytest.mark.parametrize(
    "n,align",
    [(8 * 3, 8), (8 * 3 + 5, 8), (1009, 8), (7, 8), (40, 16)],
)
def test_map_segments_ragged_end_bit_identical(small_segments, n, align):
    """Three or more segments and a ragged end: every slot comes back, in
    order, bit-equal to table[idx]."""
    rng = np.random.default_rng(n)
    t = jnp.asarray(rng.standard_normal(777).astype(np.float32))
    ix = jnp.asarray(rng.integers(0, 777, size=(n,)).astype(np.int32))
    plan = segment_plan(n, 1, 4, align)
    assert plan.segments * plan.per + plan.tail == n
    t2 = lane_rows(t)
    out = map_segments(lambda b: fetch_select(t2, b), (ix,), plan, 0)
    assert np.array_equal(np.asarray(out), np.asarray(t[ix]))


@pytest.mark.parametrize("k,r", [(1, 8), (3, 16), (13, 24), (56, 8)])
def test_fetch_select_dot_is_the_weighted_row_sum(k, r):
    """The forward body: K slots of R rows, summed lane by lane and then
    across the lanes; the products are table[idx] . weights exactly, their
    sum agrees at float32 rounding, and it differentiates."""
    from photon_tpu.ops.gather import fetch_select_dot

    rng = np.random.default_rng(k * r)
    t = rng.standard_normal(700).astype(np.float32)
    ix = rng.integers(0, 700, size=(k, r)).astype(np.int32)
    w = rng.standard_normal((k, r)).astype(np.float32)
    t2 = lane_rows(jnp.asarray(t))
    got = np.asarray(fetch_select_dot(t2, jnp.asarray(ix), jnp.asarray(w)))
    expect = np.sum(t[ix].astype(np.float64) * w, axis=0)
    np.testing.assert_allclose(got, expect, rtol=2e-6, atol=2e-6)
    one_hot = np.zeros((k, r), np.float32)
    one_hot[k // 2] = 1.0  # a single live slot a row: the sum is exact
    alone = fetch_select_dot(t2, jnp.asarray(ix), jnp.asarray(one_hot))
    assert np.array_equal(np.asarray(alone), t[ix[k // 2]])
    g = jax.grad(
        lambda tt: jnp.sum(
            fetch_select_dot(lane_rows(tt), jnp.asarray(ix), jnp.asarray(w))
        )
    )(jnp.asarray(t))
    dense = np.zeros(700, np.float64)
    np.add.at(dense, ix.reshape(-1), w.reshape(-1).astype(np.float64))
    np.testing.assert_allclose(np.asarray(g), dense, rtol=1e-5, atol=1e-5)


def test_map_segments_slices_every_stream_along_axis(small_segments):
    """Two streams cut along axis 1, the body's result stacked along the
    unit axis: what the forward pass does with its [K, n] views."""
    k, n = 3, 4 * 8 + 3
    a = jnp.arange(k * n, dtype=jnp.float32).reshape(k, n)
    b = 2.0 * a
    plan = segment_plan(n, 1, 4, 8)
    assert (plan.segments, plan.per, plan.tail) == (4, 8, 3)
    out = map_segments(lambda x, y: jnp.sum(x + y, axis=0), (a, b), plan, 1)
    assert np.array_equal(np.asarray(out), np.asarray(jnp.sum(3.0 * a, 0)))


@pytest.mark.parametrize(
    "units,per,group",
    [
        (96, 8, 4),  # whole groups: 3 consumer blocks of 32 units
        (32, 8, 4),  # exactly one
        (104, 8, 4),  # a segment left over after 3 groups
        (125, 8, 4),  # three segments and a ragged end left over
        (29, 8, 4),  # not one whole group: every segment its own block
        (96, 8, 1),  # a group of one: the one-level loop
        (88, 8, 16),  # a group larger than the pass
    ],
)
def test_map_segment_groups_is_the_consumer_of_the_body(units, per, group):
    """The two-level loop: the body on segments of ``per`` units, the
    consumer on ``group`` of them together; every unit's result is the
    whole-array ``consumer(body(x), s)``'s, bit for bit (both halves are
    per unit and exact here), whatever is left over after the last group."""
    from photon_tpu.ops.gather import SegmentPlan, map_segment_groups

    rng = np.random.default_rng(units + group)
    x = jnp.asarray(rng.integers(-8, 8, size=(units, 6)).astype(np.float32))
    y = jnp.asarray(rng.integers(-8, 8, size=(units, 6)).astype(np.float32))
    s = jnp.asarray(rng.integers(0, 6, size=(units, 3)).astype(np.int32))
    body = lambda a, b: a * b + 1.0  # noqa: E731
    consumer = lambda c, ix: jnp.take_along_axis(  # noqa: E731
        jnp.cumsum(c, axis=1), ix, axis=1
    )
    plan = SegmentPlan(units // per, per, units % per)
    got = jax.jit(
        lambda x, y, s: map_segment_groups(
            body, consumer, (x, y), (s,), plan, group
        )
    )(x, y, s)
    assert np.array_equal(np.asarray(got), np.asarray(consumer(body(x, y), s)))


def test_map_segment_groups_runs_the_consumer_once_a_group():
    """One loop, not a loop in a loop: the segments' ``while`` holds the
    consumer under one ``cond``, and the consumer sees ``group * per``
    units; a group of one is :func:`map_segments`' program."""
    from photon_tpu.ops.gather import SegmentPlan, map_segment_groups

    seen = []

    def consumer(c):
        seen.append(c.shape)
        return jnp.cumsum(c, axis=1)

    x = jnp.ones((96, 6), jnp.float32)
    plan = SegmentPlan(12, 8, 0)
    two = jax.make_jaxpr(
        lambda x: map_segment_groups(jnp.sin, consumer, (x,), (), plan, 4)
    )(x)
    assert seen == [(32, 6)] * len(seen) and seen
    text = str(two)
    assert text.count("scan[") + text.count("while[") == 1
    assert text.count("cond[") == 1
    both = lambda b: jnp.cumsum(jnp.sin(b), axis=1)  # noqa: E731
    one = jax.make_jaxpr(
        lambda x: map_segment_groups(
            jnp.sin, lambda c: jnp.cumsum(c, axis=1), (x,), (), plan, 1
        )
    )(x)
    assert str(one) == str(
        jax.make_jaxpr(lambda x: map_segments(both, (x,), plan, 0))(x)
    )


def test_chunked_take_odd_slot_count_segments():
    rng = np.random.default_rng(5)
    t = jnp.asarray(rng.standard_normal(777).astype(np.float32))
    ix = jnp.asarray(rng.integers(0, 777, size=(1009,)).astype(np.int32))
    orig = gather_mod._SEG_BYTES
    try:
        gather_mod._SEG_BYTES = 1 << 12  # force multi-segment + padding
        out = chunked_take(t, ix)
    finally:
        gather_mod._SEG_BYTES = orig
    assert np.array_equal(np.asarray(out), np.asarray(t[ix]))


def test_chunked_take_nonfinite_isolation():
    """An Inf/NaN table entry must affect only indices that SELECT it —
    not its 128-lane block neighbors (0*Inf poisoning)."""
    t = np.zeros(256, np.float32)
    t[7] = np.inf
    t[130] = np.nan
    tj = jnp.asarray(t)
    ix = jnp.asarray(np.array([0, 6, 8, 7, 129, 131, 130], np.int32))
    out = np.asarray(chunked_take(tj, ix))
    assert out[0] == 0 and out[1] == 0 and out[2] == 0
    assert np.isinf(out[3])
    assert out[4] == 0 and out[5] == 0
    assert np.isnan(out[6])


def test_segmented_take_nonfinite_isolation(small_segments):
    """The same through the segment loop: a NaN/Inf entry reaches the
    slots that select it, in whichever segment they fall, and no other."""
    t = np.arange(256, dtype=np.float32)
    t[7], t[130] = np.inf, np.nan
    ix = np.tile(np.array([0, 6, 8, 7, 129, 131, 130], np.int32), 5)
    out = np.asarray(chunked_take(jnp.asarray(t), jnp.asarray(ix)))
    assert segment_plan(ix.size, 1, 4, 1024).steps == 1  # align floors it
    plan = segment_plan(ix.size, 1, 4, 8)
    assert plan.steps >= 3
    seg = np.asarray(
        map_segments(
            lambda b: fetch_select(lane_rows(jnp.asarray(t)), b),
            (jnp.asarray(ix),),
            plan,
            0,
        )
    )
    for got in (out, seg):
        assert np.array_equal(got, t[ix], equal_nan=True)
        assert np.isinf(got).sum() == 5 and np.isnan(got).sum() == 5


def test_take_1d_platform_dispatch():
    rng = np.random.default_rng(2)
    t = jnp.asarray(rng.standard_normal(500).astype(np.float32))
    ix = jnp.asarray(rng.integers(0, 500, size=(99,)).astype(np.int32))
    outs = {}
    for platform in ("cpu", "tpu"):
        with target.compiling_for(platform):
            outs[platform] = np.asarray(take_1d(t, ix))
    assert np.array_equal(outs["cpu"], outs["tpu"])
    assert np.array_equal(outs["cpu"], np.asarray(chunked_take(t, ix)))


def test_production_routes_match_plain():
    """ELL matvec and windowed prefix rmatvec: the row fetch == the plain
    gather exactly."""
    rng = np.random.default_rng(3)
    n, d, k = 256, 2048, 12
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.standard_normal((n, k)).astype(np.float32)
    batch = SparseBatch(
        indices=jnp.asarray(idx),
        values=jnp.asarray(val),
        labels=jnp.zeros((n,), jnp.float32),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
        windows=None,
    )
    v = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    w = jax.device_put(build_column_windows(idx, val, d, window=128))
    r = jnp.asarray(rng.standard_normal(n).astype(np.float32))

    results = {}
    for platform in ("cpu", "tpu"):
        with target.compiling_for(platform):
            results[platform] = (
                np.asarray(matvec(batch, v)),
                np.asarray(rmatvec_windows_prefix(w, r, d)),
            )
    assert np.array_equal(results["cpu"][0], results["tpu"][0])
    assert np.array_equal(results["cpu"][1], results["tpu"][1])
