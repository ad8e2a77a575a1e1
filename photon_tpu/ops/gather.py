"""1-D table gather tuned for XLA:TPU's serialized-gather cliff.

Reference parity: the gathers here implement the same per-datum feature
lookups the reference's aggregators stream row-by-row on CPU executors
(photon-lib function/glm/ValueAndGradientAggregator.scala:119-247); on
TPU the lookup itself is the bottleneck, not the FLOPs.

Measurements at config-3 scale (67M gathered elements) on a v5e under
jaxlib 0.4.37, round 4 — the lab script is gone and the numbers have
not been taken again on the local chip (PERF.md, Open questions):

    plain 1-element gather     ~112 Melem/s   (iota == sorted == random:
                                               serialized, not locality-bound)
    take_along_axis lanes       ~44 Melem/s   (worse — no lane-shuffle path)
    chunked row gather+select  ~362 Melem/s   185 GB/s — bandwidth-bound

``chunked_take`` implements the winning strategy: view the table as
[rows, 128] lanes, fetch WHOLE 128-lane rows by block index (vector
loads at HBM bandwidth), and select each element's lane with a one-hot
multiply-reduce (exact: one 0/1 product per lane, so the result is
bit-identical to ``table[idx]``). The 128·itemsize bytes/element row
traffic (512 B for f32, 256 B bf16, 1024 B f64) is the price; at
~185 GB/s it beats the 110M elem/s serialized gather 3.2x.

The [*, 128] row-fetch intermediate is bounded by segmenting the flat
index stream under ``lax.map`` (sequential over segments, each segment
bandwidth-bound) — an unfused gather would otherwise materialize
slots x 512 B (34 GB at config-3 scale).

Selection: ``PHOTON_SPARSE_GATHER`` = auto (default) | chunked | plain.
AUTO routes to chunked on TPU backends, plain elsewhere (CPU's native
gather is faster than the 128x traffic blow-up).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from photon_tpu.obs.scopes import scope
from photon_tpu.types import Array

__all__ = ["chunked_take", "take_1d"]

_ENV = "PHOTON_SPARSE_GATHER"

#: per-segment row-fetch budget (bytes) — bounds the transient HBM cost
#: of an unfused gather while keeping each segment large enough to stay
#: bandwidth-bound
_SEG_BYTES = 1 << 30


def _num_segments(n_slots: int, itemsize: int = 4) -> int:
    """Segment count that keeps each segment's row fetch under
    ``_SEG_BYTES`` (the index stream is padded up to a multiple, so no
    divisibility requirement — an odd slot count must not silently
    disable segmentation and materialize the full [slots, 128] fetch).
    Per-slot bytes = 128 lanes × the TABLE dtype's itemsize — a float64
    table doubles the fetch past a 4-byte budget, bf16 halves it."""
    return max(1, -(-(n_slots * 128 * itemsize) // _SEG_BYTES))


def chunked_take(table: Array, idx: Array) -> Array:
    """``table[idx]`` for a 1-D table via 128-lane row fetches + one-hot
    lane select. Element-identical to the plain gather (the lane select
    uses ``where``, not multiply, so non-finite table entries do NOT
    poison their 128-lane neighbors through 0·Inf); ~3.2x faster on TPU
    at random-sparse scale (module docstring).

    Precondition: every index lies in [0, d). Out-of-range indices follow
    a DIFFERENT clamp than XLA's plain gather (block and lane clamp
    separately instead of the flat index), so an upstream indexing bug
    would produce backend-dependent values rather than a consistent
    clamp — all production index streams (ELL layouts, window rows) are
    built in-range by construction."""
    with scope("photon.gather"):
        return _chunked_take(table, idx)


def _chunked_take(table: Array, idx: Array) -> Array:
    (d,) = table.shape
    n_rows = -(-d // 128)
    padded = jnp.zeros((n_rows * 128,), table.dtype).at[:d].set(table)
    t2 = padded.reshape(n_rows, 128)
    flat = idx.reshape(-1)
    n = flat.size
    segs = _num_segments(n, jnp.dtype(table.dtype).itemsize)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    def seg_take(iseg):
        rows = t2[iseg >> 7]
        sel = (iseg & 127)[:, None] == lane_iota
        return jnp.sum(jnp.where(sel, rows, 0), axis=1)

    if segs == 1:
        out = seg_take(flat)
    else:
        seg_len = -(-n // segs)
        pad = segs * seg_len - n
        flat_p = (
            jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
            if pad
            else flat
        )
        out = jax.lax.map(
            seg_take, flat_p.reshape(segs, seg_len)
        ).reshape(-1)
        if pad:
            out = out[:n]
    return out.reshape(idx.shape)


def take_1d(table: Array, idx: Array) -> Array:
    """Strategy-dispatched 1-D gather (see module docstring).

    The ``PHOTON_SPARSE_GATHER`` knob and the AUTO platform choice are
    resolved at TRACE time: already-compiled programs keep the strategy
    they were traced with after an env change (set the env before the
    first call, or bust the jit cache to re-route). AUTO prefers the
    platform of the device the TABLE actually lives on (eager calls);
    under a jit trace the operand carries no committed device, so the
    default backend — which is what the program will compile for — is
    the right key."""
    impl = os.environ.get(_ENV, "auto").strip().lower()
    if impl == "auto":
        platform = None
        try:
            devices = table.devices()
            if devices:
                platform = next(iter(devices)).platform
        except Exception:
            platform = None  # tracer or uncommitted: fall back
        if platform is None:
            platform = jax.default_backend()
        impl = "chunked" if platform == "tpu" else "plain"
    if impl == "chunked":
        return chunked_take(table, idx)
    with scope("photon.gather"):
        return table[idx]
