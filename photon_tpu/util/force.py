"""Completion barrier that works over enqueue-async device backends.

``jax.block_until_ready`` is only as good as the backend's notion of
"ready": a backend that reports readiness at ENQUEUE time makes a wall
bounded by it exclude the program (and the compile) it triggered. The
barrier that holds on every backend is a device→host READ of bytes that
depend on the computation: the transfer cannot complete until the
program has run. Whether the local chip needs it is an open question
(PERF.md, Open questions).

``force`` reads ONE element per array leaf (whole leaf when tiny), so its
cost is a round trip per leaf, not a function of
the data size. Use it to close any timed region; for tight in-jit
measurement prefer reducing the program to a scalar and timing
``float(...)`` (see bench.py's digest wrapper), which pays a single
round trip total.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import numpy as np

__all__ = ["fetch_scalars", "force"]


def _count_d2h(nbytes: int) -> None:
    """Mirror the barrier's actual device→host traffic into the memory
    ledger (photon_tpu/obs/memory.py) — a no-op unless the ledger is
    live. The barrier reads ~4 bytes per leaf, and counting it keeps the
    ``mem.d2h_bytes`` ledger honest about EVERY crossing, not just the
    big ones."""
    try:
        from photon_tpu.obs import memory as obs_memory

        obs_memory.count_d2h(nbytes)
    except Exception:
        pass  # telemetry must never break the barrier


def _multi_device(leaf) -> bool:
    """True only for leaves GENUINELY sharded over multiple devices. A leaf
    without a working ``.devices()`` (host-resident or wrapped arrays in
    mixed result trees) needs no cross-device care — reading it is free —
    so it must NOT route the whole tree onto the one-round-trip-per-leaf
    fallback (ADVICE r5 #3): treat it as host-resident and let the
    concatenated single-fetch path (with its exception fallback) handle
    it."""
    try:
        return len(leaf.devices()) > 1
    except Exception:
        return False


def force(tree: Any) -> None:
    """Block until every jax.Array leaf of ``tree`` has actually been
    computed, by reading back one element of each. The per-leaf slices are
    enqueued (async, cheap) and concatenated into a single fetch so the
    blocking round trip is paid ONCE, not per leaf. No-op for non-device
    leaves (numpy arrays need no barrier)."""
    import jax.numpy as jnp

    leaves = [
        leaf
        for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array) and int(getattr(leaf, "size", 0))
    ]
    if not leaves:
        return
    _count_d2h(4 * len(leaves))  # one element per leaf crosses back
    if len(leaves) == 1:
        np.asarray(leaves[0].reshape(-1)[0:1])
        return

    # A barrier must NEVER introduce device collectives: concatenating
    # slices of multi-device-sharded leaves compiles a cross-device
    # program whose all-reduce rendezvous starts while the devices'
    # queues are still drained unevenly — on the single-core virtual
    # CPU mesh XLA's in-process rendezvous hard-aborts after 40 s of
    # skew (observed at the 10⁹-coefficient north star). Per-leaf
    # fetches read from the owning devices directly — but ONLY the
    # genuinely multi-device leaves take that path; the rest keep the
    # concatenated single-fetch path (one round trip for all of them).
    flags = [_multi_device(leaf) for leaf in leaves]
    for leaf, multi in zip(leaves, flags):
        if multi:
            np.asarray(leaf.reshape(-1)[0:1])
    rest = [leaf for leaf, multi in zip(leaves, flags) if not multi]
    if not rest:
        return
    if len(rest) == 1:
        np.asarray(rest[0].reshape(-1)[0:1])
        return
    try:
        np.asarray(
            jnp.concatenate(
                [leaf.reshape(-1)[0:1].astype(jnp.float32) for leaf in rest]
            )
        )
    except Exception:
        # Leaves committed to different devices/platforms (mixed CPU/TPU
        # trees) or exotic dtypes can make the concatenate raise — the
        # barrier must still hold, so fall back to one fetch per leaf (a
        # round trip each, but correct).
        for leaf in rest:
            np.asarray(leaf.reshape(-1)[0:1])


def fetch_scalars(scalars: Sequence[Any], barrier: Any = None) -> np.ndarray:
    """Read back a flat sequence of device scalars as float32 values in
    ONE device→host round trip, optionally ALSO serving as the
    completion barrier for ``barrier`` (see :func:`force`) in that same
    fetch.

    This is how descent's health monitor stays sync-free: the sweep's
    honest read-back barrier and the per-coordinate health scalars
    (loss / grad-norm / isfinite sentinel, all 0-d outputs of the
    already-dispatched sweep programs) travel together — folding health
    into the barrier adds ZERO read-backs and zero dispatches to the
    steady state. Booleans come back as 1.0/0.0.

    Non-device scalars (plain Python/numpy numbers in mixed trees) pass
    through without touching the device.
    """
    import jax.numpy as jnp

    scalars = list(scalars)
    barrier_leaves = [
        leaf
        for leaf in jax.tree_util.tree_leaves(barrier)
        if isinstance(leaf, jax.Array) and int(getattr(leaf, "size", 0))
    ]
    pieces = []
    for leaf in barrier_leaves:
        if _multi_device(leaf):
            # genuinely multi-device leaves barrier separately (the
            # concatenated fetch must never introduce collectives — see
            # force() above); everything else rides the single fetch
            _count_d2h(4)
            np.asarray(leaf.reshape(-1)[0:1])
        else:
            pieces.append(leaf.reshape(-1)[0:1].astype(jnp.float32))
    n_barrier = len(pieces)
    host_at: dict[int, float] = {}
    for i, s in enumerate(scalars):
        if not isinstance(s, jax.Array):
            host_at[i] = float(s)
        elif _multi_device(s):
            # same collective-freedom rule as the barrier leaves: a
            # multi-device (replicated-under-mesh) scalar must be read
            # from its owning devices directly, never concatenated into
            # a cross-device program (force() documents the rendezvous
            # hard-abort that produces — not a catchable exception)
            _count_d2h(4)
            host_at[i] = float(np.asarray(s.reshape(-1)[0:1])[0])
        else:
            pieces.append(s.reshape(-1)[0:1].astype(jnp.float32))
    if pieces:
        _count_d2h(4 * len(pieces))
        try:
            fetched = np.asarray(jnp.concatenate(pieces))
        except Exception:
            # mixed-device/platform trees: per-piece fetch keeps the
            # barrier AND the values correct at a round trip per piece
            fetched = np.concatenate(
                [np.asarray(p, dtype=np.float32) for p in pieces]
            )
        fetched = fetched[n_barrier:]
    else:
        fetched = np.zeros(0, dtype=np.float32)
    # reassemble in caller order: device values in fetch order, host
    # values at their recorded positions
    it = iter(fetched)
    return np.asarray(
        [
            host_at[i] if i in host_at else float(next(it))
            for i in range(len(scalars))
        ],
        dtype=np.float32,
    )
