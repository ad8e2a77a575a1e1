"""Completion barrier that works over enqueue-async device backends.

``jax.block_until_ready`` is only as good as the backend's notion of
"ready": a backend that reports readiness at ENQUEUE time makes a wall
bounded by it exclude the program (and the compile) it triggered. The
barrier that holds on every backend is a device→host READ of bytes that
depend on the computation: the transfer cannot complete until the
program has run. Whether the local chip needs it is an open question
(PERF.md, Open questions).

``force`` reads ONE element per array leaf, so its cost is a round trip,
not a function of the data size. The first elements of every
single-device leaf are gathered by ONE small jitted program
(:func:`_first_elements`: slice, cast to float32, concatenate) and read
in one fetch; its compile is keyed by the leaves' count, shapes and
dtypes, so a structure read every sweep traces once. Genuinely
multi-device leaves are read one by one (a barrier must never compile a
collective). Use it to close any timed region; for tight in-jit
measurement prefer reducing the program to a scalar and timing
``float(...)`` (see bench.py's digest wrapper).
"""
from __future__ import annotations

import collections
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.util import dispatch_count

__all__ = ["PendingScalars", "force", "queue_scalars"]

#: how often :func:`_first_elements` was traced (its body runs only while
#: tracing): a read-back that retraces every sweep shows up here
TRACE_COUNTERS: collections.Counter = collections.Counter()


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
    one-program single-fetch path (with its exception fallback) handle
    it."""
    try:
        return len(leaf.devices()) > 1
    except Exception:
        return False


@jax.jit
def _first_elements(leaves: tuple) -> jax.Array:
    """float32[k]: the first element of each of ``leaves``, in order
    (booleans read 1.0/0.0)."""
    TRACE_COUNTERS["first_elements"] += 1
    return jnp.concatenate(
        [leaf.reshape(-1)[0:1].astype(jnp.float32) for leaf in leaves]
    )


#: what a launch over leaves on different devices or platforms (or of a
#: dtype the cast refuses) raises: ValueError / TypeError at the call, the
#: runtime's error (a RuntimeError) at the call or at the read
_LAUNCH_ERRORS = (ValueError, TypeError, RuntimeError)


def _launch(leaves: Sequence[Any]):
    """Queue :func:`_first_elements` over single-device ``leaves``: the
    device result, or None when the launch raises (leaves committed to
    different devices or platforms)."""
    try:
        return _first_elements(tuple(leaves))
    except _LAUNCH_ERRORS:
        return None


def _read(packed, leaves: Sequence[Any]) -> np.ndarray:
    """The blocking read of a :func:`_launch`: float32 first elements of
    ``leaves``, one fetch — or, where the launch or its read failed, a
    fetch per leaf (a round trip each, but the barrier AND the values
    hold)."""
    if packed is not None:
        try:
            return np.asarray(packed)
        except _LAUNCH_ERRORS:
            pass
    return np.concatenate(
        [np.asarray(leaf.reshape(-1)[0:1]).astype(np.float32) for leaf in leaves]
    )


def force(tree: Any) -> None:
    """Block until every jax.Array leaf of ``tree`` has actually been
    computed, by reading back one element of each: one launch of the
    first-elements program and ONE blocking fetch for the single-device
    leaves. No-op for non-device leaves (numpy arrays need no barrier)."""
    leaves = [
        leaf
        for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array) and int(getattr(leaf, "size", 0))
    ]
    if not leaves:
        return
    _count_d2h(4 * len(leaves))  # one element per leaf crosses back

    # A barrier must NEVER introduce device collectives: one program over
    # slices of multi-device-sharded leaves compiles a cross-device
    # program whose all-reduce rendezvous starts while the devices'
    # queues are still drained unevenly — on the single-core virtual
    # CPU mesh XLA's in-process rendezvous hard-aborts after 40 s of
    # skew (observed at the 10⁹-coefficient north star). Per-leaf
    # fetches read from the owning devices directly — but ONLY the
    # genuinely multi-device leaves take that path; the rest share the
    # one program and its single fetch.
    rest = []
    for leaf in leaves:
        if _multi_device(leaf):
            np.asarray(leaf.reshape(-1)[0:1])
        else:
            rest.append(leaf)
    if rest:
        _read(_launch(rest), rest)


class PendingScalars:
    """A read-back :func:`queue_scalars` has put on the device's queue,
    waiting for :meth:`wait`, the one blocking device→host read.

    ``values`` is the count of device values it carries (barrier leaves
    included); ``pieces`` the separate device operations it queued: the
    one launch of the first-elements program for the single-device values
    (0 when there are none) plus one slice a multi-device value."""

    __slots__ = (
        "pieces", "values", "_n", "_host_at", "_direct", "_packed", "_parts",
        "_skip",
    )

    def __init__(self, n, host_at, direct, parts, skip, packed):
        self._n, self._host_at, self._direct = n, host_at, direct
        self._parts, self._skip, self._packed = parts, skip, packed
        self.values = len(direct) + len(parts)
        # a launch that raised leaves each part to its own read in wait()
        self.pieces = len(direct) + (len(parts) if packed is None else 1)

    def wait(self) -> np.ndarray:
        """Block until the read-back is home: float32 values in the order
        the scalars were given."""
        host_at = dict(self._host_at)
        for i, piece in self._direct:
            value = np.asarray(piece)
            if i is not None:
                host_at[i] = float(value[0])
        fetched = (
            _read(self._packed, self._parts)[self._skip:] if self._parts else ()
        )
        # reassemble in caller order: device values in fetch order, host
        # values at their recorded positions
        it = iter(fetched)
        return np.asarray(
            [
                host_at[i] if i in host_at else float(next(it))
                for i in range(self._n)
            ],
            dtype=np.float32,
        )


def queue_scalars(scalars: Sequence[Any], barrier: Any = None) -> PendingScalars:
    """Queue the read-back of a flat sequence of device scalars as
    float32 values, to come home in ONE device→host round trip when the
    result's ``wait()`` is called, optionally ALSO serving as the
    completion barrier for ``barrier`` (see :func:`force`) in that same
    fetch. The single-device scalars and one element of every
    single-device ``barrier`` leaf go to ONE launch of the first-elements
    program here (counted as one dispatch in ``util/dispatch_count``);
    nothing is read yet. Genuinely multi-device values are sliced here
    and read one by one in ``wait()`` (the one program must never
    introduce collectives — see :func:`force`).

    This is how descent's health monitor stays sync-free: the sweep's
    honest read-back barrier and the per-coordinate health scalars
    (loss / grad-norm / isfinite sentinel, all 0-d outputs of the
    already-dispatched sweep programs) travel together — folding health
    into the barrier adds zero read-backs, and the sweep programs stay
    as they are: the read-back is a program of its own, launched after
    them. Booleans come back as 1.0/0.0.

    Non-device scalars (plain Python/numpy numbers in mixed trees) pass
    through without touching the device.
    """
    scalars = list(scalars)
    barrier_leaves = [
        leaf
        for leaf in jax.tree_util.tree_leaves(barrier)
        if isinstance(leaf, jax.Array) and int(getattr(leaf, "size", 0))
    ]
    #: (caller position or None for a barrier leaf, its 1-element slice)
    direct: list = []
    parts = []
    for leaf in barrier_leaves:
        if _multi_device(leaf):
            _count_d2h(4)
            direct.append((None, leaf.reshape(-1)[0:1]))
        else:
            parts.append(leaf)
    skip = len(parts)
    host_at: dict[int, float] = {}
    for i, s in enumerate(scalars):
        if not isinstance(s, jax.Array):
            host_at[i] = float(s)
        elif _multi_device(s):
            # a multi-device (replicated-under-mesh) scalar is read from
            # its owning devices directly, never gathered into a
            # cross-device program (force() documents the rendezvous
            # hard-abort that produces — not a catchable exception)
            _count_d2h(4)
            direct.append((i, s.reshape(-1)[0:1]))
        else:
            parts.append(s)
    packed = None
    if parts:
        _count_d2h(4 * len(parts))
        packed = _launch(parts)
        if packed is not None:
            dispatch_count.record(1)  # the first-elements program's launch
    return PendingScalars(len(scalars), host_at, direct, parts, skip, packed)
