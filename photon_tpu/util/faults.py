"""Deterministic fault injection: named fault points + a fault plan.

The reference inherits its whole failure story from Spark — task retry
and lineage recomputation (SURVEY §5.3, spark/RDDLike.scala:26) — and
therefore never has to PROVE recovery works: Spark's own test matrix
does. Multi-controller JAX has no substrate to lean on, so photon-tpu's
recovery ingredients (checkpoint/resume, placement retry, divergence
policies, producer reaping) need their own proof. This module supplies
the injection half: every recovery path is exercised by a DETERMINISTIC
fault — same plan, same run, same failure, every time — so the chaos
matrix (tests/test_chaos.py) can assert the recovered model is
bit-exact against the no-fault run instead of eyeballing logs.

Fault points
------------
A fault point is one named call at an existing choke point::

    from photon_tpu.util import faults
    faults.fault_point("io.decode")

With no plan installed this is two reads of a module global — the same
A/B-pinned zero-overhead discipline as obs (disabled spans) and the
transfer sanitizer. With a plan installed, each call increments that
point's occurrence counter (thread-safe: producer threads hit scoring
points) and fires the planned fault when ``(point, occurrence)``
matches.

Shipped fault points (see docs/DESIGN.md §Fault tolerance for the
table): ``coordinate.placement``, ``sparse.placement``, ``io.decode``,
``io.native_decode``, ``io.shard_flush``, ``descent.sweep``,
``descent.coordinate`` (NaN injection), ``checkpoint.write``,
``checkpoint.replace``, ``scoring.producer``, ``scoring.chunk``,
``scoring.batch``, the feature-cache paths ``cache.write`` (per
appended chunk), ``cache.replace`` (the publish rename window),
``cache.open`` (reader open/validate), ``cache.read`` (mmap replay),
and the serving-engine paths ``serve.admit`` (inside
``AdmissionQueue.submit``), ``serve.dispatch`` (per micro-batch, inside
the retry-with-requeue scope), ``serve.swap`` (inside the locked
atomic-flip critical section — ``stall`` holds a flip open mid-swap),
``serve.evict`` (as the last lease on a drained old model retires its
device tables).

Fault plan
----------
``PHOTON_FAULTS`` (env) or :func:`install` take a spec of
semicolon-separated clauses::

    <point>@<occurrence>=<kind>[:<param>]

``occurrence`` is the 1-based count of times the point fires (``*``
matches every occurrence). Kinds:

``unavailable``   raise :class:`InjectedFault` whose message carries the
                  transient ``UNAVAILABLE`` marker — exercises every
                  retry/restart classifier exactly like a transport flake.
``io_error``      raise :class:`InjectedIOError` (an ``OSError``) — a
                  torn read / failed decode.
``error``         raise :class:`InjectedFault` with NO transient marker
                  — a fatal failure; classifiers must NOT retry it.
``nan``           no raise: the instrumented site poisons its value
                  (descent injects NaN into the matched coordinate's
                  state — the health monitor must catch it).
``stall[:sec]``   ``time.sleep(sec)`` (default 5) — a hung producer /
                  slow host; watchdogs must convert it to a clean error.
``crash``         raise :class:`InjectedCrash` (a ``BaseException``) —
                  simulates abrupt process death for in-process tests:
                  no ``except Exception`` cleanup path may run.
``kill``          ``SIGKILL`` the process — the real thing, for the
                  subprocess chaos drive (scripts/chaos_drive.py).

Occurrence counting is the determinism anchor: the program's control
flow is deterministic (seeded builds, fixed update sequences), so the
N-th arrival at a point is the same arrival in every run. A restart in
the SAME process keeps counting (a matched one-shot clause does not
re-fire on the resumed attempt — exactly how a transient fault behaves);
a relaunched process starts fresh, so relaunch scripts clear
``PHOTON_FAULTS`` for the recovery leg.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import signal
import threading
import time
from contextlib import contextmanager
from typing import Iterator

__all__ = [
    "FaultClause",
    "FaultPlan",
    "InjectedCrash",
    "InjectedFault",
    "InjectedIOError",
    "active",
    "clear",
    "fault_point",
    "install",
    "install_from_env",
    "injected",
    "parse_plan",
]

logger = logging.getLogger(__name__)

_ENV = "PHOTON_FAULTS"
_KINDS = (
    "unavailable", "io_error", "error", "nan", "stall", "crash", "kill",
)


class InjectedFault(RuntimeError):
    """A planned fault (kinds ``unavailable`` / ``error``). The
    ``unavailable`` kind embeds the transient marker in its message so
    the shared classifiers (util/retry.is_transient) treat it exactly
    like a real transport flake."""


class InjectedIOError(OSError):
    """A planned I/O fault (kind ``io_error``)."""


class InjectedCrash(BaseException):
    """Simulated abrupt process death (kind ``crash``). Deliberately a
    ``BaseException``: no ``except Exception`` recovery/cleanup handler
    may see it — only process-boundary semantics (the previous on-disk
    state) survive, which is what the atomic-write tests pin."""


@dataclasses.dataclass(frozen=True)
class FaultClause:
    point: str
    occurrence: int | None  # None = every occurrence ("*")
    kind: str
    param: str | None = None

    def render(self) -> str:
        occ = "*" if self.occurrence is None else str(self.occurrence)
        suffix = f":{self.param}" if self.param is not None else ""
        return f"{self.point}@{occ}={self.kind}{suffix}"


class FaultPlan:
    """A parsed fault plan plus its occurrence counters."""

    def __init__(self, clauses: tuple[FaultClause, ...]):
        self.clauses = clauses
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}
        self._points = {c.point for c in clauses}

    def match(self, point: str) -> FaultClause | None:
        """Count this arrival at ``point`` and return the clause it
        triggers, if any. Points the plan never names skip the counter
        entirely (and the lock with it)."""
        if point not in self._points:
            return None
        with self._lock:
            n = self._counts.get(point, 0) + 1
            self._counts[point] = n
        for c in self.clauses:
            if c.point == point and (c.occurrence is None or c.occurrence == n):
                return c
        return None

    def render(self) -> str:
        return ";".join(c.render() for c in self.clauses)


def parse_plan(spec: str) -> FaultPlan:
    """Parse a ``point@occurrence=kind[:param]`` spec (see module doc)."""
    clauses = []
    for raw in spec.split(";"):
        raw = raw.strip()
        if not raw:
            continue
        head, sep, action = raw.partition("=")
        if not sep:
            raise ValueError(
                f"bad fault clause {raw!r}: expected "
                "<point>@<occurrence>=<kind>[:<param>]"
            )
        point, sep, occ = head.partition("@")
        point = point.strip()
        occ = occ.strip()
        if not sep or not point or not occ:
            raise ValueError(
                f"bad fault clause {raw!r}: missing point@occurrence"
            )
        if occ == "*":
            occurrence = None
        else:
            occurrence = int(occ)
            if occurrence < 1:
                raise ValueError(
                    f"bad fault clause {raw!r}: occurrence is 1-based"
                )
        kind, _, param = action.partition(":")
        kind = kind.strip()
        if kind not in _KINDS:
            raise ValueError(
                f"bad fault clause {raw!r}: unknown kind {kind!r} "
                f"(one of {', '.join(_KINDS)})"
            )
        clauses.append(
            FaultClause(
                point=point,
                occurrence=occurrence,
                kind=kind,
                param=param.strip() or None,
            )
        )
    if not clauses:
        raise ValueError(f"fault spec {spec!r} contains no clauses")
    return FaultPlan(tuple(clauses))


#: the active plan — None is THE disabled state every fault_point checks
_PLAN: FaultPlan | None = None


def active() -> FaultPlan | None:
    return _PLAN


def install(plan: FaultPlan | str) -> FaultPlan:
    """Install a fault plan (replacing any active one) and return it."""
    global _PLAN
    if isinstance(plan, str):
        plan = parse_plan(plan)
    _PLAN = plan
    logger.warning("fault plan installed: %s", plan.render())
    return plan


def clear() -> None:
    global _PLAN
    _PLAN = None


def install_from_env() -> FaultPlan | None:
    """(Re)install from ``PHOTON_FAULTS`` — CLI drivers call this at
    startup so a subprocess chaos drive controls faults per run; an
    empty/unset env clears any active plan."""
    spec = os.environ.get(_ENV, "").strip()
    if not spec:
        clear()
        return None
    return install(spec)


@contextmanager
def injected(spec: str) -> Iterator[FaultPlan]:
    """Test scoping: install ``spec`` for the with-body, then restore the
    previous plan (tests never leak faults into each other)."""
    global _PLAN
    prev = _PLAN
    plan = install(spec)
    try:
        yield plan
    finally:
        _PLAN = prev


def fault_point(point: str) -> FaultClause | None:
    """THE instrumentation call. Disabled (no plan): two module-global
    reads, nothing else — zero device work, A/B-pinned in
    tests/test_chaos.py. Enabled: counts the arrival and executes the
    matched clause — raising kinds raise here; ``nan`` returns the
    clause for the site to act on; ``stall`` sleeps then returns it.
    """
    plan = _PLAN
    if plan is None:
        return None
    clause = plan.match(point)
    if clause is None:
        return None
    logger.warning("fault injected at %s: %s", point, clause.render())
    try:
        # chaos visibility: the injected fault lands as an instant in
        # whatever causal trace is active on this thread (obs/causal.py),
        # so /trace shows the fault INSIDE the victim's causal chain
        from photon_tpu.obs import causal

        causal.mark_fault(point, clause.kind)
    except Exception:  # fault injection must not depend on tracing
        pass
    if clause.kind == "unavailable":
        raise InjectedFault(
            f"UNAVAILABLE: injected fault at {point!r} "
            f"({clause.render()})"
        )
    if clause.kind == "io_error":
        raise InjectedIOError(
            f"injected I/O fault at {point!r} ({clause.render()})"
        )
    if clause.kind == "error":
        raise InjectedFault(
            f"injected fatal fault at {point!r} ({clause.render()})"
        )
    if clause.kind == "crash":
        raise InjectedCrash(
            f"injected crash at {point!r} ({clause.render()})"
        )
    if clause.kind == "kill":
        logger.error("fault plan SIGKILLs the process at %r", point)
        os.kill(os.getpid(), signal.SIGKILL)
    if clause.kind == "stall":
        time.sleep(float(clause.param) if clause.param else 5.0)
    return clause


# plans ride into subprocesses via the environment (the chaos drive sets
# PHOTON_FAULTS on the child); library imports honor it too so a faulted
# run needs no code change anywhere
if os.environ.get(_ENV, "").strip():
    install_from_env()
