"""Compile-cost telemetry: counts and walls for every XLA program built.

The compile bill is a first-class cost: a cold GLMix fit compiles one
sweep and one score program per coordinate, and the TPU compiler takes
from seconds to minutes for each (PERF.md, Findings PR 26). A cost that
large must be *measured where it is paid*, not discovered inside a
benchmark timeout: this module hangs
listeners on ``jax.monitoring`` (the same hooks the persistent
compilation cache reports through) and keeps process-global counters of

- ``backend_compiles`` / ``backend_compile_s`` — one bump per XLA
  backend compile, with its wall (fires on persistent-cache hits too,
  where the wall is the retrieval time);
- ``cache_hits`` / ``cache_misses`` — persistent compilation cache
  outcomes (zero when no cache dir is configured);
- ``trace_s`` / ``lowering_s`` — jaxpr trace + MLIR lowering walls, the
  host-side share of a cold start.

Consumers diff :func:`snapshot` around a region (the descent loop does
this per sweep; the estimator per fit; bench per config) or use the
:func:`watch` context manager. ``thread_scope`` gives per-thread
attribution for the parallel AOT precompile pass — jax runs the
listeners on whichever thread compiles, so a thread-local delta
attributes each program's compile wall to the program that paid it.

**Which program.** jax hands every duration event a ``fun_name``
(``jit(segment_f)``; the trace event the bare ``segment_f``, given the
same ``jit(...)`` here), and records ``cache_hits`` on the compiling
thread just before the ``backend_compile_duration`` of a program it
read back from the persistent cache. So beside the totals there is a
table, one row per program name (:func:`programs`): compiles, their
seconds, how many of them (and how many of the seconds) were retrievals
from the persistent cache, trace and lowering seconds, and the
``perf_counter`` of the last event; and the events themselves, so that
:func:`programs_since` can say what compiled after an instant — which
program the second sweep recompiled, not that one did. A traced function
that calls jitted ones counts their trace seconds in its own row and in
theirs, exactly as ``trace_s`` of the totals does: the rows sum to the
totals. Both are bounded (``MAX_PROGRAMS`` rows, the rest under
``OVERFLOW_ROW``; the last ``MAX_EVENTS`` events).

Listeners are process-global and never unregistered; :func:`install` is
idempotent.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time

_LOCK = threading.Lock()
_INSTALLED = False

_ZERO = {
    "backend_compiles": 0,
    "backend_compile_s": 0.0,
    "cache_hits": 0,
    "cache_misses": 0,
    "trace_s": 0.0,
    "lowering_s": 0.0,
}

_totals = dict(_ZERO)
_tls = threading.local()

#: bounds of the per-program table and of the event log behind
#: :func:`programs_since` (a cold GLMix fit compiles ~10^3 programs)
MAX_PROGRAMS = 4096
MAX_EVENTS = 65536
OVERFLOW_ROW = "(other programs)"

_ROW_ZERO = {
    "compiles": 0,
    "backend_compile_s": 0.0,
    "cache_served": 0,
    "cache_served_s": 0.0,
    "trace_s": 0.0,
    "lowering_s": 0.0,
    "last_t": 0.0,
}
_programs: dict[str, dict] = {}
#: (perf_counter, program, seconds field, seconds, served by the cache)
_events: collections.deque = collections.deque(maxlen=MAX_EVENTS)

#: monitoring keys → (counter field, seconds field or None)
_DURATION_KEYS = {
    "/jax/core/compile/backend_compile_duration": (
        "backend_compiles",
        "backend_compile_s",
    ),
    "/jax/core/compile/jaxpr_trace_duration": (None, "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (None, "lowering_s"),
}
_EVENT_KEYS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def _bump(count_key, secs_key, secs):
    with _LOCK:
        scopes = [_totals] + list(getattr(_tls, "scopes", ()))
        for acc in scopes:
            if count_key is not None:
                acc[count_key] += 1
            if secs_key is not None:
                acc[secs_key] += secs
    # telemetry-spine mirror (photon_tpu/obs): dotted compile.* counters
    # in the global metrics registry — no-ops while telemetry is disabled
    from photon_tpu import obs

    if count_key is not None:
        obs.counter(f"compile.{count_key}")
    if secs_key is not None:
        obs.counter(f"compile.{secs_key}", secs)


def _program_name(fun_name) -> str:
    name = str(fun_name) if fun_name else "(unnamed)"
    return name if "(" in name else f"jit({name})"


def _add_to_row(row: dict, secs_key: str, secs: float, cached: bool, t: float):
    row[secs_key] += secs
    if secs_key == "backend_compile_s":
        row["compiles"] += 1
        if cached:
            row["cache_served"] += 1
            row["cache_served_s"] += secs
    row["last_t"] = max(row["last_t"], t)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    keys = _DURATION_KEYS.get(event)
    if keys is None:
        return
    secs = float(duration_secs)
    _bump(keys[0], keys[1], secs)
    # the mark is this thread's, set by the cache_hits event of the very
    # compile whose duration this is (jax records both on the compiling
    # thread); any compile duration clears it
    cached = False
    if keys[0] is not None:
        cached = getattr(_tls, "cache_hit", False)
        _tls.cache_hit = False
    name = _program_name(kwargs.get("fun_name"))
    t = time.perf_counter()
    with _LOCK:
        if name not in _programs and len(_programs) >= MAX_PROGRAMS:
            name = OVERFLOW_ROW
        row = _programs.setdefault(name, dict(_ROW_ZERO))
        _add_to_row(row, keys[1], secs, cached, t)
        _events.append((t, name, keys[1], secs, cached))


#: fires as a compile that may use the persistent cache begins
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_REQUEST:
        _tls.cache_hit = False  # a mark left by a compile that raised
        return
    key = _EVENT_KEYS.get(event)
    if key is not None:
        if key == "cache_hits":
            _tls.cache_hit = True
        _bump(key, None, 0.0)


def install() -> bool:
    """Register the monitoring listeners (idempotent). Returns True: the
    hooks are live."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return True
    from jax._src import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    with _LOCK:
        _INSTALLED = True
    return True


def installed() -> bool:
    """True when the monitoring listeners are registered. Exactly one
    registration ever happens per process — repeated ``install()`` calls
    (every ``fit()``, every ``watch()``) are no-ops, so per-region deltas
    stay single-counted no matter how many fits share the process."""
    with _LOCK:
        return _INSTALLED


def snapshot() -> dict:
    """Copy of the cumulative process-global counters (monotonic)."""
    install()
    with _LOCK:
        return dict(_totals)


def delta(before: dict, after: dict | None = None) -> dict:
    """``after − before`` fieldwise; ``after`` defaults to now."""
    if after is None:
        after = snapshot()
    out = {}
    for k, z in _ZERO.items():
        d = after.get(k, z) - before.get(k, z)
        out[k] = round(d, 4) if isinstance(z, float) else d
    return out


def programs() -> dict[str, dict]:
    """The per-program table since :func:`install`: name -> ``compiles``,
    ``backend_compile_s``, ``cache_served`` / ``cache_served_s`` (the
    compiles, and their seconds, that were retrievals from the persistent
    cache), ``trace_s``, ``lowering_s``, ``last_t`` (``perf_counter`` of
    the program's last event). A copy."""
    install()
    with _LOCK:
        return {name: dict(row) for name, row in _programs.items()}


def programs_since(t: float) -> dict[str, dict]:
    """The same rows, counting only the events at or after the
    ``perf_counter`` instant ``t`` (of the last ``MAX_EVENTS``)."""
    install()
    with _LOCK:
        events = [ev for ev in _events if ev[0] >= t]
    out: dict[str, dict] = {}
    for when, name, secs_key, secs, cached in events:
        _add_to_row(out.setdefault(name, dict(_ROW_ZERO)), secs_key, secs,
                    cached, when)
    return out


def describe(rows: dict[str, dict], since: float | None = None) -> str:
    """One line naming the programs of ``rows`` that compiled, the latest
    first, for an assertion's message:
    ``jit(f) x2 0.310s (1 from cache) at +41.2s, jit(g) ...`` — the offset
    is each program's last event after the instant ``since``."""
    parts = []
    for name, row in sorted(rows.items(), key=lambda kv: -kv[1]["last_t"]):
        if row["compiles"]:
            when = "" if since is None else f" at +{row['last_t'] - since:.1f}s"
            parts.append(
                f"{name} x{row['compiles']} {row['backend_compile_s']:.3f}s"
                f" ({row['cache_served']} from cache){when}"
            )
    return ", ".join(parts) or "no program"


@contextlib.contextmanager
def watch():
    """Context manager yielding a dict filled with the region's compile
    delta on exit: ``with watch() as stats: ... ; stats['backend_compiles']``."""
    install()
    before = snapshot()
    stats: dict = {}
    try:
        yield stats
    finally:
        stats.update(delta(before))


@contextlib.contextmanager
def thread_scope():
    """Per-thread compile attribution for parallel precompiles: only
    compiles executed on THIS thread land in the yielded dict (jax runs
    monitoring listeners on the compiling thread). Nestable."""
    install()
    acc = dict(_ZERO)
    with _LOCK:
        scopes = getattr(_tls, "scopes", None)
        if scopes is None:
            scopes = _tls.scopes = []
        scopes.append(acc)
    try:
        yield acc
    finally:
        with _LOCK:
            _tls.scopes.remove(acc)
        for k, z in _ZERO.items():
            if isinstance(z, float):
                acc[k] = round(acc[k], 4)
