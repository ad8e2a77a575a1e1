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

Listeners are process-global and never unregistered; :func:`install` is
idempotent.
"""
from __future__ import annotations

import contextlib
import threading

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


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    keys = _DURATION_KEYS.get(event)
    if keys is not None:
        _bump(keys[0], keys[1], float(duration_secs))


def _on_event(event: str, **kwargs) -> None:
    key = _EVENT_KEYS.get(event)
    if key is not None:
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
