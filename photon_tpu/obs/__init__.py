"""Runtime telemetry spine: span tracing + metrics + exporters.

ONE runtime layer with three parts (``util/timed``, ``util/events``,
``util/compile_watch``, ``util/dispatch_count`` and the descent tracker
rows bridge into it):

- :mod:`photon_tpu.obs.tracer` — a thread-safe span :class:`Tracer`
  (monotonic clocks, nestable spans, a near-zero-overhead no-op when
  disabled). Every span, recorded or not, also enters a
  ``jax.profiler.TraceAnnotation`` named ``photon.<span>``, so host
  phases line up with device traces captured by the jax profiler.
  :mod:`photon_tpu.obs.scopes` is the device side: the ``photon.*``
  scope vocabulary of the kernels and optimizer phases.
- :mod:`photon_tpu.obs.metrics` — a :class:`MetricsRegistry` of
  counters / gauges / histograms with a flat ``snapshot()`` dict.
- :mod:`photon_tpu.obs.export` — Chrome trace-event JSON (opens in
  Perfetto / ``chrome://tracing``), a JSONL run manifest, and a
  human-readable per-phase summary table.

The LIVE half (everything above exports at end of run) is the
telemetry plane, composed per run by :class:`LiveTelemetryPlane`:

- :mod:`photon_tpu.obs.flight` — a crash-surviving mmap ring of recent
  span/event/metric records (``blackbox.ring``) with blackbox dumps on
  fatal signals and stale-ring recovery after a real SIGKILL;
- :mod:`photon_tpu.obs.series` — periodic registry-delta JSONL rows
  (``series.jsonl``), so runs yield time-resolved trajectories;
- :mod:`photon_tpu.obs.http` — opt-in ``/metrics`` (Prometheus text) /
  ``/healthz`` / ``/blackbox`` endpoints served from the live process.

The module-level functions operate on ONE process-global pipeline
(default tracer + default registry) gated by a single enable switch, so
instrumentation sites stay one-liners::

    from photon_tpu import obs

    obs.enable()
    with obs.span("fit", grid=3):
        ...
    obs.write_chrome_trace("run.trace.json")

Telemetry is DISABLED by default (set ``PHOTON_OBS=1`` to enable at
import, or call :func:`enable`). Disabled spans still measure wall time
(two monotonic clock reads — descent derives its tracker rows from
them) and still enter their profiler annotation, but record nothing,
take no locks, and never touch the device: enabling or disabling
telemetry cannot change the dispatch or read-back profile of a run.
"""
from __future__ import annotations

import logging
import os

from photon_tpu.obs import (
    causal,
    fleet,
    flight,
    health,
    http,
    memory,
    series,
    slo,
)
from photon_tpu.obs.export import (
    chrome_trace,
    export_artifacts,
    export_partial_artifacts,
    histogram_summary,
    phase_summary,
    summary_table,
    write_chrome_trace,
    write_memory_report,
    write_metrics,
    write_run_manifest,
)
from photon_tpu.obs.metrics import MetricsRegistry
from photon_tpu.obs.tracer import Span, Tracer

__all__ = [
    "LiveTelemetryPlane",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "causal",
    "chrome_trace",
    "counter",
    "disable",
    "enable",
    "enabled",
    "export_artifacts",
    "export_partial_artifacts",
    "fleet",
    "flight",
    "gauge",
    "get_registry",
    "get_tracer",
    "health",
    "histogram",
    "histogram_summary",
    "http",
    "instant",
    "live_plane",
    "memory",
    "phase_summary",
    "reset",
    "series",
    "slo",
    "span",
    "summary_table",
    "write_chrome_trace",
    "write_memory_report",
    "write_metrics",
    "write_run_manifest",
]

logger = logging.getLogger(__name__)

_tracer = Tracer(enabled=os.environ.get("PHOTON_OBS", "") == "1")
_registry = MetricsRegistry()


def get_tracer() -> Tracer:
    """The process-global default tracer."""
    return _tracer


def get_registry() -> MetricsRegistry:
    """The process-global default metrics registry."""
    return _registry


def enabled() -> bool:
    return _tracer.enabled


def enable() -> None:
    """Turn the global telemetry pipeline on (tracer + bridge counters)."""
    _tracer.enabled = True


def disable() -> None:
    _tracer.enabled = False


def reset() -> None:
    """Drop every recorded span, zero the registry, and clear the memory
    ledger's per-run state (artifact boundary: bench calls this per
    config so each artifact holds one run). Static executable footprints
    survive — they describe process-lifetime compiled programs (see
    photon_tpu/obs/memory.py)."""
    _tracer.clear()
    _registry.clear()
    memory.get_ledger().reset_run_state()
    fleet.clear_breakdown()
    fleet.clear_sweeps_cache()
    slo.reset_run_state()
    causal.reset_run_state()


def span(name: str, cat: str = "phase", **args) -> Span:
    """A span on the default tracer — always measures, records only when
    telemetry is enabled."""
    return _tracer.span(name, cat=cat, **args)


def instant(name: str, cat: str = "event", **args) -> None:
    """Record an instant (zero-duration) event when enabled."""
    _tracer.instant(name, cat=cat, **args)


def counter(name: str, value: float = 1.0) -> None:
    """Bump a counter on the default registry (no-op while disabled, so
    bridge call sites cost one attribute check on the hot path)."""
    if _tracer.enabled:
        _registry.counter(name, value)


def gauge(name: str, value: float) -> None:
    """Set a gauge on the default registry (no-op while disabled)."""
    if _tracer.enabled:
        _registry.gauge(name, value)


def histogram(name: str, value: float) -> None:
    """Observe a histogram sample on the default registry (no-op while
    disabled)."""
    if _tracer.enabled:
        _registry.histogram(name, value)


class LiveTelemetryPlane:
    """The always-on half of the spine for ONE run directory: stale-ring
    recovery (what a SIGKILLed previous run was doing → ``blackbox-
    <seq>.json``), the mmap flight recorder + crash handlers, the series
    flusher (``series.jsonl``), and the opt-in HTTP endpoints — composed
    with one ``start()``/``close()`` pair so the drivers' ``run_profile``
    can finally-guard the whole plane. Every piece is individually
    optional (``PHOTON_OBS_RING_MB=0``, ``PHOTON_OBS_FLUSH_S=0``, unset
    ``PHOTON_OBS_HTTP_PORT``) and teardown is LIFO with each step
    guarded: telemetry must never fail — or leak past — the run."""

    def __init__(self, directory):
        self.directory = str(directory)
        self.recovered_blackbox: str | None = None
        self.recorder = None
        self.flusher = None
        self.server = None
        self.fleet_publisher = None

    def start(self) -> "LiveTelemetryPlane":
        """Arm the plane. Exception-safe: if any later step fails (an
        invalid knob value, the configured port already bound), every
        piece armed so far is torn down BEFORE the error propagates —
        the operator who set a bad knob gets a loud failure (the repo's
        knob-validation convention), never a half-armed plane leaking
        crash handlers and threads into the rest of the process."""
        try:
            os.makedirs(self.directory, exist_ok=True)
            self.recovered_blackbox = flight.recover_stale(self.directory)
            self.recorder = flight.enable(self.directory)
            if self.recorder is not None:
                flight.install_crash_handler()
            self.flusher = series.start_flusher(
                os.path.join(self.directory, "series.jsonl")
            )
            # fleet membership (photon_tpu/obs/fleet.py): heartbeat
            # snapshots + the per-sweep arrival log; a no-op (None) in a
            # single-process run unless PHOTON_OBS_FLEET=1 forces it
            self.fleet_publisher = fleet.start_publisher(self.directory)
            self.server = http.start_from_env()
        except BaseException:
            self.close()
            raise
        return self

    def close(self) -> None:
        for step in (
            http.stop_server,
            fleet.stop_publisher,
            series.stop_flusher,
            flight.uninstall_crash_handler,
            flight.disable,
        ):
            try:
                step()
            except Exception as e:  # pragma: no cover - defensive
                logger.warning(
                    "telemetry-plane teardown step %s failed: %s: %s",
                    step.__name__, type(e).__name__, e,
                )


def live_plane(directory) -> LiveTelemetryPlane:
    """Start a :class:`LiveTelemetryPlane` under ``directory``."""
    return LiveTelemetryPlane(directory).start()
