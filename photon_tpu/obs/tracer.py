"""Thread-safe span tracer with device-trace alignment.

A :class:`Span` is one named, timed region on one thread. Spans nest:
each thread keeps its own open-span stack, so a span started while
another is open records that span as its parent — across threads (the
parallel AOT precompile pool, bench workers) spans stay independent and
Perfetto renders each thread as its own track.

Clocks: ``time.perf_counter_ns`` (monotonic — durations are immune to
wall-clock steps) for timing, with one ``time.time()`` anchor captured
at tracer construction so exporters can place the monotonic timeline in
wall-clock time.

Overhead discipline: a DISABLED tracer's ``span()`` returns a span that
still measures (two clock reads, so callers like the descent tracker
can read ``duration_s`` either way) but skips the lock, the record
list and the parent stack — and it never dispatches device work in any
mode, so telemetry cannot change a run's dispatch/read-back profile.

Device-trace alignment does NOT wait for telemetry: every span enters a
``jax.profiler.TraceAnnotation`` named ``photon.<span name>``, enabled
or not, so a profiler capture of any run (the benchmark sets no
``PHOTON_*`` variable) carries the program's host phases on the device
trace's clock, apart from a harness's own annotations by their prefix.
With no profiler session live the annotation is a flag test; a process
that never imported jax is not made to.
"""
from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any

from photon_tpu.obs import causal


@dataclass
class SpanRecord:
    """One finished span, as recorded by the tracer."""

    name: str
    cat: str
    t0_ns: int  # perf_counter_ns at entry
    dur_ns: int  # 0 for instant events
    tid: int
    span_id: int
    parent_id: int | None
    args: dict[str, Any] = field(default_factory=dict)
    instant: bool = False


#: what the profiler's trace shows a span under: ``photon.<span name>``
ANNOTATION_PREFIX = "photon."


def _trace_annotation(name: str, **meta):
    """A jax.profiler.TraceAnnotation ``photon.<name>`` carrying ``meta``
    (span/trace IDs, so device-profiler slices join back to host spans
    and causal traces), or None when the profiler is unavailable or jax
    was never imported (no capture can be live then; host spans simply
    don't show up in device traces — everything else keeps working)."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    name = ANNOTATION_PREFIX + name
    try:
        try:
            return profiler.TraceAnnotation(name, **meta)
        except TypeError:
            # older jax: TraceAnnotation takes no metadata kwargs —
            # fall back to the bare named annotation
            return profiler.TraceAnnotation(name)
    except Exception:  # pragma: no cover - profiler unavailable
        return None


class Span:
    """Context manager for one traced region.

    ``with tracer.span("fit") as sp: ... sp.set(grid=3)`` — attributes
    set during the span land in the exported event's ``args``. After
    exit, ``duration_s`` holds the measured wall regardless of whether
    the span was recorded.
    """

    __slots__ = (
        "_tracer",
        "name",
        "cat",
        "args",
        "_t0_ns",
        "_dur_ns",
        "_recording",
        "_ann",
        "_parent_id",
        "span_id",
    )

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0_ns = 0
        self._dur_ns = 0
        self._recording = False
        self._ann = None
        self._parent_id = None
        self.span_id = 0

    def set(self, **kwargs) -> "Span":
        """Attach attributes (exported as trace-event ``args``)."""
        self.args.update(kwargs)
        return self

    @property
    def duration_s(self) -> float:
        return self._dur_ns / 1e9

    def __enter__(self) -> "Span":
        tracer = self._tracer
        # enabled state is latched at entry so a mid-span toggle cannot
        # produce a half-recorded span
        self._recording = tracer.enabled
        meta = {}
        if self._recording:
            self.span_id = next(tracer._ids)
            stack = tracer._stack()
            self._parent_id = stack[-1] if stack else None
            stack.append(self.span_id)
            meta["span_id"] = self.span_id
            trace_id = causal.current_trace_id()
            if trace_id is not None:
                meta["trace_id"] = trace_id
        if tracer.annotate_device:
            # always, recording or not: the annotation is what puts this
            # span on a profiler capture's clock (module docstring)
            self._ann = _trace_annotation(self.name, **meta)
            if self._ann is not None:
                self._ann.__enter__()
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._dur_ns = time.perf_counter_ns() - self._t0_ns
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if not self._recording:
            return
        tracer = self._tracer
        stack = tracer._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        tracer._record(
            SpanRecord(
                name=self.name,
                cat=self.cat,
                t0_ns=self._t0_ns,
                dur_ns=self._dur_ns,
                tid=threading.get_ident(),
                span_id=self.span_id,
                parent_id=self._parent_id,
                args=self.args,
            )
        )


class Tracer:
    """Collects :class:`SpanRecord`s from every thread of the process."""

    def __init__(self, enabled: bool = True, annotate_device: bool = True):
        self.enabled = enabled
        self.annotate_device = annotate_device
        self._lock = threading.Lock()
        self._spans: list[SpanRecord] = []
        self._ids = itertools.count(1)
        self._tls = threading.local()
        # wall-clock ↔ monotonic anchor for exporters
        # phl-ok: PHL006 epoch anchor — the ONE wall-clock capture; all spans step from the monotonic base
        self.epoch_wall_s = time.time()
        self.epoch_ns = time.perf_counter_ns()
        self.pid = os.getpid()

    # -- recording ---------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _record(self, rec: SpanRecord) -> None:
        with self._lock:
            self._spans.append(rec)

    def span(self, name: str, cat: str = "phase", **args) -> Span:
        return Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "event", **args) -> None:
        if not self.enabled:
            return
        stack = self._stack()
        self._record(
            SpanRecord(
                name=name,
                cat=cat,
                t0_ns=time.perf_counter_ns(),
                dur_ns=0,
                tid=threading.get_ident(),
                span_id=next(self._ids),
                parent_id=stack[-1] if stack else None,
                args=args,
                instant=True,
            )
        )

    # -- reading -----------------------------------------------------------

    def spans(self) -> list[SpanRecord]:
        """Snapshot of every recorded span (copy — safe to iterate while
        other threads keep recording)."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._tls = threading.local()
