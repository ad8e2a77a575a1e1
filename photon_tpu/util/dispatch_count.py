"""Process-global counter of compiled-program launches on the sweep path.

Every program launch costs a host round trip the device sits out, so
the number of programs launched per sweep is a first-class perf metric
(what one launch costs on the local chip: PERF.md, Open questions). Coordinate
implementations call :func:`record` at every site that enqueues a
compiled program (fused sweep steps record 1; the unfused fallback
records one per train/score program plus its eager arithmetic);
``run_coordinate_descent`` snapshots the counter around each sweep and
reports the delta in the tracker's per-sweep rows, which ``bench.py``
surfaces as ``dispatches_per_sweep``.

This counts OUR OWN launch sites, not XLA's executor — ad-hoc eager ops
outside the descent loop are invisible to it. The fused-sweep dispatch
regression test (tests/test_fused_sweep.py) independently verifies the
1-program-per-coordinate claim with jit call/trace counters.
"""
from __future__ import annotations

from photon_tpu import obs

_count = 0


def record(n: int = 1) -> None:
    """Count ``n`` compiled-program launches (mirrored as the
    ``descent.dispatches`` telemetry counter when obs is enabled)."""
    global _count
    _count += n
    obs.counter("descent.dispatches", n)


def snapshot() -> int:
    """Current cumulative launch count (monotonic; diff two snapshots)."""
    return _count
