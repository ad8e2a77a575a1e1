"""The platform the program being traced will be compiled for.

Several decisions in the package are facts about that platform, not
options: XLA:TPU serializes a 1-element gather and an unsorted scatter
(so a TPU program takes ``ops/gather``'s row fetch and builds
``ops/sparse_windows``' layout, a CPU program neither), and XLA:CPU
corrupts donated buffers (so only an off-CPU program donates). Each asks
:func:`platform`, here and nowhere else, at trace or build time (never at
import: reading the default backend initialises it).

:func:`compiling_for` is the seam for code that lowers for a device it
does not have: ``tests/test_tpu_compile.py`` and the builders' chip-less
compiles for a described topology. It is not an option: no environment
variable, flag or config field reads into it, and nothing under
``photon_tpu/`` enters it.
"""
from __future__ import annotations

import contextlib
import threading

import jax

__all__ = ["compiling_for", "donation_enabled", "platform"]

# process-wide, not thread-local: a parallel precompile
# (game/descent.precompile_coordinates) traces on worker threads and must
# see what the thread that entered the context sees
_lock = threading.Lock()
_active: list[tuple[object, str]] = []


def platform() -> str:
    """``"tpu"``, ``"cpu"``, …: the innermost active :func:`compiling_for`,
    else ``jax.default_backend()``."""
    with _lock:
        if _active:
            return _active[-1][1]
    return jax.default_backend()


@contextlib.contextmanager
def compiling_for(name: str):
    """Answer :func:`platform` with ``name`` inside the block, for programs
    that are COMPILED for a described device AND NOT RUN. Nests; restored on
    exit and on exception.

    It answers every question at once, the donation of buffers among them,
    and XLA:CPU corrupts donated buffers (:func:`donation_enabled`): a test
    that RUNS on the CPU what the chip would run enters the block around
    the layout BUILD alone (the layout then decides the backward pass by
    being in the batch), never around a sweep or score program it runs."""
    entry = (object(), name)
    with _lock:
        _active.append(entry)
    try:
        yield
    finally:
        with _lock:
            _active.remove(entry)


def donation_enabled() -> bool:
    """Whether the fused sweep and score programs donate their buffers
    (total / score / state; the scorer's [B, K] feature blocks): the
    steady-state memory win at scale, everywhere but on the CPU.

    On XLA:CPU (jaxlib 0.4.37) donated fused-sweep buffers intermittently
    corrupt the allocator heap: ``double free or corruption`` /
    ``corrupted size vs. prev_size`` aborts at teardown and NaN scores
    mid-run, about 1 in 10 runs of tests/test_mf.py + the fused-sweep
    suite, never without donation."""
    return platform() != "cpu"
