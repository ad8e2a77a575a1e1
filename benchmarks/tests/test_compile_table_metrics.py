"""The three readers of the program's compile table (``uncached_compiles``,
``uncached_compile_s``, ``trace_lower_s``) on a hand-made ``run`` and a
hand-made table: set-up is what came before the window's first step, and a
program without the table gives nothing to read."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.metrics import (  # noqa: E402
    trace_lower_s,
    uncached_compile_s,
    uncached_compiles,
)
from photon_tpu.util import compile_watch  # noqa: E402


class Spans:
    rows = [
        ("build", 0.0, 4.0),
        ("step", 5.0, 6.0), ("step", 6.0, 7.0), ("step", 7.0, 8.0),  # set-up's
        ("step", 10.0, 11.0), ("step", 11.0, 12.0),  # the window's
    ]


RUN = {"spans": Spans(), "steps": [{"wall_s": 1.0}, {"wall_s": 1.0}]}


def row(compiles, compile_s, served, served_s, trace_s, lowering_s, last_t):
    return {
        "compiles": compiles, "backend_compile_s": compile_s,
        "cache_served": served, "cache_served_s": served_s,
        "trace_s": trace_s, "lowering_s": lowering_s, "last_t": last_t,
    }


#: the whole process: two compiles of segment_f (one from the cache), a
#: small program the cache never keeps, and one compiled INSIDE the window
WHOLE = {
    "jit(segment_f)": row(2, 3.5, 1, 0.5, 0.25, 0.125, 5.5),
    "jit(add)": row(1, 0.25, 0, 0.0, 0.0625, 0.03125, 3.0),
    "jit(late)": row(1, 2.0, 0, 0.0, 1.0, 1.0, 10.5),
}
SINCE_WINDOW = {"jit(late)": row(1, 2.0, 0, 0.0, 1.0, 1.0, 10.5)}


@pytest.fixture()
def table(monkeypatch):
    asked = []

    def programs_since(t):
        asked.append(t)
        return copy.deepcopy(SINCE_WINDOW)

    monkeypatch.setattr(compile_watch, "programs", lambda: copy.deepcopy(WHOLE))
    monkeypatch.setattr(compile_watch, "programs_since", programs_since)
    return asked


def test_readers_count_set_up_alone(table):
    assert uncached_compiles.read(RUN) == 2  # one segment_f, one add; not late
    assert uncached_compile_s.read(RUN) == pytest.approx(3.0 + 0.25)
    assert trace_lower_s.read(RUN) == pytest.approx(0.375 + 0.09375)
    assert set(table) == {10.0}  # the window's first step, not set-up's


def test_a_run_with_no_window_step_reads_the_whole_table(table):
    run = {"spans": Spans(), "steps": []}
    assert uncached_compiles.read(run) == 3
    assert table == []


def test_a_program_without_the_table_gives_nothing(monkeypatch):
    monkeypatch.delattr(compile_watch, "programs_since")
    for reader in (uncached_compiles, uncached_compile_s, trace_lower_s):
        assert reader.read(RUN) is None
