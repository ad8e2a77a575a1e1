"""The trace reduction on a hand-made trace, with hand-checked values, and
on a small xplane recorded on the chip."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import pytest

from benchmarks.lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    # two steps on the host, [0, 10] and [10, 20]; the device runs a solve
    # program [1, 8] (a while op enclosing two fusions) and [11, 19]
    return {
        "host": [("bench.step", 0.0, 10.0), ("bench.step", 10.0, 20.0)],
        "devices": {
            0: {
                "modules": [("jit_solve(123)", 1.0, 8.0), ("jit_solve(123)", 11.0, 19.0)],
                "ops": [
                    ("while.1", 1.0, 8.0), ("fusion.2", 1.0, 4.0), ("fusion.3", 4.0, 7.5),
                    ("while.1", 11.0, 19.0), ("fusion.2", 11.0, 15.0), ("fusion.3", 15.0, 19.0),
                ],
            }
        },
    }


def test_union_and_covered():
    merged = trace.union([(0, 2), (1, 3), (5, 6)])
    assert merged == [(0, 3), (5, 6)]
    assert trace.covered(merged, 2, 5.5) == pytest.approx(1.5)


def test_reduce_hand_checked():
    r = trace.reduce(hand_made())
    assert r["window_s"] == pytest.approx(20.0)
    assert r["busy_s"] == pytest.approx(7.0 + 8.0)
    assert [s["wall_s"] for s in r["steps"]] == [10.0, 10.0]
    assert [s["busy_s"] for s in r["steps"]] == pytest.approx([7.0, 8.0])
    assert r["module_s"] == {"jit_solve": pytest.approx(15.0)}
    # self time: the while keeps only what its body does not cover
    assert r["op_s"]["fusion.2"] == pytest.approx(7.0)
    assert r["op_s"]["fusion.3"] == pytest.approx(7.5)
    assert r["op_s"]["while.1"] == pytest.approx(0.5)
    # idle: [0,1] + [8,10] + [10,11] + [19,20], all inside steps
    assert r["gap_s"] == {"bench.step": pytest.approx(5.0)}
    assert trace.top(r["op_s"], 2) == [["fusion.3", pytest.approx(7.5)], ["fusion.2", pytest.approx(7.0)]]


def test_no_step_annotation_raises():
    t = hand_made()
    t["host"] = []
    with pytest.raises(ValueError):
        trace.reduce(t)


def test_recorded_xplane_from_the_chip():
    path = os.path.join(DATA, "probe.xplane.pb")
    t = trace.load(path)
    assert list(t["devices"]) == [0]
    assert len([e for e in t["host"] if e[0] == "bench.step"]) == 3
    r = trace.reduce(t)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert len(r["steps"]) == 3
    assert all(0.0 < s["busy_s"] <= s["wall_s"] for s in r["steps"])
    assert sum(r["module_s"].values()) == pytest.approx(r["busy_s"], rel=0.05)
