"""At a tiny size on the CPU, through the very code that decides ``correct``
(``run.main`` from the device check on): each cell's program against the
plain reference comes out correct, the lower-precision control comes out
not correct, and so does a run with the timed path broken underneath, once
for each fault a training cell can have."""
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np
import pytest

CELLS = ["linear_tron.solve", "sparse_poisson.solve"]


@pytest.fixture(scope="module")
def run_mod():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_run", os.path.join(ROOT, "benchmarks", "run.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _f32_like_the_chip():
    """The repo's own conftest turns x64 on for the CPU suite; the chip runs
    float32, and the limits are float32's."""
    import jax

    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", x64)


def drive(run_mod, capsys, monkeypatch, cell, *extra, fault=None, seed=11):
    if fault is not None:
        real = run_mod.load_module

        def patched(kind, name):
            mod = real(kind, name)
            if kind == "runners":
                setup = mod.setup

                def broken_setup(*a, **k):
                    state = setup(*a, **k)
                    fault(state)
                    return state

                mod.setup = broken_setup
            return mod

        monkeypatch.setattr(run_mod, "load_module", patched)
    rc = run_mod.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.2",
                       "--trace", "0", "--rehearse", *extra])
    assert rc == 0
    captured = capsys.readouterr()
    result = json.loads(captured.out.strip().splitlines()[-1])
    # every number compared is printed beside its limit, last on stderr
    assert [ln.split()[1] for ln in captured.err.strip().splitlines()[-len(result["check"]):]] \
        == list(result["check"])
    assert list(result)[-1] == "check"
    return result


def state_unchanged(state):
    """A step that returns its state unchanged: a segment hands back the
    solve it was given; a whole solve hands back its start point and the
    first loss."""
    if state.start is not None:
        state.advance = lambda solve: solve
        return
    advance = state.advance

    def broken(_):
        res = advance(None)
        flat = np.full_like(np.asarray(res.loss_history), np.asarray(res.loss_history)[0])
        return res._replace(x=state.w0, loss_history=flat)

    state.advance = broken


def stops_early(state):
    """A solve that says it has met its stopping rule before it has: the
    first step's counters carry the reason 'function values converged'."""
    counters = state.counters

    def broken(solve):
        it, trials, hvp, reason, value = counters(solve)
        return it, trials, hvp, reason * 0 + 2, value

    state.counters = broken


def half_batch(state):
    """Half of the batch left out: the second half of the rows weighs nothing."""
    w = np.asarray(state.batch.weights).copy()
    w[len(w) // 2:] = 0.0
    import jax.numpy as jnp

    state.batch = state.batch._replace(weights=jnp.asarray(w))


@pytest.mark.parametrize("cell", CELLS)
def test_program_agrees_with_reference(run_mod, capsys, monkeypatch, cell):
    result = drive(run_mod, capsys, monkeypatch, cell)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert all(v["limit"] is not None for v in result["check"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_is_not_correct(run_mod, capsys, monkeypatch, cell):
    result = drive(run_mod, capsys, monkeypatch, cell, "--control")
    assert result["correct"] is False


# a segment's end is held by the stopping rule; a whole solve's by the
# coefficients' change, so only the segmented cell can stop early unseen
FAULTS = [(c, f) for c in CELLS for f in (state_unchanged, half_batch)]
FAULTS.append(("sparse_poisson.solve", stops_early))


@pytest.mark.parametrize("cell,fault", FAULTS, ids=lambda v: getattr(v, "__name__", v))
def test_broken_timed_path_is_not_correct(run_mod, capsys, monkeypatch, cell, fault):
    result = drive(run_mod, capsys, monkeypatch, cell, fault=fault)
    assert result["correct"] is False


def test_stopping_rule_numbers():
    """check.stopping by hand: three segments of 2 iterations, the second
    ending its solve on the loss tolerance, the third starting afresh."""
    from benchmarks.lib import check

    rule = {"segment_iters": 2, "max_iterations": 100}
    ref = {"loss_tol": 0.5, "grad_tol": 1e-3}
    hist = np.array([100.0, 60.0, 50.0, 49.75, 49.75])

    def rec(it, fresh, reason, loss=hist):
        return {"iterations": it, "fresh": fresh, "reason": reason, "loss": loss,
                "gnorm": np.ones_like(loss)}

    sound = [rec(2, True, None), rec(3, False, "function_values"), rec(2, True, None)]
    assert check.stopping(sound, ref, rule) == (0, 0.5)  # |49.75 - 50| / 0.5
    # stops on a change of 10, twenty times its tolerance
    early = [rec(2, True, "function_values"), rec(2, True, None), rec(4, False, None)]
    assert check.stopping(early, ref, rule) == (0, 20.0)
    # a segment that hands its state back; a solve not started afresh after an end
    stuck = [rec(2, True, None), rec(2, False, None), rec(4, False, None)]
    assert check.stopping(stuck, ref, rule)[0] == 1
    carried = [rec(2, True, "gradient"), rec(4, False, None), rec(6, False, None)]
    assert check.stopping(carried, ref, rule) == (1, 1000.0)
    # whole solves (no segment length): each one is fresh and ends
    whole = [rec(4, True, "function_values")] * 3
    assert check.stopping(whole, ref, {"segment_iters": None, "max_iterations": 15}) == (0, 0.0)
    # a failed line search leaves the loss as it was (entries 3 and 4), or is none
    failed = [rec(2, True, None), rec(4, False, "not_improving")]
    assert check.stopping(failed, ref, rule) == (0, 0.0)
    assert check.stopping([rec(2, True, None), rec(3, False, "not_improving")], ref, rule) \
        == (0, float("inf"))


def test_metric_and_cell_files_are_found_by_name(run_mod):
    bench = run_mod.load_json("BENCHMARK.json")
    for w in bench["workloads"]:
        cell = run_mod.load_json("benchmarks", "workloads", f"{w['name']}.json")
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "configs", f"{w['config']}.json"))
    for m in bench["per_layer"]:
        assert callable(run_mod.load_module("metrics", m["name"]).read)
    for m in bench["end_to_end"]:
        assert callable(run_mod.load_module("end_to_end", m["name"]).read)
    with pytest.raises(FileNotFoundError):
        run_mod.load_module("metrics", "no_such_metric")


def test_bf16_rounding_is_to_nearest_even():
    from benchmarks.lib.reference import to_bf16

    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 2**-7, 1.0 + 3 * 2**-9, -3.14159], np.float32)
    # 1 + 2^-8 is a tie between 1 and 1 + 2^-7: to even (1.0)
    assert to_bf16(x).tolist() == [1.0, 1.0, 1.0078125, 1.0078125, -3.140625]
