"""The comparison that decides ``correct``: what the program's first steps
produced against the plain reference that followed them.

``observed`` is one record per first step (``loss`` and ``gnorm`` histories
with entry i the state after optimizer iteration i, the coefficients ``x``
after the step, its ``gradient`` there, ``iterations`` so far in its solve,
whether the step started that solve (``fresh``) and the ``reason`` it ended
it for, or None); ``ref`` is the reference's record of the same shape,
followed for as many iterations as the last followed step reached, with the
absolute tolerances of its stopping rule. Each number but the last two is a
relative gap; its limit comes from the configuration's file.

- ``loss_gap``: the widest gap of the loss over every optimizer iteration
  the first steps took (each step's loss is among them) and over each
  step's final loss.
- ``grad0_gap``: the gap of the first gradient's norm, as the optimizer got
  it (the history's entry 0).
- ``dx_gap``: the gap between the NORMS of the coefficients' change after
  the first steps, |x - x0| of the program against the reference's.
- ``x_diff``: the norm of the DIFFERENCE of the two coefficient vectors
  over the reference's change. Rounding in a lower precision scatters the
  coefficients without moving their norm, so this is a number a
  lower-precision control fails where the three above cannot see it.

Those four follow the reference's own path, for the configuration's
``follow_steps`` of the first steps. An L-BFGS direction is a small
difference of large numbers wherever one coordinate (an intercept)
dominates the gradient, so float32 and float64 part ways after the second
iteration by far more than rounding (PERF.md, PR 28): there the path is
followed for one step only. The other numbers stand AT the points ``x`` the
program holds after the last followed step and after EVERY later one of the
first steps (``at_x``: the reference's objective there, and its gradient at
the first of them), where no optimizer amplifies anything:

- ``loss_at_x_gap``: the program's loss at each of those points against the
  reference's, the widest.
- ``grad_at_x_diff``: the norm of the difference of the program's
  (pseudo-)gradient at the first point and the reference's, over the
  reference's norm.
- ``grad_at_x_median``, ``grad_at_x_p99``: the MEDIAN and the 99th
  percentile over coordinates of that difference's size, over the median
  size of the reference's entries. One hot column (an intercept every row
  holds) sums millions of terms, and the float32 rounding of that one sum is
  as large as all that bfloat16 scatters over the other coordinates, so the
  norm cannot tell the two apart; the median coordinate can, and the 99th
  percentile sees a fault in one coordinate of a hundred where the median
  needs half of them.

Two more hold every first step to what the configuration states of a step's
length and a solve's end (``rule``: ``segment_iters``, ``max_iterations``;
the tolerances are the reference's own, ``ref["loss_tol"]`` and
``ref["grad_tol"]``):

- ``iters_off``: how many of the first steps did not advance as stated. A
  step that left its solve running advanced by exactly ``segment_iters``
  iterations; one that ended it advanced by 1 to ``segment_iters`` (to
  ``max_iterations`` where a step is a whole solve); a solve is started
  afresh exactly where the step before ended one. Exact: the limit is 0.
- ``stop_excess``: for every first step that ended its solve, what the rule
  it names tested over that rule's tolerance, the largest (0 where none
  ended): |f_i - f_{i-1}| over the loss tolerance, the gradient norm over its
  tolerance, ``max_iterations`` over the iterations done; a failed line
  search has to have left the loss as it was. A solve that stops before its
  rule is met reads above 1, and 1 is the limit the configuration states.

A configuration is held to the numbers its ``limits`` name, and to no
other.
"""
from __future__ import annotations

import numpy as np


def _gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if not (np.all(np.isfinite(a)) and np.all(np.isfinite(b))):
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def points(observed: list[dict], follow: int | None = None) -> list[dict]:
    """The first steps AT whose end the reference evaluates the objective:
    the last one whose path it followed, and every later one."""
    return observed[(follow or len(observed)) - 1:]


def _relative_quantiles(diff, scale) -> tuple[float, float]:
    if not np.all(np.isfinite(diff)):
        return float("inf"), float("inf")
    size = np.abs(diff)
    unit = max(float(np.median(np.abs(scale))), 1e-300)
    return float(np.median(size)) / unit, float(np.quantile(size, 0.99)) / unit


def stopping(observed: list[dict], ref: dict, rule: dict) -> tuple[int, float]:
    """(``iters_off``, ``stop_excess``) of the first steps."""
    k, t = rule["segment_iters"], rule["max_iterations"]
    off, excess, ended = 0, 0.0, True
    it_before = 0
    for rec in observed:
        it, reason = rec["iterations"], rec["reason"]
        loss, gnorm = rec["loss"], rec["gnorm"]
        advanced = it - (0 if rec["fresh"] else it_before)
        if rec["fresh"] != ended:
            off += 1
        elif reason is None:
            off += advanced != k
        else:
            off += not 1 <= advanced <= (k or t)
        if reason == "function_values":
            excess = max(excess, abs(loss[it] - loss[it - 1]) / ref["loss_tol"])
        elif reason == "gradient":
            excess = max(excess, gnorm[it] / ref["grad_tol"])
        elif reason == "max_iterations":
            excess = max(excess, t / max(it, 1))
        elif reason == "not_improving":
            excess = max(excess, 0.0 if loss[it] == loss[it - 1] else float("inf"))
        ended = reason is not None or k is None
        it_before = it
    return int(off), float(excess)


def compare(observed: list[dict], ref: dict, x0, at_x: list[dict],
            follow: int | None = None, rule: dict | None = None) -> dict:
    """``follow``: how many of the first steps the reference's own path was
    followed for (the configuration's ``follow_steps``; all of them where it
    is not given). ``at_x``: the reference's objective at each of
    ``points(observed, follow)``, with its gradient at the first. The loss is
    held at every one of them; the gradient at the first, where it is far
    from zero (near the optimum its norm falls towards the rounding of its
    own terms). ``rule``: the configuration's stopping rule, where the steps'
    advance and ends are held to it."""
    held = points(observed, follow)
    every = observed
    observed = observed[: follow or len(observed)]
    last = observed[-1]
    n = min(last["iterations"], ref["iterations"]) + 1
    ref_loss = np.asarray(ref["loss"], np.float64)
    gaps = [_gap(last["loss"][:n], ref_loss[:n])]
    for rec in observed:  # each step's own final loss
        i = rec["iterations"]
        gaps.append(_gap(rec["loss"][i], ref_loss[min(i, ref["iterations"])]))
    x0 = np.asarray(x0, np.float64)
    dx_ref = np.linalg.norm(ref["x"] - x0)
    g_diff = held[0]["gradient"] - at_x[0]["gradient"]
    median, p99 = _relative_quantiles(g_diff, at_x[0]["gradient"])
    numbers = {
        "loss_gap": max(gaps),
        "grad0_gap": _gap(last["gnorm"][0], ref["gnorm"][0]),
        "dx_gap": _gap(np.linalg.norm(last["x"] - x0), dx_ref),
        "x_diff": float(np.linalg.norm(last["x"] - ref["x"]) / max(dx_ref, 1e-300))
        if np.all(np.isfinite(last["x"])) else float("inf"),
        "loss_at_x_gap": max(
            _gap(rec["loss"][rec["iterations"]], at["loss"]) for rec, at in zip(held, at_x)
        ),
        "grad_at_x_diff": float(
            np.linalg.norm(g_diff) / max(np.linalg.norm(at_x[0]["gradient"]), 1e-300)
        ) if np.all(np.isfinite(g_diff)) else float("inf"),
        "grad_at_x_median": median,
        "grad_at_x_p99": p99,
    }
    if rule is not None:
        numbers["iters_off"], numbers["stop_excess"] = stopping(every, ref, rule)
    return numbers


def judge(numbers: dict, limits: dict) -> dict:
    """name -> {"value", "limit", "ok"} for every number the configuration
    gives a limit. A number it gives none is not compared in that cell
    (PERF.md names each with its readings) and is left out."""
    return {
        name: {"value": numbers[name], "limit": limit, "ok": bool(numbers[name] <= limit)}
        for name, limit in limits.items()
    }
