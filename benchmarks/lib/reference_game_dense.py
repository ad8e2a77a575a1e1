"""Plain reference for a logistic GLMix (GAME) fit over a DENSE fixed
effect: ``reference_game.py``'s block coordinate descent (L-BFGS with the
strong-Wolfe search, Gauss-Seidel over fixed -> random effects in the order
given, entities batched by size, the rows the program's build kept for each
capped entity), with the fixed effect's two products made over a dense
``[n, d]`` float32 block, row block by row block, each widened to float64 in
a scratch buffer (``reference.DenseOps``). NumPy float64 on the host;
nothing here is imported from the program and nothing goes through the chip
or its compiler.

``precision="bf16"`` is the CONTROL, as in ``reference_game.py``: stored
feature values and both operands of every product with them are rounded to
bfloat16, sums stay wide.

Faults, for the readings the limits are set from: ``reference_game``'s
``"stale_last"`` and ``"steepest_fixed"`` as they stand, and one of this
deployment's own: ``"idle_capped"`` leaves every other CAPPED entity of the
first random effect at its start (the entities that hold the cap's rows or
more: the row-heavy bucket this deployment exists for; there is no
single-row entity here for ``"idle_single"`` to leave out).
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib import reference_game
from benchmarks.lib.reference import DenseOps


class GlmixDense(reference_game.Glmix):
    """The deployment as the reference sees it: the fixed effect's dense
    block ``inputs["fe_x"]``, the random effects, the labels."""

    def __init__(self, config: dict, inputs: dict, precision=None):
        feat, solver = config["features"], config["solver"]
        self.precision = precision or "f64"
        self.d = feat["d"]
        self.labels = np.asarray(inputs["labels"], np.float64)
        self.ops = DenseOps(inputs["fe_x"], precision=self.precision, block=1 << 12)
        self.l2 = solver["l2_weight"]
        self.caps = [re["cap"] for re in config["random_effects"].values()]
        self.res = [
            reference_game.RandomEffect(
                name, inputs[name + ".ids"], inputs[name + ".features"],
                inputs[name + ".active"], re["entities"], self.l2, self.precision)
            for name, re in config["random_effects"].items()
        ]
        self.fe_solver = {"max_iterations": solver["fe_max_iterations"],
                          "max_trials": solver["fe_ls_max_iterations"],
                          "tolerance": solver["fe_tolerance"], "history": solver["history"]}
        self.re_solver = {"max_iterations": solver["re_max_iterations"],
                          "max_trials": solver["re_ls_max_iterations"],
                          "tolerance": solver["re_tolerance"], "history": solver["history"]}

    def descend(self, sweeps: int, fault=None, fe_path=None) -> dict:
        if fault != "idle_capped":
            return super().descend(sweeps, fault=fault, fe_path=fe_path)
        first = self.res[0]
        idle = (first.counts >= self.caps[0]) & (np.arange(first.entities) % 2 == 0)
        train = first.train
        first.train = lambda table, labels, offsets, solver, skip=None: train(
            table, labels, offsets, solver, idle)
        try:
            return super().descend(sweeps, fe_path=fe_path)
        finally:
            del first.train
