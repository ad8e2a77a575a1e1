"""Plain reference for a logistic GLMix (GAME) fit: block coordinate descent
over one sparse fixed effect and per-entity random effects, in NumPy float64
on the host. Nothing here is imported from the program and nothing goes
through the chip or its compiler.

What it follows. GLMix (Zhang et al., KDD 2016) as photon-ml runs it:
``CoordinateDescent`` trains one coordinate at a time on the others' newest
scores as offsets (Gauss-Seidel, in the update order the configuration
gives), rescoring every row of the coordinate afterwards; a fixed-effect
coordinate is one L2-regularised logistic regression over all rows
(``FixedEffectCoordinate``); a random-effect coordinate is one independent
L2-regularised logistic regression per entity over that entity's ACTIVE
rows (``RandomEffectCoordinate``; the rows past the per-entity cap are
passive: scored, never trained on). Each solve is L-BFGS (Nocedal and Wright
7.4, two-loop recursion, gamma = s.y / y.y, at most ``history`` pairs, a
pair kept only where s.y > 1e-10) with a strong-Wolfe line search (Nocedal
and Wright 3.5 and 3.6: bracket by doubling, zoom by safeguarded quadratic
interpolation; c1 1e-4, c2 0.9), the first trial step min(1, 1/|g|) and 1
thereafter, and photon-ml's ``Optimizer.scala`` stopping: absolute
tolerances are the configured tolerance times the loss and the gradient
norm at the ZERO vector; the order is iterations, step failed, loss change,
gradient norm.

Departures from photon-ml, each for a reason:

- Which rows are active is NOT drawn here. photon-ml reservoir-samples them;
  the program draws them from its own generator, and a reference that drew
  other rows would compare two different problems. The runner hands over
  the rows the program kept (``active``), and the cap is applied to those.
- A line-search trial evaluates the loss along the direction from the
  margins z + alpha z_d, not from a fresh product with the features: the
  margins are affine in the step, so the two are the same number, and the
  reads that need no read are not made (as in ``reference.py``). Breeze's
  ``StrongWolfeLineSearch`` interpolates cubically; this one quadratically,
  as the program's does: a line search's accepted step decides the whole
  path after it, and a reference that accepted other steps could hold the
  program to a fixed point only, not to its sweeps.
- Entities are solved in batches of like size (rows padded with weight 0,
  which adds exact zeros) on a few threads. A lane that has stopped is left
  as it is; no lane's arithmetic sees another's. For speed only.
- Normalisation, down-sampling, warm starts and variances are not modelled:
  the configuration uses none.

``precision="bf16"`` is the CONTROL: stored feature values and both operands
of every product with them are rounded to bfloat16, sums stay wide.
``fault`` plants what a broken descent would do, for the readings the limits
are set from: ``"idle_single"`` leaves every other single-row entity of the
first random effect at its start, ``"stale_last"`` trains the last
coordinate on the scores from before the coordinate ahead of it was updated,
``"steepest_fixed"`` gives the fixed effect's solve no curvature pairs (every
direction is the negative gradient: what a lost or misread history does).
"""
from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from benchmarks.lib.reference import SparseOps, to_bf16

CURVATURE_EPS = 1e-10
#: cells (entities x rows x d, float64) a batch of solves holds at most, and
#: the threads that solve batches side by side: together ~3 GB at the peak,
#: on a machine whose accelerator runtime has taken a third of the host's
#: memory before the first array is made
BATCH_CELLS = 1 << 21
THREADS = 8

NOT_CONVERGED, MAX_ITERATIONS, FUNCTION_VALUES, GRADIENT, NOT_IMPROVING = 0, 1, 2, 3, 4


def log1p_exp(z):
    return np.maximum(z, 0.0) + np.log1p(np.exp(-np.abs(z)))


def sigmoid(z):
    e = np.exp(-np.abs(z))
    return np.where(z >= 0, 1.0 / (1.0 + e), e / (1.0 + e))


def loss_and_d1(z, y):
    """Logistic loss and its derivative in the margin, labels 0/1."""
    return log1p_exp(z) - y * z, sigmoid(z) - y


# --- the solver, over a batch of independent problems ----------------------


class Batch:
    """``B`` independent GLMs of one shape: ``forward(v [B, d]) -> [B, R]``,
    ``backward(r [B, R]) -> [B, d]``, labels, offsets and weights [B, R]."""

    def __init__(self, forward, backward, labels, offsets, weights, l2):
        self.forward, self.backward = forward, backward
        self.labels, self.offsets, self.weights, self.l2 = labels, offsets, weights, l2

    def full(self, x):
        """(loss [B], gradient [B, d], margins [B, R]) at ``x``."""
        z = self.offsets + self.forward(x)
        return (*self.from_margins(x, z), z)

    def from_margins(self, x, z):
        losses, d1 = loss_and_d1(z, self.labels)
        f = np.sum(self.weights * losses, axis=1) + 0.5 * self.l2 * np.sum(x * x, axis=1)
        return f, self.backward(self.weights * d1) + self.l2 * x


def _interp(a_lo, phi_lo, dphi_lo, a_hi, phi_hi):
    """Minimiser of the quadratic through (a_lo, phi_lo, dphi_lo) and
    (a_hi, phi_hi), kept a tenth of the bracket from its ends; the midpoint
    where there is none."""
    d = a_hi - a_lo
    denom = phi_hi - phi_lo - dphi_lo * d
    with np.errstate(all="ignore"):
        quad = a_lo - 0.5 * dphi_lo * d * d / np.where(denom == 0.0, 1.0, denom)
    lo, hi = np.minimum(a_lo, a_hi), np.maximum(a_lo, a_hi)
    margin = 0.1 * (hi - lo)
    bad = (denom == 0.0) | (quad < lo + margin) | (quad > hi - margin) | ~np.isfinite(quad)
    return np.where(bad, a_lo + 0.5 * d, quad)


def wolfe_search(phi, f0, dphi0, first_step, *, c1=1e-4, c2=0.9, max_trials=25, expansion=2.0):
    """Strong-Wolfe search along one direction per lane. ``phi(alpha [B]) ->
    (value [B], slope [B])``. Returns (step, value, found, trials): where no
    Wolfe point turns up within ``max_trials``, the best point that met the
    sufficient-decrease test, with ``found`` true; where none did, step 0
    and ``found`` false."""
    zero = np.zeros_like(f0)
    stage = np.zeros(f0.shape, np.int64)  # 0 bracketing, 1 zoom
    done = np.zeros(f0.shape, bool)
    alpha = np.array(first_step, np.float64)
    a_prev, phi_prev, dphi_prev = zero.copy(), f0.copy(), dphi0.copy()
    a_lo, phi_lo, dphi_lo = zero.copy(), f0.copy(), dphi0.copy()
    a_hi, phi_hi = zero.copy(), f0.copy()
    a_star, phi_star, success = zero.copy(), f0.copy(), np.zeros(f0.shape, bool)
    a_best, phi_best, has_best = zero.copy(), f0.copy(), np.zeros(f0.shape, bool)
    trials = np.zeros(f0.shape, np.int64)
    for i in range(max_trials):
        live = ~done
        if not live.any():
            break
        in_zoom = stage == 1
        alpha = np.where(in_zoom, _interp(a_lo, phi_lo, dphi_lo, a_hi, phi_hi), alpha)
        f, dphi = phi(alpha)
        armijo = f <= f0 + c1 * alpha * dphi0
        curv = np.abs(dphi) <= -c2 * dphi0
        wolfe = armijo & curv
        better = armijo & (~has_best | (f < phi_best))
        # bracketing: the minimum is bracketed once the value rises or the
        # sufficient decrease fails, or once the slope turns positive
        to_zoom_hi = ~armijo | ((i > 0) & (f >= phi_prev))
        to_zoom_rev = armijo & (dphi >= 0.0) & ~to_zoom_hi
        br_done = wolfe & ~to_zoom_hi
        enter_zoom = (to_zoom_hi | to_zoom_rev) & ~br_done
        br_lo = (np.where(to_zoom_hi, a_prev, alpha), np.where(to_zoom_hi, phi_prev, f),
                 np.where(to_zoom_hi, dphi_prev, dphi))
        br_hi = (np.where(to_zoom_hi, alpha, a_prev), np.where(to_zoom_hi, f, phi_prev))
        # zoom
        shrink_hi = ~armijo | (f >= phi_lo)
        zm_done = ~shrink_hi & curv
        flip = ~shrink_hi & ~zm_done & (dphi * (a_hi - a_lo) >= 0.0)
        zm_lo = (np.where(shrink_hi, a_lo, alpha), np.where(shrink_hi, phi_lo, f),
                 np.where(shrink_hi, dphi_lo, dphi))
        zm_hi = (np.where(shrink_hi, alpha, np.where(flip, a_lo, a_hi)),
                 np.where(shrink_hi, f, np.where(flip, phi_lo, phi_hi)))
        zm_stuck = np.abs(a_hi - a_lo) * np.maximum(np.abs(dphi0), 1.0) <= 1e-12
        done_now = np.where(in_zoom, zm_done | zm_stuck, br_done)
        star_now = np.where(in_zoom, zm_done, br_done)

        def keep(new, old):  # a lane that is done stays as it is
            return np.where(live, new, old)

        a_best, phi_best = keep(np.where(better, alpha, a_best), a_best), \
            keep(np.where(better, f, phi_best), phi_best)
        has_best = keep(has_best | better, has_best)
        new_lo = [np.where(in_zoom, z, np.where(enter_zoom, b, o))
                  for z, b, o in zip(zm_lo, br_lo, (a_lo, phi_lo, dphi_lo))]
        new_hi = [np.where(in_zoom, z, np.where(enter_zoom, b, o))
                  for z, b, o in zip(zm_hi, br_hi, (a_hi, phi_hi))]
        a_lo, phi_lo, dphi_lo = (keep(n, o) for n, o in zip(new_lo, (a_lo, phi_lo, dphi_lo)))
        a_hi, phi_hi = (keep(n, o) for n, o in zip(new_hi, (a_hi, phi_hi)))
        a_star, phi_star = keep(np.where(star_now, alpha, a_star), a_star), \
            keep(np.where(star_now, f, phi_star), phi_star)
        success = keep(success | star_now, success)
        a_prev, phi_prev, dphi_prev = (
            keep(np.where(in_zoom, o, n), o)
            for n, o in zip((alpha, f, dphi), (a_prev, phi_prev, dphi_prev)))
        next_alpha = np.where(in_zoom | enter_zoom, alpha, alpha * expansion)
        stage = keep(np.where(in_zoom, stage, np.where(enter_zoom, 1, 0)), stage)
        alpha = keep(next_alpha, alpha)
        trials = trials + live
        done = done | (live & done_now)
    use_best = ~success & has_best
    step = np.where(success, a_star, np.where(use_best, a_best, 0.0))
    value = np.where(success, phi_star, np.where(use_best, phi_best, f0))
    return step, value, success | use_best, trials


def two_loop(g, s_hist, y_hist, rho, pairs):
    """-H g from the pairs kept newest first; [m, B, d] histories, ``pairs``
    [B] of them valid in each lane."""
    m = s_hist.shape[0]
    q = g.copy()
    alphas = np.zeros((m,) + g.shape[:1])
    for j in range(m):
        valid = j < pairs
        a = np.where(valid, rho[j] * np.sum(s_hist[j] * q, axis=1), 0.0)
        q = q - a[:, None] * y_hist[j]
        alphas[j] = a
    sy = np.sum(s_hist[0] * y_hist[0], axis=1)
    yy = np.sum(y_hist[0] * y_hist[0], axis=1)
    gamma = np.where((pairs > 0) & (yy > 0), sy / np.where(yy > 0, yy, 1.0), 1.0)
    r = gamma[:, None] * q
    for j in reversed(range(m)):
        valid = j < pairs
        beta = np.where(valid, rho[j] * np.sum(y_hist[j] * r, axis=1), 0.0)
        r = r + s_hist[j] * (alphas[j] - beta)[:, None]
    return -r


def lbfgs(batch: Batch, x0, *, max_iterations, tolerance, history=10, max_trials=25,
          c1=1e-4, c2=0.9):
    """L-BFGS over every lane of ``batch`` from ``x0`` [B, d]. Returns the
    points, losses and gradients reached, the iterations, trials and stop
    reason of each lane, and the loss and gradient-norm histories
    [B, max_iterations + 1] padded with the last value. ``history`` 0 keeps
    no pair: steepest descent with the same search (a planted fault)."""
    b, d = x0.shape
    m = max(1, min(history, max_iterations))
    f_zero, g_zero, _ = batch.full(np.zeros_like(x0))
    loss_tol = np.abs(f_zero) * tolerance
    grad_tol = np.linalg.norm(g_zero, axis=1) * tolerance
    x = x0.copy()
    f, g, z = batch.full(x)
    s_hist, y_hist = np.zeros((m, b, d)), np.zeros((m, b, d))
    rho, pairs = np.zeros((m, b)), np.zeros(b, np.int64)
    it, reason = np.zeros(b, np.int64), np.zeros(b, np.int64)
    evals = np.full(b, 2, np.int64)
    loss_hist = np.repeat(f[:, None], max_iterations + 1, axis=1)
    gnorm_hist = np.repeat(np.linalg.norm(g, axis=1)[:, None], max_iterations + 1, axis=1)
    while True:
        live = reason == NOT_CONVERGED
        if not live.any():
            break
        direction = two_loop(g, s_hist, y_hist, rho, pairs)
        descent = np.sum(direction * g, axis=1) < 0
        direction = np.where(descent[:, None], direction, -g)
        gnorm = np.linalg.norm(g, axis=1)
        first = np.where(pairs == 0, np.minimum(1.0, 1.0 / np.maximum(gnorm, 1e-12)), 1.0)
        z_d = batch.forward(direction)
        xx, xd, dd = (np.sum(a * c, axis=1) for a, c in ((x, x), (x, direction), (direction, direction)))

        def phi(alpha):
            losses, d1 = loss_and_d1(z + alpha[:, None] * z_d, batch.labels)
            value = np.sum(batch.weights * losses, axis=1) \
                + 0.5 * batch.l2 * (xx + 2.0 * alpha * xd + alpha * alpha * dd)
            slope = np.sum(batch.weights * d1 * z_d, axis=1) + batch.l2 * (xd + alpha * dd)
            return value, slope

        step, f_new, found, trials = wolfe_search(
            phi, f, np.sum(g * direction, axis=1), first, c1=c1, c2=c2, max_trials=max_trials)
        x_new = x + step[:, None] * direction
        z_new = z + step[:, None] * z_d
        _, d1 = loss_and_d1(z_new, batch.labels)
        g_new = batch.backward(batch.weights * d1) + batch.l2 * x_new
        s_vec, y_vec = x_new - x, g_new - g
        sy = np.sum(s_vec * y_vec, axis=1)
        push = live & (sy > CURVATURE_EPS) & (history > 0)  # history 0: steepest descent
        for hist, row in ((s_hist, s_vec), (y_hist, y_vec)):
            hist[1:, push], hist[0, push] = hist[:-1, push], row[push]
        rho[1:, push], rho[0, push] = rho[:-1, push], 1.0 / sy[push]
        pairs = pairs + push
        it_new = it + 1
        gnorm_new = np.linalg.norm(g_new, axis=1)
        verdict = np.where(
            it_new >= max_iterations, MAX_ITERATIONS,
            np.where(~found, NOT_IMPROVING,
                     np.where(np.abs(f_new - f) <= loss_tol, FUNCTION_VALUES,
                              np.where(gnorm_new <= grad_tol, GRADIENT, NOT_CONVERGED))))
        rows = np.flatnonzero(live)
        loss_hist[rows, it_new[rows]] = f_new[rows]
        gnorm_hist[rows, it_new[rows]] = gnorm_new[rows]
        lv = live[:, None]
        x, g, z = np.where(lv, x_new, x), np.where(lv, g_new, g), np.where(lv, z_new, z)
        f = np.where(live, f_new, f)
        it, evals = np.where(live, it_new, it), evals + np.where(live, trials, 0)
        reason = np.where(live, verdict, reason)
    steps = np.arange(max_iterations + 1)[None, :]
    loss_hist = np.where(steps <= it[:, None], loss_hist, f[:, None])
    gnorm_hist = np.where(steps <= it[:, None], gnorm_hist, np.linalg.norm(g, axis=1)[:, None])
    return {"x": x, "value": f, "gradient": g, "iterations": it, "evaluations": evals,
            "reason": reason, "loss": loss_hist, "gnorm": gnorm_hist,
            "loss_tol": loss_tol, "grad_tol": grad_tol}


# --- the deployment ---------------------------------------------------------


class RandomEffect:
    """One random-effect coordinate: ``ids`` [n] the entity of every row,
    ``features`` [n, d], ``active`` [n] whether the program trains on the
    row. Entities are grouped by their count of active rows, padded to the
    group's largest with rows of weight 0."""

    def __init__(self, name, ids, features, active, entities, l2, precision):
        self.name, self.ids, self.entities, self.l2 = name, np.asarray(ids), int(entities), l2
        self.low = precision == "bf16"
        self.x = to_bf16(features) if self.low else np.asarray(features)
        self.d = self.x.shape[1]
        rows = np.flatnonzero(active)
        rows = rows[np.argsort(self.ids[rows], kind="stable")]
        counts = np.bincount(self.ids[rows], minlength=self.entities)
        starts = np.concatenate(([0], np.cumsum(counts)))[:-1]
        self.counts = counts
        # size groups: 1, 2, 3, 4, then up to each power of two
        level = np.where(counts <= 4, counts, 1 << np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64))
        self.groups = []
        for r in np.unique(level[counts > 0]):
            ents = np.flatnonzero((level == r) & (counts > 0))
            per = max(1, BATCH_CELLS // (int(r) * self.d))
            for lo in range(0, len(ents), per):
                e = ents[lo:lo + per]
                slot = np.arange(int(r))[None, :]
                valid = slot < counts[e][:, None]
                pos = rows[np.minimum(starts[e][:, None] + slot, len(rows) - 1)]
                self.groups.append((e, pos, valid.astype(np.float64)))

    def score(self, table):
        """x_row . w_entity(row) for every row, active or passive."""
        t = to_bf16(table).astype(np.float64) if self.low else table
        out = np.empty(len(self.ids))
        step = 1 << 18
        for lo in range(0, len(self.ids), step):
            sl = slice(lo, lo + step)
            out[sl] = np.einsum("nd,nd->n", self.x[sl], t[self.ids[sl]], dtype=np.float64)
        return out

    def _batch(self, group, labels, offsets):
        ents, pos, valid = group
        feats = self.x[pos].astype(np.float64)  # [B, R, d]
        low = self.low

        def forward(v):
            return np.einsum("brd,bd->br", feats, to_bf16(v).astype(np.float64) if low else v)

        def backward(r):
            return np.einsum("brd,br->bd", feats, to_bf16(r).astype(np.float64) if low else r)

        return Batch(forward, backward, labels[pos], offsets[pos], valid, self.l2)

    def train(self, table, labels, offsets, solver, skip=None):
        """Every entity's solve from its row of ``table``; returns the new
        table. ``skip`` [entities] marks entities left as they are."""
        new = table.copy()

        def one(group):
            ents = group[0]
            res = lbfgs(self._batch(group, labels, offsets), table[ents], **solver)
            return ents, res["x"]

        with cf.ThreadPoolExecutor(THREADS) as pool:
            for ents, x in pool.map(one, self.groups):
                keep = np.ones(len(ents), bool) if skip is None else ~skip[ents]
                new[ents[keep]] = x[keep]
        return new

    def gradient(self, table, labels, offsets):
        """[entities, d]: the gradient of every entity's own objective (its
        active rows, the other coordinates as offsets) at its row of
        ``table``."""
        grad = self.l2 * table
        for group in self.groups:
            ents = group[0]
            grad[ents] = self._batch(group, labels, offsets).full(table[ents])[1]
        return grad


class Glmix:
    """The deployment as the reference sees it: the fixed effect's ELL block,
    the random effects, the labels, and the update order fixed -> random
    effects in the order given."""

    def __init__(self, config: dict, inputs: dict, precision=None):
        feat, solver = config["features"], config["solver"]
        self.precision = precision or "f64"
        self.d = feat["d"]
        self.labels = np.asarray(inputs["labels"], np.float64)
        self.ops = SparseOps(inputs["fe_cols"], inputs["fe_vals"], self.d, precision=self.precision)
        self.l2 = solver["l2_weight"]
        self.res = [
            RandomEffect(name, inputs[name + ".ids"], inputs[name + ".features"],
                         inputs[name + ".active"], re["entities"], self.l2, self.precision)
            for name, re in config["random_effects"].items()
        ]
        self.fe_solver = {"max_iterations": solver["fe_max_iterations"],
                          "max_trials": solver["fe_ls_max_iterations"],
                          "tolerance": solver["fe_tolerance"], "history": solver["history"]}
        self.re_solver = {"max_iterations": solver["re_max_iterations"],
                          "max_trials": solver["re_ls_max_iterations"],
                          "tolerance": solver["re_tolerance"], "history": solver["history"]}

    # a point is (w [d], [table [entities, d] per random effect])
    def zero(self):
        return np.zeros(self.d), [np.zeros((re.entities, re.d)) for re in self.res]

    def pack(self, point) -> np.ndarray:
        return np.concatenate([point[0]] + [t.reshape(-1) for t in point[1]])

    def unpack(self, x):
        x = np.asarray(x, np.float64)
        w, at, tables = x[: self.d], self.d, []
        for re in self.res:
            tables.append(x[at: at + re.entities * re.d].reshape(re.entities, re.d))
            at += re.entities * re.d
        return w, tables

    def scores(self, point):
        return [self.ops.forward(point[0])] + [re.score(t) for re, t in zip(self.res, point[1])]

    def _fe_batch(self, offsets):
        ones = np.ones((1, len(self.labels)))
        return Batch(lambda v: self.ops.forward(v[0])[None], lambda r: self.ops.backward(r[0])[None],
                     self.labels[None], offsets[None], ones, self.l2)

    def evaluate(self, point, gradient=True, scores=None) -> dict:
        """The whole regularised objective at ``point`` (every row, active or
        passive, and every coefficient's L2 term) and, by coordinate, the
        gradient of the objective that coordinate trains on: the fixed effect
        over every row, each entity over its active rows, the others as
        offsets."""
        scores = scores or self.scores(point)
        total = np.sum(scores, axis=0)
        losses, d1 = loss_and_d1(total, self.labels)
        reg = np.sum(point[0] ** 2) + sum(np.sum(t * t) for t in point[1])
        out = {"loss": float(np.sum(losses) + 0.5 * self.l2 * reg)}
        if gradient:
            parts = [self.ops.backward(d1) + self.l2 * point[0]]
            for re, t, s in zip(self.res, point[1], scores[1:]):
                parts.append(re.gradient(t, self.labels, total - s).reshape(-1))
            out["gradient"] = np.concatenate(parts)
        return out

    def fixed_from_zero(self, iterations: int) -> dict:
        """The fixed effect's solve of the FIRST sweep, for its first
        ``iterations`` iterations: from the zero point the random effects
        score nothing, so its objective is the whole objective. ``loss[i]``
        and ``gnorm[i]`` after iteration i, padded with the last where it
        stopped before."""
        solver = {**self.fe_solver, "max_iterations": iterations}
        res = lbfgs(self._fe_batch(np.zeros(len(self.labels))), np.zeros((1, self.d)), **solver)
        return {"loss": res["loss"][0], "gnorm": res["gnorm"][0]}

    def descend(self, sweeps: int, fault=None, fe_path=None) -> dict:
        """``sweeps`` Gauss-Seidel sweeps from the zero point. ``loss[i]`` and
        ``gnorm[i]`` are the whole objective and the norm of its gradient by
        coordinate after sweep i (entry 0: the zero point); ``fe_path[i]``
        is the fixed effect after sweep i + 1, ``fe_first`` the loss and
        gradient-norm histories of the first sweep's fixed-effect solve.
        Given a ``fe_path``, the fixed effect is not solved: sweep i takes
        ``fe_path[i]`` for it, and the random effects are trained on its
        scores (the fit HELD to another fit's fixed effects)."""
        w, tables = self.zero()
        scores = self.scores((w, tables))
        first = self.evaluate((w, tables), scores=scores)
        loss, gnorm = [first["loss"]], [float(np.linalg.norm(first["gradient"]))]
        fe_iterations, path, fe_first = [], [], None
        fe_solver = {**self.fe_solver, "history": 0} if fault == "steepest_fixed" else self.fe_solver
        for i in range(sweeps):
            if fe_path is None:
                total = np.sum(scores, axis=0)
                res = lbfgs(self._fe_batch(total - scores[0]), w[None], **fe_solver)
                w = res["x"][0]
                fe_iterations.append(int(res["iterations"][0]))
                if i == 0:
                    fe_first = {"loss": res["loss"][0], "gnorm": res["gnorm"][0]}
            else:
                w = np.asarray(fe_path[i], np.float64)
            path.append(w)
            before = list(scores)
            scores[0] = self.ops.forward(w)
            for k, re in enumerate(self.res, start=1):
                seen = list(scores)
                if fault == "stale_last" and k == len(self.res):
                    seen[k - 1] = before[k - 1]
                skip = None
                if fault == "idle_single" and k == 1:
                    skip = (re.counts == 1) & (np.arange(re.entities) % 2 == 0)
                offsets = np.sum(seen, axis=0) - seen[k]
                tables[k - 1] = re.train(tables[k - 1], self.labels, offsets, self.re_solver, skip)
                scores[k] = re.score(tables[k - 1])
            at = self.evaluate((w, tables), scores=scores)
            loss.append(at["loss"])
            gnorm.append(float(np.linalg.norm(at["gradient"])))
        return {"x": self.pack((w, tables)), "loss": np.array(loss), "gnorm": np.array(gnorm),
                "iterations": sweeps, "fe_iterations": fe_iterations, "fe_path": path,
                "fe_first": fe_first, "gradient": at["gradient"],
                # a fit ends on its sweep count; no tolerance stops it
                "loss_tol": 0.0, "grad_tol": 0.0}
