"""Plain references: the same semantics as the program's solves, written
straight from the published algorithms in NumPy float64, with no kernel, no
layout and nothing imported from the program.

- ``tron``: trust-region Newton with truncated conjugate gradient (Lin and
  More 1999, as in LIBLINEAR's ``tron.cpp`` and photon-ml's ``TRON.scala``:
  eta 1e-4/0.25/0.75, sigma 0.25/0.5/4, at most ``max_cg`` CG steps to a
  residual of ``cg_tol`` times the gradient).
- ``owlqn``: Andrew and Gao 2007: pseudo-gradient, two-loop L-BFGS direction
  (Nocedal and Wright 7.4, gamma = s.y / y.y) aligned to the orthant,
  backtracking by halves with projection onto the orthant and the Armijo
  test along the projected displacement.

Both follow photon-ml's ``Optimizer.scala`` for stopping: absolute
tolerances are the configured tolerance times the loss and the gradient
norm at the ZERO coefficient vector; the order is iterations, step failed,
loss change, gradient norm.

Everything runs on the host in float64. The feature passes come from an
operator with ``forward(v) -> X v`` and ``backward(r) -> X' r``, ``SparseOps``
or ``DenseOps``, both over row blocks on a few threads so that a reference
ends inside a window's length. Passes that need no read are not made (X 0;
the margins at an accepted point).

``precision="bf16"`` turns an operator into the CONTROL: the nearest
precision below the float32 the configurations state. Stored values and
both operands of every product are rounded to bfloat16, sums stay wide:
what a bf16 feature block with f32 accumulation computes.
"""
from __future__ import annotations

import concurrent.futures as cf
import threading

import numpy as np

LOSSES = {
    # name: (loss, d1, d2) of the margin z and the label y
    "squared": (
        lambda z, y: 0.5 * (z - y) ** 2,
        lambda z, y: z - y,
        lambda z, y: np.ones_like(z),
    ),
    "poisson": (
        lambda z, y: np.exp(z) - y * z,
        lambda z, y: np.exp(z) - y,
        lambda z, y: np.exp(z),
    ),
}


def to_bf16(a: np.ndarray) -> np.ndarray:
    """Round float values to the nearest bfloat16 (ties to even), returned
    in float32."""
    bits = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))) & np.uint32(
        0xFFFF0000
    )
    return rounded.view(np.float32)


class SparseOps:
    """X v and X' r of a padded-ELL block ``indices``/``values`` [n, k] over
    ``d`` columns, in float64, row blocks on a few threads."""

    def __init__(self, indices, values, d, *, precision="f64", threads=12, block=1 << 17):
        self.idx = np.asarray(indices)
        self.low = precision == "bf16"
        self.vals = to_bf16(values) if self.low else np.asarray(values)
        self.d = int(d)
        n = self.idx.shape[0]
        self.blocks = [(lo, min(lo + block, n)) for lo in range(0, n, block)]
        self.threads = threads
        self.n = n

    def _map(self, fn):
        with cf.ThreadPoolExecutor(self.threads) as pool:
            return list(pool.map(fn, self.blocks))

    def forward(self, v):
        table = to_bf16(v).astype(np.float64) if self.low else np.asarray(v, np.float64)

        def part(b):
            lo, hi = b
            return np.einsum(
                "nk,nk->n", table[self.idx[lo:hi]], self.vals[lo:hi], dtype=np.float64
            )

        return np.concatenate(self._map(part))

    def backward(self, r):
        r = to_bf16(r).astype(np.float64) if self.low else np.asarray(r, np.float64)

        def part(b):
            lo, hi = b
            contrib = self.vals[lo:hi].astype(np.float64) * r[lo:hi, None]
            return np.bincount(
                self.idx[lo:hi].reshape(-1), weights=contrib.reshape(-1), minlength=self.d
            )

        return np.sum(self._map(part), axis=0)


class DenseOps:
    """X v and X' r of a dense float32 block, in float64 on the host. The
    block is the benchmark's own (made from the seed, on the device): it is
    read back once, so that no product of the reference goes through the chip
    or its compiler. Row blocks on a few threads, each widened to float64 in
    a scratch buffer its thread keeps (the host pays dearly for memory it has
    not touched yet), BLAS held to one thread each.
    ``precision="bf16"`` rounds the block and both operands to bfloat16
    first: the control."""

    def __init__(self, features, *, precision="f64", threads=12, block=1 << 10):
        self.low = precision == "bf16"
        self.n, self.d = features.shape
        self.blocks = [(lo, min(lo + block, self.n)) for lo in range(0, self.n, block)]
        self.threads = threads
        self.x = np.asarray(features, dtype=np.float32)  # the one read-back
        if self.low:
            self.x = np.concatenate([to_bf16(self.x[lo:hi]) for lo, hi in self.blocks])
        self._scratch = threading.local()

    def _wide(self, b):
        buf = getattr(self._scratch, "buf", None)
        if buf is None:
            buf = self._scratch.buf = np.empty((self.blocks[0][1], self.d), np.float64)
        out = buf[: b[1] - b[0]]
        np.copyto(out, self.x[b[0]:b[1]])
        return out

    def _map(self, fn):
        import threadpoolctl

        with threadpoolctl.threadpool_limits(1), cf.ThreadPoolExecutor(self.threads) as pool:
            return list(pool.map(fn, self.blocks))

    def forward(self, v):
        v = to_bf16(v).astype(np.float64) if self.low else np.asarray(v, np.float64)
        return np.concatenate(self._map(lambda b: self._wide(b) @ v))

    def backward(self, r):
        r = to_bf16(r).astype(np.float64) if self.low else np.asarray(r, np.float64)
        return np.sum(self._map(lambda b: r[b[0]:b[1]] @ self._wide(b)), axis=0)


class Objective:
    """value = sum_i weight_i l(z_i, y_i) + l2/2 |w|^2, z = X w + offset."""

    def __init__(self, ops, loss, labels, l2, offsets=None, weights=None):
        self.ops = ops
        self.loss, self.d1, self.d2 = LOSSES[loss]
        self.y = np.asarray(labels, np.float64)
        self.l2 = float(l2)
        self.off = 0.0 if offsets is None else np.asarray(offsets, np.float64)
        self.wt = 1.0 if weights is None else np.asarray(weights, np.float64)

    def margins(self, w):
        if not np.any(w):  # X 0 is 0: no pass
            return np.zeros(self.ops.n) + self.off
        return self.ops.forward(w) + self.off

    def value_at(self, w, z):
        return float(np.sum(self.wt * self.loss(z, self.y)) + 0.5 * self.l2 * (w @ w))

    def grad_at(self, w, z):
        return self.ops.backward(self.wt * self.d1(z, self.y)) + self.l2 * w

    def value_grad(self, w):
        z = self.margins(w)
        return self.value_at(w, z), self.grad_at(w, z), z

    def hessian_operator(self, z):
        """v -> H v at the point whose margins are ``z``."""
        curv = self.wt * self.d2(z, self.y)
        return lambda v: self.ops.backward(curv * self.ops.forward(v)) + self.l2 * v


def _reason(it, f, f_prev, gnorm, loss_tol, grad_tol, max_it, step_failed):
    if it >= max_it:
        return "max_iterations"
    if step_failed:
        return "not_improving"
    if abs(f - f_prev) <= loss_tol:
        return "function_values"
    if gnorm <= grad_tol:
        return "gradient"
    return None


def _truncated_cg(hv, g, delta, max_cg, cg_tol):
    d = np.zeros_like(g)
    r = -g
    p = r.copy()
    rtr = r @ r
    tol = cg_tol * np.linalg.norm(g)
    for _ in range(max_cg):
        if np.sqrt(rtr) <= tol:
            break
        hp = hv(p)
        php = p @ hp
        alpha = rtr / php if php > 0 else 0.0
        d_new = d + alpha * p
        if np.linalg.norm(d_new) > delta or php <= 0:
            # back to the boundary along p
            std, dd, pp = d @ p, d @ d, p @ p
            rad = np.sqrt(max(std * std + pp * (delta * delta - dd), 0.0))
            if std >= 0:
                a = (delta * delta - dd) / (std + rad if std + rad > 0 else 1.0)
            else:
                a = (rad - std) / (pp if pp > 0 else 1.0)
            return d + a * p, r - a * hp
        d = d_new
        r = r - alpha * hp
        rtr_new = r @ r
        p = r + (rtr_new / rtr) * p
        rtr = rtr_new
    return d, r


def tron(obj: Objective, x0, *, max_iterations=15, tolerance=1e-5, max_cg=20,
         cg_tol=0.1):
    """Returns ``{"loss": [f_0, f_1, ...], "gnorm": [...], "x": x, "iterations": it,
    "gradient": g, "reason": why it stopped, "loss_tol", "grad_tol": the
    absolute tolerances of the stopping rule}`` with entry i the state after
    iteration i."""
    eta0, eta1, eta2 = 1e-4, 0.25, 0.75
    s1, s2, s3 = 0.25, 0.5, 4.0
    x = np.asarray(x0, np.float64)
    f_zero, g_zero, _ = obj.value_grad(np.zeros_like(x))
    loss_tol = abs(f_zero) * tolerance
    grad_tol = np.linalg.norm(g_zero) * tolerance
    f, g, z = obj.value_grad(x)
    delta = np.linalg.norm(g)
    loss, gnorm = [f], [np.linalg.norm(g)]
    it, reason = 0, None
    while reason is None:
        step, r = _truncated_cg(obj.hessian_operator(z), g, delta, max_cg, cg_tol)
        snorm = np.linalg.norm(step)
        gs = g @ step
        prered = -0.5 * (gs - step @ r)
        f_new, g_new, z_new = obj.value_grad(x + step)
        actred = f - f_new
        denom = f_new - f - gs
        alpha = s3 if denom <= 0 else max(s1, -0.5 * (gs / denom))
        if it == 0:
            delta = min(delta, snorm)
        if actred < eta0 * prered:
            delta = min(max(alpha, s1) * snorm, s2 * delta)
        elif actred < eta1 * prered:
            delta = max(s1 * delta, min(alpha * snorm, s2 * delta))
        elif actred < eta2 * prered:
            delta = max(s1 * delta, min(alpha * snorm, s3 * delta))
        else:
            delta = max(delta, min(alpha * snorm, s3 * delta))
        accept = actred > eta0 * prered
        f_prev = f
        if accept:
            x, f, g, z = x + step, f_new, g_new, z_new
        it += 1
        reason = _reason(it, f, f_prev, np.linalg.norm(g), loss_tol, grad_tol,
                         max_iterations, (not accept) and delta <= 1e-12)
        if not accept and reason == "function_values":
            reason = None
        loss.append(f)
        gnorm.append(np.linalg.norm(g))
    return {"loss": loss, "gnorm": gnorm, "x": x, "iterations": it, "gradient": g,
            "reason": reason, "loss_tol": loss_tol, "grad_tol": grad_tol}


def pseudo_gradient(x, g, l1):
    lo, hi = g - l1, g + l1
    at_zero = np.where(hi < 0, hi, np.where(lo > 0, lo, 0.0))
    return np.where(x != 0.0, g + l1 * np.sign(x), at_zero)


def _two_loop(pg, pairs):
    """-H pg from the (s, y) pairs, newest last."""
    q = pg.copy()
    alphas = []
    for s, y in reversed(pairs):
        a = (s @ q) / (s @ y)
        alphas.append(a)
        q -= a * y
    if pairs:
        s, y = pairs[-1]
        q *= (s @ y) / (y @ y)
    for (s, y), a in zip(pairs, reversed(alphas)):
        b = (y @ q) / (s @ y)
        q += s * (a - b)
    return -q


def owlqn(obj: Objective, x0, l1, *, max_iterations=100, tolerance=1e-7,
          num_corrections=10, ls_max=25, c1=1e-4, stop_after=None):
    """Returns the same record as ``tron``; ``loss`` is the full objective
    f + l1 |x|_1 and ``gnorm`` the norm of the pseudo-gradient. ``stop_after``
    caps the iterations (to follow a program's first segments)."""
    x = np.asarray(x0, np.float64)
    full = lambda fs, w: fs + l1 * np.sum(np.abs(w))  # noqa: E731
    f_zero, g_zero, _ = obj.value_grad(np.zeros_like(x))
    loss_tol = abs(f_zero) * tolerance
    grad_tol = np.linalg.norm(pseudo_gradient(np.zeros_like(x), g_zero, l1)) * tolerance
    fs, g = (f_zero, g_zero) if not np.any(x) else obj.value_grad(x)[:2]
    f = full(fs, x)
    loss, gnorm = [f], [np.linalg.norm(pseudo_gradient(x, g, l1))]
    pairs: list = []
    it, reason = 0, None
    cap = max_iterations if stop_after is None else min(max_iterations, stop_after)
    while reason is None and it < cap:
        pg = pseudo_gradient(x, g, l1)
        direction = _two_loop(pg, pairs)
        direction = np.where(direction * pg < 0.0, direction, 0.0)
        if direction @ direction == 0.0:
            direction = -pg
        xi = np.where(x != 0.0, np.sign(x), np.sign(-pg))
        step = min(1.0, 1.0 / max(np.linalg.norm(pg), 1e-12)) if not pairs else 1.0
        ok = False
        x_new, f_new, z_new = x, f, None
        for _ in range(ls_max):
            cand = x + step * direction
            cand = np.where(np.sign(cand) == xi, cand, 0.0)
            z = obj.margins(cand)
            f_cand = full(obj.value_at(cand, z), cand)
            dx = cand - x
            step *= 0.5
            if f_cand <= f + c1 * (pg @ dx) and dx @ dx > 0.0:
                ok, x_new, f_new, z_new = True, cand, f_cand, z
                break
        g_new = obj.grad_at(x_new, z_new) if ok else g
        s_vec, y_vec = x_new - x, g_new - g
        if s_vec @ y_vec > 1e-10:
            pairs = (pairs + [(s_vec, y_vec)])[-num_corrections:]
        f_prev = f
        x, f, g = x_new, f_new, g_new
        it += 1
        pgn = np.linalg.norm(pseudo_gradient(x, g, l1))
        reason = _reason(it, f, f_prev, pgn, loss_tol, grad_tol, max_iterations, not ok)
        loss.append(f)
        gnorm.append(pgn)
    return {"loss": loss, "gnorm": gnorm, "x": x, "iterations": it,
            "gradient": pseudo_gradient(x, g, l1), "reason": reason,
            "loss_tol": loss_tol, "grad_tol": grad_tol}


def evaluate_at(obj: Objective, x, l1: float = 0.0, gradient: bool = True) -> dict:
    """The full objective f + l1 |x|_1 and, where asked for (one pass more),
    its (pseudo-)gradient AT ``x``: what the program should hold at the point
    where it stands."""
    x = np.asarray(x, np.float64)
    z = obj.margins(x)
    at = {"loss": obj.value_at(x, z) + l1 * np.sum(np.abs(x))}
    if gradient:
        at["gradient"] = pseudo_gradient(x, obj.grad_at(x, z), l1)
    return at
