"""Inputs from seeds. Data are this system's weights.

A configuration's STRUCTURE (which column every stored slot names: what
decides window and bucket shapes, and so the compiled programs) comes from
``structure_seed`` in its file and is the same in every run. The VALUES
(features, the true model, labels, the start point) come from ``--seed``.

The sparse scheme is ``bench.config_sparse_poisson``'s and the dense one
``bench.config_tron``'s (copies: the originals stay with the old bench and
are listed in PERF.md for deletion).
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, salt: int) -> np.random.Generator:
    """A generator for any whole-number seed (the driver's pass 2**31)."""
    return np.random.default_rng([int(seed), int(salt)])


def sparse_structure(n: int, d: int, k: int, structure_seed: int) -> np.ndarray:
    """[n, k] int32 column indices: slot 0 of every row is column 0 (the
    intercept, one hot column), the rest uniform over 1..d-1."""
    rng = rng_for(structure_seed, 0)
    idx = rng.integers(1, d, size=(n, k), dtype=np.int32)
    idx[:, 0] = 0
    return idx


def sparse_poisson_values(idx: np.ndarray, d: int, seed: int) -> dict:
    """Values and Poisson labels drawn from a true model, for a structure
    ``idx``; float32, as served. The start point is the zero vector, where
    photon-ml starts a solve: from ``bench``'s 1e-6 x normal start (there to
    defeat a replay cache that is gone) every coordinate begins in a random
    orthant a rounding error wide, and which coordinates the first steps clip
    is then decided by rounding: float32 and float64 part ways at the second
    iteration (PERF.md, PR 28)."""
    n, k = idx.shape
    rng = rng_for(seed, 1)
    vals = rng.standard_normal((n, k), dtype=np.float32) / np.float32(np.sqrt(k))
    vals[:, 0] = 1.0
    w_true = (rng.standard_normal(d) * 0.3).astype(np.float32)
    margin = np.zeros(n, np.float64)
    step = 1 << 18
    for lo in range(0, n, step):  # in blocks: the gathered table is 8 B a slot
        sl = slice(lo, lo + step)
        margin[sl] = np.einsum("nk,nk->n", vals[sl], w_true[idx[sl]], dtype=np.float64)
    rate = np.exp(np.clip(margin - 0.5, -4.0, 3.0))
    labels = rng.poisson(rate).astype(np.float32)
    w0 = np.zeros(d, np.float32)
    return {"values": vals, "labels": labels, "w0": w0}


def dense_linear(n: int, d: int, seed: int, chunks: int = 16):
    """(X [n, d] f32, labels [n], w0 [d]) made on the device in ONE jitted
    call from the seed: X standard normal, labels = X w_true + 0.1 noise
    with w_true ~ 0.1 normal, start point 0.01 normal. X is written chunk by
    chunk into one buffer so that making it does not set the memory peak."""
    import jax
    import jax.numpy as jnp

    rows = n // chunks
    if rows * chunks != n:
        raise ValueError(f"n={n} is not a multiple of {chunks} chunks")

    @jax.jit
    def make(key):
        kx, kw, kn, k0 = jax.random.split(key, 4)
        w_true = 0.1 * jax.random.normal(kw, (d,), jnp.float32)

        def body(i, carry):
            x, y = carry
            blk = jax.random.normal(jax.random.fold_in(kx, i), (rows, d), jnp.float32)
            yb = jnp.matmul(blk, w_true, precision=jax.lax.Precision.HIGHEST)
            x = jax.lax.dynamic_update_slice(x, blk, (i * rows, 0))
            y = jax.lax.dynamic_update_slice(y, yb, (i * rows,))
            return x, y

        x, y = jax.lax.fori_loop(
            0, chunks, body, (jnp.zeros((n, d), jnp.float32), jnp.zeros((n,), jnp.float32))
        )
        y = y + 0.1 * jax.random.normal(kn, (n,), jnp.float32)
        w0 = 0.01 * jax.random.normal(k0, (d,), jnp.float32)
        return x, y, w0

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return make(key)
