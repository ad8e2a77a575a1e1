"""A GLMix (GAME) deployment from seeds: one sparse fixed-effect shard and
one dense random-effect shard per entity type, after ``chip_smoke.generate``
and ``bench._run_game_config`` (copies of their scheme; the originals stay
with the smoke and the old bench).

STRUCTURE comes from the configuration's ``structure_seed`` and is the same
in every run: which column every stored fixed-effect slot names (slot 0 of
every row is column 0, the intercept; no column twice in a row) and which
entity of each type every
row belongs to (Zipf ids, every entity seen at least once). It decides the
window layout, the random-effect buckets and so the compiled programs.
VALUES come from ``--seed``: feature values, the true model, the labels.
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib.datagen import rng_for


def zipf_ids(rng, n: int, entities: int, a: float) -> np.ndarray:
    """[n] int64 entity ids, Zipf(a) folded onto ``entities``; the first
    ``entities`` rows walk every entity once, so none is unseen."""
    if n < entities:
        raise ValueError(f"{n} rows cannot cover {entities} entities")
    ids = ((rng.zipf(a, size=n) - 1) % entities).astype(np.int64)
    ids[:entities] = rng.permutation(entities)
    return ids


def fe_structure(n: int, d: int, k: int, structure_seed: int) -> np.ndarray:
    """[n, k] int32 column indices, ascending in every row: slot 0 is column
    0 (the intercept), the rest distinct columns of 1..d-1. Distinct, as
    ``chip_smoke.generate`` has them: a column stored twice in a row is
    legal input, but the program's dense layout (which small shapes are
    given) keeps the last of the two where its sparse layout adds them."""
    rng = rng_for(structure_seed, 0)
    cols = np.sort(rng.integers(1, d, size=(n, k - 1), dtype=np.int32), axis=1)
    for _ in range(64):
        dup = np.zeros(cols.shape, bool)
        dup[:, 1:] = cols[:, 1:] == cols[:, :-1]
        if not dup.any():
            break
        cols[dup] = rng.integers(1, d, size=int(dup.sum()), dtype=np.int32)
        cols.sort(axis=1)
    else:
        raise RuntimeError("could not make the fixed-effect columns distinct")
    return np.concatenate([np.zeros((n, 1), np.int32), cols], axis=1)


def structure(config: dict) -> dict:
    """The seed-stable half: ``fe_cols`` [n, k] int32 and one id column per
    random-effect coordinate of the configuration."""
    feat = config["features"]
    n = feat["n"]
    out = {"fe_cols": fe_structure(n, feat["d"], feat["nnz_per_row"],
                                   config["structure_seed"])}
    for salt, (name, re) in enumerate(config["random_effects"].items(), start=1):
        rng = rng_for(config["structure_seed"], salt)
        out[name] = zipf_ids(rng, n, re["entities"], config["zipf_a"])
    return out


def values(config: dict, struct: dict, seed: int) -> dict:
    """The ``--seed`` half, float32 as served: ``fe_vals`` [n, k] (slot 0 is
    the intercept's 1), one dense ``[n, d]`` block per random effect, and 0/1
    labels drawn from a true fixed + per-entity logistic model with a base
    rate well under one half, as click data has."""
    feat = config["features"]
    n, d, k = feat["n"], feat["d"], feat["nnz_per_row"]
    rng = rng_for(seed, 1)
    vals = rng.standard_normal((n, k), dtype=np.float32) / np.float32(np.sqrt(k))
    vals[:, 0] = 1.0
    w_fe = (rng.standard_normal(d) * 0.5).astype(np.float32)
    w_fe[0] = -1.0
    margin = np.zeros(n, np.float64)
    step = 1 << 18
    cols = struct["fe_cols"]
    for lo in range(0, n, step):  # in blocks: the gathered table is 8 B a slot
        sl = slice(lo, lo + step)
        margin[sl] = np.einsum("nk,nk->n", vals[sl], w_fe[cols[sl]], dtype=np.float64)
    out = {"fe_vals": vals}
    for name, re in config["random_effects"].items():
        x = rng.standard_normal((n, re["d"]), dtype=np.float32)
        w = (rng.standard_normal((re["entities"], re["d"])) * 0.4).astype(np.float32)
        for lo in range(0, n, step):
            sl = slice(lo, lo + step)
            margin[sl] += np.einsum("nd,nd->n", x[sl], w[struct[name][sl]], dtype=np.float64)
        out[name] = x
    out["labels"] = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    return out
