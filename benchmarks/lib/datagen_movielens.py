"""A ratings-shaped GLMix deployment from seeds: one DENSE fixed-effect
block and one dense random-effect block per entity type, over an
explicit-feedback log whose every user and movie holds tens to thousands of
rows (MovieLens 20M's counts; ``datagen_game.py``'s Zipf ids give the
click log's single-row entities and are not this log's shape).

STRUCTURE comes from the configuration's ``structure_seed`` and is the same
in every run: which user and which movie every row belongs to. It decides
the random-effect buckets and so the compiled programs.

- users: every user's row count is ``floor`` + a log-normal draw, scaled so
  that the counts sum to the rows (``assumed.user_rows``: at least 20, mean
  rows / users, the largest near 10**4, as MovieLens 20M's 20 / 144 / 9 254);
  the rows are then dealt to the users in one random order.
- movies: a Zipf-Mandelbrot popularity ``p(rank) ~ (rank + q) ** -a``
  (``assumed.movie_popularity``), every row's movie drawn from it, the
  first ``entities`` rows walking every movie once so that none is unseen.

VALUES come from ``--seed``: the dense block (column 0 the intercept's 1,
the rest standard normal over sqrt(d)), the random effects' blocks, the true
model and the labels (rating >= 4: about half the rows). The fixed effect's
block is made in row blocks straight into one float32 array; no float64
array of its size is ever made.
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib.datagen import rng_for

#: rows a block: 2**16 x 128 float32 is 32 MB
ROW_BLOCK = 1 << 16


def user_counts(rng, n: int, users: int, law: dict) -> np.ndarray:
    """[users] int64 row counts: ``law["floor"]`` + a log-normal of shape
    ``law["sigma"]``, scaled to sum to ``n`` exactly."""
    floor, sigma = int(law["floor"]), float(law["sigma"])
    spare = n - floor * users
    if spare < 0:
        raise ValueError(f"{n} rows cannot give {users} users {floor} rows each")
    draw = rng.lognormal(0.0, sigma, size=users)
    extra = np.floor(draw * (spare / draw.sum())).astype(np.int64)
    # the rounding's remainder goes to the most active users, one row each
    short = spare - int(extra.sum())
    extra[np.argsort(-draw, kind="stable")[:short]] += 1
    return floor + extra


def movie_ids(rng, n: int, movies: int, law: dict) -> np.ndarray:
    """[n] int64 movie of every row: Zipf-Mandelbrot ranks, the movie of a
    rank fixed by one permutation; the first ``movies`` rows walk every
    movie once."""
    if n < movies:
        raise ValueError(f"{n} rows cannot cover {movies} movies")
    p = (np.arange(1, movies + 1) + float(law["q"])) ** -float(law["a"])
    cdf = np.cumsum(p / p.sum())
    ids = np.minimum(np.searchsorted(cdf, rng.uniform(size=n)), movies - 1)
    ids = rng.permutation(movies)[ids]
    ids[:movies] = rng.permutation(movies)
    return ids.astype(np.int64)


def structure(config: dict) -> dict:
    """The seed-stable half: one id column per random effect."""
    n = config["features"]["n"]
    res, laws = config["random_effects"], config["structure"]
    rng = rng_for(config["structure_seed"], 1)
    counts = user_counts(rng, n, res["per_user"]["entities"], laws["user_rows"])
    users = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
    users = users[rng.permutation(n)]
    rng = rng_for(config["structure_seed"], 2)
    return {"per_user": users,
            "per_movie": movie_ids(rng, n, res["per_movie"]["entities"],
                                   laws["movie_popularity"])}


def values(config: dict, struct: dict, seed: int) -> dict:
    """The ``--seed`` half, float32 as served: ``fe_x`` [n, d] (column 0 is
    the intercept's 1), one ``[n, d]`` block per random effect, and 0/1
    labels drawn from a true fixed + per-entity logistic model."""
    feat = config["features"]
    n, d = feat["n"], feat["d"]
    rng = rng_for(seed, 1)
    w_fe = (rng.standard_normal(d) * 0.5).astype(np.float32)
    w_fe[0] = 0.0  # about half the ratings are 4 or more
    fe_x = np.empty((n, d), np.float32)
    margin = np.zeros(n, np.float64)
    scale = np.float32(1.0 / np.sqrt(d))
    for lo in range(0, n, ROW_BLOCK):
        block = fe_x[lo:lo + ROW_BLOCK]
        rng.standard_normal(out=block, dtype=np.float32)
        block *= scale
        block[:, 0] = 1.0
        margin[lo:lo + ROW_BLOCK] = block @ w_fe
    out = {"fe_x": fe_x}
    step = 1 << 18
    for name, re in config["random_effects"].items():
        x = rng.standard_normal((n, re["d"]), dtype=np.float32)
        w = (rng.standard_normal((re["entities"], re["d"])) * 0.4).astype(np.float32)
        for lo in range(0, n, step):
            sl = slice(lo, lo + step)
            margin[sl] += np.einsum("nd,nd->n", x[sl], w[struct[name][sl]], dtype=np.float64)
        out[name] = x
    out["labels"] = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    return out
