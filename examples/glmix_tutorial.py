"""GLMix end-to-end tutorial: fixed effect + per-user + per-item random
effects on synthetic MovieLens-shaped data.

The photon-tpu counterpart of the reference's GAME training walkthrough
(reference README.md "GAME - Generalized Additive Mixed Effects" and the
GameEstimator flow, photon-api estimators/GameEstimator.scala:304): build a
GameData set, train a three-coordinate GLMix model by block coordinate
descent, score, and evaluate — global AUC plus grouped per-user AUC.

Run (CPU):   JAX_PLATFORMS=cpu python examples/glmix_tutorial.py
Run (TPU):   python examples/glmix_tutorial.py
Multi-chip:  pass --mesh-data/--mesh-entity to shard over a device mesh.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=400)
    ap.add_argument("--items", type=int, default=120)
    ap.add_argument("--samples", type=int, default=20_000)
    ap.add_argument("--mesh-data", type=int, default=0)
    ap.add_argument("--mesh-entity", type=int, default=1)
    args = ap.parse_args()

    from photon_tpu.evaluation import MultiEvaluator
    from photon_tpu.game.config import (
        FixedEffectCoordinateConfig,
        RandomEffectCoordinateConfig,
    )
    from photon_tpu.game.data import CSRMatrix, GameData
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.optimize.common import OptimizerConfig
    from photon_tpu.optimize.problem import GLMProblemConfig
    from photon_tpu.types import TaskType

    # --- synthetic MovieLens-shaped data ---------------------------------
    rng = np.random.default_rng(0)
    n, u_count, i_count = args.samples, args.users, args.items
    d_global, d_re = 32, 8
    uid = (rng.zipf(1.3, size=n) - 1) % u_count  # skewed activity
    iid = (rng.zipf(1.2, size=n) - 1) % i_count
    x_global = rng.normal(size=(n, d_global))
    x_user = rng.normal(size=(n, d_re))
    x_item = rng.normal(size=(n, d_re))

    w_global = rng.normal(size=d_global) * 0.4
    w_user = rng.normal(size=(u_count, d_re)) * 0.6  # per-user taste
    w_item = rng.normal(size=(i_count, d_re)) * 0.5  # per-item appeal
    margin = (
        x_global @ w_global
        + np.einsum("nd,nd->n", x_user, w_user[uid])
        + np.einsum("nd,nd->n", x_item, w_item[iid])
    )
    labels = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
        np.float64
    )

    data = GameData.build(
        labels=labels,
        feature_shards={
            "global": CSRMatrix.from_dense(x_global),
            "per_user": CSRMatrix.from_dense(x_user),
            "per_item": CSRMatrix.from_dense(x_item),
        },
        id_tags={
            "userId": [f"u{v}" for v in uid],
            "itemId": [f"i{v}" for v in iid],
        },
    )

    # --- three coordinates: global GLM + two random-effect tables --------
    def opt(max_iter):
        return GLMProblemConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer_config=OptimizerConfig(max_iterations=max_iter),
        )

    configs = {
        "global": FixedEffectCoordinateConfig(
            feature_shard="global",
            optimization=opt(40),
            regularization_weights=(1.0,),
        ),
        "per-user": RandomEffectCoordinateConfig(
            random_effect_type="userId",
            feature_shard="per_user",
            optimization=opt(15),
            regularization_weights=(10.0,),
        ),
        "per-item": RandomEffectCoordinateConfig(
            random_effect_type="itemId",
            feature_shard="per_item",
            optimization=opt(15),
            regularization_weights=(10.0,),
        ),
    }

    mesh = None
    if args.mesh_data:
        from photon_tpu.parallel import make_mesh

        mesh = make_mesh(
            num_data=args.mesh_data, num_entity=args.mesh_entity
        )

    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs=configs,
        update_sequence=["global", "per-user", "per-item"],
        descent_iterations=3,
        mesh=mesh,
    )

    t0 = time.perf_counter()
    if mesh is None:
        result = est.fit(data)[0]
    else:
        with mesh:
            result = est.fit(data)[0]
    fit_s = time.perf_counter() - t0

    # --- score + evaluate ------------------------------------------------
    scores = result.model.score(data)
    prob = 1 / (1 + np.exp(-np.asarray(scores)))
    auc_all = _auc(labels, prob)
    per_user_auc = MultiEvaluator.auc("userId")(
        np.asarray(scores), labels, np.asarray([f"u{v}" for v in uid])
    )

    print(f"trained {len(configs)} coordinates on n={n} in {fit_s:.1f}s")
    print(f"global AUC:             {auc_all:.4f}")
    print(f"per-user AUC (grouped): {per_user_auc:.4f}")
    base = max(labels.mean(), 1 - labels.mean())
    print(f"(label base rate {base:.3f} — random scoring gives AUC 0.5)")
    assert auc_all > 0.7, "tutorial model should beat random comfortably"


def _auc(labels, scores):
    order = np.argsort(scores)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(scores) + 1)
    pos = labels > 0.5
    n_pos, n_neg = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


if __name__ == "__main__":
    main()
