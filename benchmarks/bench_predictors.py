"""Paper Table II / Figs 8-12: the five scheduling-latency predictors on
the simulator-generated Table-III dataset (MAE / MSE / MAPE / R2 + fit and
predict timing)."""
from __future__ import annotations

import time

import numpy as np

from repro.cluster.dataset import generate_latency_dataset
from repro.core.predictors import ALL_MODELS, evaluate, train_test_split


def run(fast: bool = True):
    n_place = 250 if fast else 700
    X, y = generate_latency_dataset(num_placements=n_place, num_nodes=10, seed=0)
    Xtr, Xte, ytr, yte = train_test_split(X, y, seed=0)
    out = []
    for name, cls in ALL_MODELS.items():
        kwargs = {}
        if fast and name in ("svm", "mlp"):
            kwargs["steps"] = 1500
        t0 = time.time()
        m = cls(**kwargs).fit(Xtr, ytr)
        fit_s = time.time() - t0
        t0 = time.time()
        for _ in range(5):
            pred = m.predict(Xte)
        pred_us = (time.time() - t0) / 5 * 1e6
        e = evaluate(yte, pred)
        out.append((
            f"predictors.{name}",
            pred_us,
            f"mae={e['mae']:.2f};mse={e['mse']:.1f};mape={e['mape']:.3f};"
            f"r2={e['r2']:.3f};fit_s={fit_s:.2f};n={len(y)}",
        ))
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    for row in run():
        print(",".join(map(str, row)))
