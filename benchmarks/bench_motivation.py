"""Paper Table I / Figs 1-4: correlation of response time with scheduling
latency vs CPU utilization, in the two motivation experiments."""
from __future__ import annotations

import time

from repro.cluster.motivation import experiment1, experiment2, fit_quality


def run(fast: bool = True):
    t0 = time.time()
    e1 = experiment1(seed=0)
    e2 = experiment2(seed=100)
    rows = []
    for tag, data in (("exp1", e1), ("exp2", e2)):
        mape_r, r2_r = fit_quality(data[:, 1], data[:, 2])
        mape_c, r2_c = fit_quality(data[:, 0], data[:, 2])
        rows.append((f"motivation.{tag}.runqlat_resp", mape_r, r2_r))
        rows.append((f"motivation.{tag}.cpu_resp", mape_c, r2_c))
    us = (time.time() - t0) * 1e6 / 4
    out = []
    for name, mape, r2 in rows:
        out.append((name, us, f"MAPE={mape:.3f};R2={r2:.3f}"))
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    for row in run():
        print(",".join(map(str, row)))
