"""Paper Figs 6-7: QPS -> CPU/MEM linearity per workload type."""
from __future__ import annotations

import time

from repro.cluster.dataset import generate_resource_dataset
from repro.cluster.workloads import ONLINE_NAMES
from repro.core.resource_model import ResourcePredictor


def run(fast: bool = True):
    out = []
    for w in ONLINE_NAMES:
        qps, cpu, mem = generate_resource_dataset(w, seed=0)
        t0 = time.time()
        rp = ResourcePredictor().fit(w, qps, cpu, mem)
        fit_us = (time.time() - t0) * 1e6
        r2c, r2m = rp.r2(w, qps, cpu, mem)
        out.append((
            f"resource_model.{w}", fit_us,
            f"r2_cpu={r2c:.3f};r2_mem={r2m:.3f};"
            f"slope_cpu={rp.cpu_fits[w].slope:.4f};slope_mem={rp.mem_fits[w].slope:.4f}",
        ))
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    for row in run():
        print(",".join(map(str, row)))
