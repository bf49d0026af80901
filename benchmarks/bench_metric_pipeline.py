"""Metric-pipeline throughput: runqlat histogram aggregation + Eq. 1/2
evaluation at cluster scale (the collector runs on every node each tick)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import metric
from repro.core.interference import node_interference


def run(fast: bool = True):
    out = []
    key = jax.random.PRNGKey(0)
    # 1000 nodes x 14 services x 256 samples/tick
    nodes, services, samples = (1000, 14, 256) if fast else (4000, 14, 256)
    s = jax.random.uniform(key, (nodes, services, samples), minval=0, maxval=1100)

    hist = jax.jit(metric.histogram)
    h = hist(s)
    jax.block_until_ready(h)
    t0 = time.time()
    for _ in range(5):
        h = hist(s)
    jax.block_until_ready(h)
    us = (time.time() - t0) / 5 * 1e6
    rate = nodes * services * samples / (us / 1e6)
    out.append(("metric.histogram_cluster_tick", us,
                f"nodes={nodes};samples_per_s={rate:.3g}"))

    on, off = h[:, :8], h[:, 8:]
    intf = jax.jit(node_interference)
    v = intf(on, off)
    jax.block_until_ready(v)
    t0 = time.time()
    for _ in range(10):
        v = intf(on, off)
    jax.block_until_ready(v)
    us = (time.time() - t0) / 10 * 1e6
    out.append(("metric.node_interference_eq1", us,
                f"nodes_per_s={nodes / (us / 1e6):.3g}"))

    avg = jax.jit(metric.avg_runqlat)
    a = avg(h)
    jax.block_until_ready(a)
    t0 = time.time()
    for _ in range(10):
        a = avg(h)
    jax.block_until_ready(a)
    us = (time.time() - t0) / 10 * 1e6
    out.append(("metric.avg_runqlat_eq2", us, f"hists={nodes * services}"))
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    for row in run():
        print(",".join(map(str, row)))
