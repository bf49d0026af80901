"""Model input feature vector — paper Table III.

Layout (46 features):
  [0]      qps                      -- QPS of the pod being scheduled
  [1:13]   performance metrics      -- cpu util, memory stats, net/disk I/O
  [13:21]  hardware events          -- perf counters
  [21:46]  scheduling-latency stats -- summary of the node's 200-bin runqlat
                                       histogram: avg, p50/p90/p99, total
                                       count, and 20 coarse band masses
"""
from __future__ import annotations

import numpy as np

from repro.core import metric

PERF_METRICS = [
    "cpu_utilization",
    "memory_usage",
    "mem_cache",
    "mem_pgfault",
    "mem_pgmajfault",
    "working_set",
    "memory_rss",
    "net_recv_avg",
    "net_recv_packets_avg",
    "net_send_avg",
    "net_send_packets_avg",
    "disk_io_avg",
]

HARDWARE_EVENTS = [
    "cycles",
    "instructions",
    "cache_references",
    "cache_misses",
    "branch_instructions",
    "branch_misses",
    "context_switches",
    "cpu_migrations",
]

_NUM_BANDS = 20
RUNQLAT_STATS = ["runqlat_avg", "runqlat_p50", "runqlat_p90", "runqlat_p99", "runqlat_count"] + [
    f"runqlat_band_{b}" for b in range(_NUM_BANDS)
]

FEATURE_NAMES = ["qps"] + PERF_METRICS + HARDWARE_EVENTS + RUNQLAT_STATS
NUM_FEATURES = len(FEATURE_NAMES)


def runqlat_summary(hist: np.ndarray) -> np.ndarray:
    """Summarize (..., 200) runqlat histograms into (..., 25) Table-III stat
    blocks.  A whole fleet's node histograms go through one call: four
    device dispatches in all, instead of four per node."""
    import jax.numpy as jnp

    hist = np.asarray(hist, dtype=np.float64)
    h = jnp.asarray(hist)
    stats = np.stack([np.asarray(metric.avg_runqlat(h), np.float64)] + [
        np.asarray(metric.percentile(h, q), np.float64)
        for q in (50.0, 90.0, 99.0)], axis=-1)
    total = hist.sum(axis=-1)
    bands = hist.reshape(*hist.shape[:-1], _NUM_BANDS,
                         metric.NUM_BINS // _NUM_BANDS).sum(axis=-1)
    bands = bands / np.maximum(total, 1.0)[..., None]  # normalized masses
    return np.concatenate([stats, total[..., None], bands], axis=-1)


def feature_vector(qps: float, perf: dict, hw: dict, runqlat_hist: np.ndarray) -> np.ndarray:
    """Assemble one Table-III input row from raw node telemetry."""
    row = [float(qps)]
    row += [float(perf[k]) for k in PERF_METRICS]
    row += [float(hw[k]) for k in HARDWARE_EVENTS]
    row = np.asarray(row, dtype=np.float64)
    return np.concatenate([row, runqlat_summary(runqlat_hist)])


def node_feature_matrix(qps: np.ndarray, perf: np.ndarray, hw: np.ndarray, hists: np.ndarray) -> np.ndarray:
    """Vectorized assembly: qps (N,), perf (N,12), hw (N,8), hists (N,200) -> (N,42)."""
    return np.concatenate([qps[:, None], perf, hw, runqlat_summary(hists)],
                          axis=1)
