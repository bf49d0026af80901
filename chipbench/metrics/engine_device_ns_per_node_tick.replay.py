"""Device time of the seed-batched rollout engine per node-tick it
computed, bucketing padding included.

The engine is the compiled program ``state.batched_rollout`` runs; its
modules carry the name of the scanned function, ``_scan_windows_impl``.
"""

UNIT = "ns"
PROGRAM = "_scan_windows_impl"


def read(run):
    if run.trace is None or run.cell.workload["driver"] != "replay":
        return None
    seconds = sum(s for name, s in run.trace["modules"].items()
                  if PROGRAM in name)
    ticks = sum(r["computed_node_ticks"] for r in run.records)
    if seconds <= 0 or ticks <= 0:
        return None
    return seconds * 1e9 / ticks
