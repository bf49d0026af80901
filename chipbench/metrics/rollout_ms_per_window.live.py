"""Host time of the live rollout per window: ``run_experiment``'s
``rollout`` phase (``Cluster.rollout_scan`` until its outputs are ready,
with the first 30-tick warm-up rollout of each call) over the windows
the loop stepped."""

UNIT = "ms"


def read(run):
    recs = [r for r in run.records if "windows" in r]
    windows = sum(r["windows"] for r in recs)
    if windows == 0:
        return None
    return 1e3 * sum(r["phases"].get("rollout", 0.0) for r in recs) / windows
