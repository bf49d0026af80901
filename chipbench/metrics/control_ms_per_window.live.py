"""Host time of the control loop per window: its ``verify``,
``forecast``, ``detect`` and ``plan`` phases and the driver's
``snapshot`` (the ``ClusterView`` the loop reads), over the windows the
loop stepped, as ``run_experiment`` returns them."""

UNIT = "ms"
PHASES = ("snapshot", "verify", "forecast", "detect", "plan")


def read(run):
    recs = [r for r in run.records if "windows" in r]
    windows = sum(r["windows"] for r in recs)
    if windows == 0:
        return None
    total = sum(r["phases"].get(p, 0.0) for r in recs for p in PHASES)
    return 1e3 * total / windows
