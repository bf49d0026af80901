"""Share of the traced window in which no operation ran on the device,
in live cells (profiler trace, averaged over the chips used)."""

UNIT = "%"


def read(run):
    if run.trace is None or run.cell.workload["driver"] != "live":
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
