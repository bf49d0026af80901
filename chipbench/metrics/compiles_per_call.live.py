"""Programs compiled per live call inside the window: the live driver's
own ``backend_compile`` listener, counted across each call.  Set-up
compiles every shape a day brings, so a call should compile none."""

UNIT = "count"


def read(run):
    recs = [r for r in run.records if "compiles" in r]
    if not recs or run.cell.workload["driver"] != "live":
        return None
    return sum(r["compiles"] for r in recs) / len(recs)
