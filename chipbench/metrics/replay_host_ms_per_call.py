"""Host time of a replay call outside the engine's own timing: the call's
wall minus the ``wall_s`` it returns (``replay_inputs`` before it, the
per-seed reduction after it)."""

UNIT = "ms"


def read(run):
    if run.cell.workload["driver"] != "replay" or not run.records:
        return None
    host = [r["end"] - r["start"] - r["wall_s"] for r in run.records]
    return 1e3 * sum(host) / len(host)
