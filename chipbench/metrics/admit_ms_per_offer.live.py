"""Host time of an ICO-F admission, per offer: the scheduler's
``repro.admit.*`` phases (the candidate prefilter, the quantifier, the
jit'd score, ``Cluster.place``) over every offer of the window's calls,
retries included, as ``run_experiment`` returns them."""

UNIT = "ms"


def read(run):
    recs = [r for r in run.records if "offers" in r]
    offers = sum(r["offers"] for r in recs)
    if offers == 0:
        return None
    admit = sum(v for r in recs for k, v in r["phases"].items()
                if k.startswith("admit."))
    return 1e3 * admit / offers
