"""gk_iters: mean useful GK iterations per call (the GK ``kprime``:
``ConvergenceInfo.iterations`` of an F-SVD solve, ``RankEstimate``
``iterations`` of a rank estimate)."""

GK_METHODS = ("fsvd", "fsvd_sharded")


def read(run):
    gk = run.cell.entry == "estimate" or run.spec.method in GK_METHODS
    if not gk or not run.iterations:
        return None
    return sum(run.iterations) / len(run.iterations)
