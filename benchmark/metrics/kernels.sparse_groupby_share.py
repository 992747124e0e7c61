"""kernels.sparse_groupby_share: what it reads is in the `.json` beside it.
None where the program has no such counter (such a program
answers the GROUP BY on the host) or nothing was launched."""


def read(ctx):
    c = ctx["counters"]
    if "sparseGroupByLaunches" not in c or not c.get("launches"):
        return None
    return 100.0 * c["sparseGroupByLaunches"] / c["launches"]
