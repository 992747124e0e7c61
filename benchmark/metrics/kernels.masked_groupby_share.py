"""kernels.masked_groupby_share: what it reads is in the `.json` beside it.
None where the program has no such counter (PR 37's parent) or nothing was
launched."""


def read(ctx):
    c = ctx["counters"]
    if "maskedGroupByLaunches" not in c or not c.get("launches"):
        return None
    return 100.0 * c["maskedGroupByLaunches"] / c["launches"]
