"""kernels.presort_compact_share: what it reads is in the `.json` beside it.
None where the program has no such counters (PR 33's parent) or no launch
held the two sorts; never 0 for either."""


def read(ctx):
    c = ctx["counters"]
    if "presortCompactLaunches" not in c or "fullSortLaunches" not in c:
        return None
    launches = c["presortCompactLaunches"] + c["fullSortLaunches"]
    if not launches:
        return None
    return 100.0 * c["presortCompactLaunches"] / launches
