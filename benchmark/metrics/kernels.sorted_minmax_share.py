"""kernels.sorted_minmax_share: what it reads is in the `.json` beside it.
None where the program has no such counter (such a program scatters every
row for a MIN or a MAX past the broadcast cap) or nothing was launched."""


def read(ctx):
    c = ctx["counters"]
    if "sortedMinMaxLaunches" not in c or not c.get("launches"):
        return None
    return 100.0 * c["sortedMinMaxLaunches"] / c["launches"]
