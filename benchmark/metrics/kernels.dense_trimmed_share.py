"""kernels.dense_trimmed_share: what it reads is in the `.json` beside it.
None where the program has no such counter (such a program fetches the
sort regime's dense table whole) or nothing was launched."""


def read(ctx):
    c = ctx["counters"]
    if "denseTrimmedLaunches" not in c or not c.get("launches"):
        return None
    return 100.0 * c["denseTrimmedLaunches"] / c["launches"]
