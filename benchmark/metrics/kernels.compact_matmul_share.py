"""kernels.compact_matmul_share: what it reads is in the `.json` beside it.
None where the program has no such counters (a program without the
compaction in front of its matmul rungs) or no launch held the stage; never 0
for either."""


def read(ctx):
    c = ctx["counters"]
    if "compactMatmulLaunches" not in c or "fullMatmulLaunches" not in c:
        return None
    launches = c["compactMatmulLaunches"] + c["fullMatmulLaunches"]
    if not launches:
        return None
    return 100.0 * c["compactMatmulLaunches"] / launches
