"""kernels.compact_decode_share: what it reads is in the `.json` beside it.
None where the program has no such counters (PR 29's parent) or no launch
held the two decode branches; never 0 for either."""


def read(ctx):
    c = ctx["counters"]
    if "compactDecodeLaunches" not in c or "denseDecodeLaunches" not in c:
        return None
    launches = c["compactDecodeLaunches"] + c["denseDecodeLaunches"]
    if not launches:
        return None
    return 100.0 * c["compactDecodeLaunches"] / launches
