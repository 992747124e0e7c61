"""server.acquire_merge_ms: what it reads is in the `.json` beside it.
None where the program has no such field (PR 38's parent)."""

from benchmark.harness import program_trace as pt


def read(ctx):
    return pt.mean_sum(ctx, "serverAcquireMs", "serverMergeMs")
