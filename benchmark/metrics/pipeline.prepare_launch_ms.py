"""pipeline.prepare_launch_ms: what it reads is in the `.json` beside it.
None where the program has no such field, span or scope (PR 26's parent)."""

from benchmark.harness import program_trace as pt


def read(ctx):
    def pick(resp):
        v = pt.fields(resp, "devicePrepareMs", "deviceLaunchMs")
        return sum(v) if v else None
    return pt.mean_of(ctx, pick)
