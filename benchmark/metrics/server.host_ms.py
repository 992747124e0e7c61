"""server.host_ms: what it reads is in the `.json` beside it.
None where the program has no such field, span or scope (PR 26's parent)."""

from benchmark.harness import program_trace as pt, readers


PIPELINE = ("queueWaitMs", "devicePrepareMs", "deviceLaunchMs",
            "deviceHandoffMs", "deviceFetchMs", "deviceDecodeMs")


def read(ctx):
    def pick(resp):
        v = pt.fields(resp, "serverTimeMs", *PIPELINE)
        return v[0] - sum(v[1:]) if v else None
    return readers._mean_of(ctx, pick)
