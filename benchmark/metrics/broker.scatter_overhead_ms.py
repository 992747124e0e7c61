"""broker.scatter_overhead_ms: what it reads is in the `.json` beside it.
None where the program has no such field, span or scope (PR 26's parent)."""

from benchmark.harness import program_trace as pt, readers


def read(ctx):
    def pick(resp):
        v = pt.fields(resp, "serverTimeMs")
        scatter = (resp.get("phaseTimesMs") or {}).get("scatter")
        return float(scatter) - v[0] if v and scatter is not None else None
    return readers._mean_of(ctx, pick)
