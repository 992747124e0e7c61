"""mesh.collective_bytes_per_answer: what it reads is in the `.json` beside
it. None where the program has no such counter (PR 28's parent) or no launch
crossed the mesh (a mesh of one)."""

from benchmark.harness import readers


def read(ctx):
    c = ctx["counters"]
    answers = len(readers._responses(ctx))
    if not c.get("meshLaunches") or "collectiveBytes" not in c or not answers:
        return None
    return c["collectiveBytes"] / answers
