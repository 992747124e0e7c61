"""pipeline.singleton_batch_share: what it reads is in the `.json` beside it.
None where the program has no such counter (the parent of PR 26)."""


def read(ctx):
    c = ctx["counters"]
    if "batchesOfOne" not in c or not c.get("batches"):
        return None
    return 100.0 * c["batchesOfOne"] / c["batches"]
