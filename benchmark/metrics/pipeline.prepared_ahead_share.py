"""pipeline.prepared_ahead_share: what it reads is in the `.json` beside it.
None where the program has no such counter (one that prepares every query
when its drain closes)."""


def read(ctx):
    c = ctx["counters"]
    if "preparedAhead" not in c or not c.get("dispatched"):
        return None
    return 100.0 * c["preparedAhead"] / c["dispatched"]
