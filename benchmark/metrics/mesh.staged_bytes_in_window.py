"""mesh.staged_bytes_in_window: what it reads is in the `.json` beside it.
None where the program has no such counter (PR 32's parent)."""


def read(ctx):
    c = ctx["counters"]
    if "setBlockBytes" not in c:
        return None
    return float(c["setBlockBytes"])
