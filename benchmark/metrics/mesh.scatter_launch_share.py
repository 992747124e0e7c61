"""mesh.scatter_launch_share: what it reads is in the `.json` beside it.
None where the program has no such counters (PR 28's parent) or no launch
crossed the mesh (a mesh of one)."""


def read(ctx):
    c = ctx["counters"]
    if "scatterLaunches" not in c or not c.get("meshLaunches"):
        return None
    return 100.0 * c["scatterLaunches"] / c["meshLaunches"]
