"""device.idle_unattributed_share: what it reads is in the `.json` beside it.
None where the program opens none of PR 38's spans (PR 38's parent)."""

from benchmark.harness import idle_classes


def read(ctx):
    return idle_classes.share(ctx, "unattributed")
