"""device.idle_host_busy_share: what it reads is in the `.json` beside it.
None where the program has no such field, span or scope (PR 26's parent)."""

from benchmark.harness import program_trace as pt


def read(ctx):
    return pt.idle_share_while(ctx, [pt.SPAN_PREFIX + "pipeline." + n
                                     for n in ("prepare", "launch", "decode")])
