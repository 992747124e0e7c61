"""pipeline.prepare_cpu_share: what it reads is in the `.json` beside it.
None where the program has no such field (PR 38's parent)."""

from benchmark.harness import program_trace as pt, readers

CPU = ("devicePrepareCpuMs", "deviceLaunchCpuMs")
WALL = ("devicePrepareMs", "deviceLaunchMs")


def read(ctx):
    rows = [v for v in (pt.fields(r["response"], *CPU, *WALL)
                        for r in readers._responses(ctx)) if v]
    wall = sum(sum(v[2:]) for v in rows)
    return 100.0 * sum(sum(v[:2]) for v in rows) / wall if wall else None
