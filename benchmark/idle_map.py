#!/usr/bin/env python3
"""Where the device's idle time lies in one kept slice (`run.py --trace 1
--keep-trace <file>`): the seven classes of `harness/idle_classes.py` and
their sum against the idle share, every thread's innermost `pinot:*` span by
its overlap with the idle (the host spans to cut first), and how much of
`pipeline.prepare` and `pipeline.launch` their named children cover.

    python3 benchmark/idle_map.py <slice.xplane.pb> [out.json]

Not a metric: a map for the builder of a host-path PR (PR 38). Shares are %
of the slice; a thread's innermost span is the one opened last of those open
on its line, so nested spans are counted once, by their own time.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import idle_classes, program_trace as pt  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402


def innermost(t) -> dict:
    """{span name: merged intervals in which it is the innermost span open
    on its thread}."""
    by_line = {}
    for name, rows in t["spans"].items():
        for s, e, _, line in rows:
            by_line.setdefault(line, []).append((s, e, name))
    out = {}
    for rows in by_line.values():
        events = sorted([(s, 1, i) for i, (s, _, _) in enumerate(rows)]
                        + [(e, 0, i) for i, (_, e, _) in enumerate(rows)])
        stack, at = [], None
        for x, opens, i in events:
            if stack and x > at:
                out.setdefault(rows[stack[-1]][2], []).append((at, x))
            at = x
            if opens:
                stack.append(i)
            elif i in stack:
                stack.remove(i)
    return {k: trace_reduce.union(v) for k, v in out.items()}


def covered(t, parent: str, children) -> float | None:
    """% of `parent`'s open time in which one of `children` is open."""
    par = pt.open_spans(t, [pt.SPAN_PREFIX + parent])
    kids = pt.open_spans(t, [pt.SPAN_PREFIX + c for c in children])
    n = pt.length(par)
    return 100.0 * pt.length(pt.intersect(par, kids)) / n if n else None


def idle_map(t, modules) -> dict:
    """The map of a reduced slice `t` (`program_trace.reduce`) whose module
    intervals are `modules`."""
    width = t["hi"] - t["lo"]
    idle = pt.complement(t["busy"], t["lo"], t["hi"])
    ctx = {"trace": True, "program_trace": t, "program_modules": modules}
    pipeline = pt.SPAN_PREFIX + "pipeline."
    older = {"host_busy": pt.idle_share_while(
                 ctx, [pipeline + n for n in ("prepare", "launch", "decode")]),
             "starved": pt.idle_share_while(ctx, [pipeline + "wait"],
                                            [pipeline + "fetch"])}
    classes = idle_classes.partition(ctx) or {}
    idle_share = 100.0 * pt.length(idle) / width
    seven = sum(v or 0.0 for v in older.values()) + sum(
        classes.get(c, 0.0) for c in idle_classes.CLASSES)
    by_idle = sorted(((100.0 * pt.length(pt.intersect(idle, v)) / width, k)
                      for k, v in innermost(t).items()), reverse=True)
    return {"idle_share": idle_share, **older, **classes,
            "seven_minus_idle": seven - idle_share,
            "innermost_by_idle": [[k, v] for v, k in by_idle],
            "prepare_covered": covered(t, "pipeline.prepare",
                                       ("prepare.plan", "prepare.inputs")),
            "launch_covered": covered(t, "pipeline.launch",
                                      ("launch.kernel", "launch.call")),
            "spans": {k: [len(v), sum(e - s for s, e, _, _ in v) / 1e6]
                      for k, v in sorted(t["spans"].items())}}


def main(path: str, out: str = "") -> int:
    t = pt.reduce(path)
    if t is None:
        print("no TPU plane or no bench:window span in", path)
        return 1
    text = json.dumps(idle_map(t, idle_classes.module_intervals(
        path, t["lo"], t["hi"])), indent=1)
    print(text)
    if out:
        with open(out, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
