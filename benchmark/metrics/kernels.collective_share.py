"""kernels.collective_share: what it reads is in the `.json` beside it.
None where the program names no scope (PR 26's parent) or the run no trace.

A collective is known by its scope (`pinot.collective*` in its `tf_op`) or,
where the compiler made the operation itself and gave it no `tf_op`, by its
HLO opcode: the v5e compiler turns every `psum_scatter` of this program into
an `all-reduce` and a `dynamic-slice` that carry no metadata (PR 28, compiled
for a described v5e:2x2). `ctx` carries no path, so the opcodes are read from
the file `program_trace.slice_of` reads: the newest traced slice under
`.bench_work`.
"""

import glob
import os
import re

from benchmark.harness import program_trace as pt
from benchmark.harness import trace_reduce

COLLECTIVE_OPCODE = re.compile(
    r"\b(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start|-done)?\(")


def opcode_intervals(path: str, lo: float, hi: float) -> list:
    """[(start, end)] of the first device's operations in [lo, hi) whose name
    (their HLO text) is a collective's."""
    devices = [p for p in pt.load(path)
               if p["name"].startswith("/device:TPU:")]
    return [(max(start, lo), min(start + dur, hi))
            for ln in (devices[0]["lines"] if devices else [])
            if ln["name"] == pt.OPS_LINE
            for name, start, dur, _ in ln["events"]
            if start < hi and start + dur > lo
            and COLLECTIVE_OPCODE.search(name)]


def read(ctx):
    t = pt.slice_of(ctx)
    if not t or not t["busy"] or not any(scope for _, _, scope in t["ops"]):
        return None
    found = glob.glob(os.path.join(
        pt.ROOT, ".bench_work", "*", "profile", "plugins", "profile", "*",
        "*.xplane.pb"))
    by_opcode = opcode_intervals(max(found, key=os.path.getmtime),
                                 t["lo"], t["hi"]) if found else []
    by_scope = [(s, e) for s, e, scope in t["ops"]
                if scope.startswith(pt.SCOPE_PREFIX + "collective")]
    return 100.0 * pt.length(trace_reduce.union(by_scope + by_opcode)) \
        / pt.length(t["busy"])
