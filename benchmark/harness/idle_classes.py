"""The device's idle time in the traced slice, split into classes that do not
overlap and add up (PR 38). The first device's idle instants, in order, the
earlier class winning:

  host_busy     a pinot:pipeline.prepare, .launch or .decode is open
                (device.idle_host_busy_share, PR 26)
  starved       pinot:pipeline.wait is open and no .fetch
                (device.idle_starved_share, PR 26)
  in_program    inside a module's execution on the "XLA Modules" line: the
                device's own gaps, whatever the host does
  gc            a pinot:gc span is open (a generation-2 collection)
  fetch         pinot:pipeline.fetch, .gather or .handoff is open: the device
                has finished and the drain holds the next batch
  request       a request-path span is open (http.*, broker.*, server.*,
                prepare.*, launch.*)
  unattributed  the rest

The first two are the readers PR 26 wrote and are not repeated here: the five
below each leave out what either of them counts, so the seven add up to
device.idle_share (exactly where the two do not overlap; they can, where the
fetcher decodes while the dispatcher waits).

A reader returns None where the slice holds no span this PR added to the
program (the parent of PR 38), and where `program_trace` finds no slice.
"""

import glob
import os

from . import program_trace as pt, trace_reduce

CLASSES = ("in_program", "gc", "fetch", "request", "unattributed")
#: spans the program opens since PR 38: one of them in the slice says the
#: classes below can be told apart
NEW_SPANS = tuple(pt.SPAN_PREFIX + n for n in (
    "http.query", "broker.fingerprint", "broker.account", "broker.serialize",
    "broker.deserialize", "server.decode", "server.encode", "server.acquire",
    "server.merge", "prepare.plan", "prepare.inputs", "launch.kernel",
    "launch.call", "gc"))
REQUEST_PREFIXES = tuple(pt.SPAN_PREFIX + p for p in (
    "http.", "broker.", "server.", "prepare.", "launch."))


def _pipeline(*names):
    return [pt.SPAN_PREFIX + "pipeline." + n for n in names]


def module_intervals(path: str, lo: float, hi: float) -> list:
    """Merged intervals of the first device's module executions ("XLA
    Modules" line), clipped to [lo, hi). Walks only that line's events."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for field, plane in pt._fields(buf):
        if field != 1:
            continue
        lines = []
        name = ""
        for g, v in pt._fields(plane):
            if g == 2:
                name = pt._text(v)
            elif g == 3:
                lines.append(v)
        if not name.startswith("/device:TPU:"):
            continue
        out = []
        for ln in lines:
            lname, t0_ns, events = "", 0, []
            for g, v in pt._fields(ln):
                if g == 2:
                    lname = pt._text(v)
                elif g == 3:
                    t0_ns = v
                elif g == 4:
                    events.append(v)
            if lname != pt.MODULES_LINE:
                continue
            for ev in events:
                offset_ps = dur_ps = 0
                for g, v in pt._fields(ev):
                    if g == 2:
                        offset_ps = v
                    elif g == 3:
                        dur_ps = v
                start = t0_ns + offset_ps / 1000.0
                end = start + dur_ps / 1000.0
                if end > lo and start < hi:
                    out.append((max(start, lo), min(end, hi)))
        return trace_reduce.union(out)
    return []


def _modules(ctx, t) -> list:
    """The slice's module intervals, kept on `ctx` (a test puts its own)."""
    if "program_modules" not in ctx:
        found = glob.glob(os.path.join(
            pt.ROOT, ".bench_work", "*", "profile", "plugins", "profile", "*",
            "*.xplane.pb"))
        ctx["program_modules"] = (
            module_intervals(max(found, key=os.path.getmtime), t["lo"],
                             t["hi"]) if found else [])
    return ctx["program_modules"]


def partition(ctx) -> dict | None:
    """{class: share of the slice, %} for the five classes, plus
    "overlap": the share that host_busy and starved both count. Kept on
    `ctx`, so that the five readers of one run compute it once."""
    if "idle_classes" not in ctx:
        ctx["idle_classes"] = _partition(ctx)
    return ctx["idle_classes"]


def _partition(ctx) -> dict | None:
    t = pt.slice_of(ctx)
    if not t or not any(n in t["spans"] for n in NEW_SPANS):
        return None
    lo, hi = t["lo"], t["hi"]
    width = hi - lo
    rest = pt.complement(t["busy"], lo, hi)
    host_busy = pt.open_spans(t, _pipeline("prepare", "launch", "decode"))
    starved = pt.intersect(
        pt.open_spans(t, _pipeline("wait")),
        pt.complement(pt.open_spans(t, _pipeline("fetch")), lo, hi))
    out = {"overlap": 100.0 * pt.length(
        pt.intersect(pt.intersect(rest, host_busy), starved)) / width}
    taken = trace_reduce.union([tuple(iv) for iv in host_busy + starved])
    rest = pt.intersect(rest, pt.complement(taken, lo, hi))
    request = [n for n in t["spans"] if n.startswith(REQUEST_PREFIXES)]
    for name, held in (
            ("in_program", _modules(ctx, t)),
            ("gc", pt.open_spans(t, [pt.SPAN_PREFIX + "gc"])),
            ("fetch", pt.open_spans(t, _pipeline("fetch", "gather",
                                                 "handoff"))),
            ("request", pt.open_spans(t, request))):
        held = [list(iv) for iv in held]
        out[name] = 100.0 * pt.length(pt.intersect(rest, held)) / width
        rest = pt.intersect(rest, pt.complement(held, lo, hi))
    out["unattributed"] = 100.0 * pt.length(rest) / width
    return out


def share(ctx, name: str) -> float | None:
    p = partition(ctx)
    return p[name] if p else None
