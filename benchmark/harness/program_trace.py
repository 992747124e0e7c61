"""What the program itself wrote into the traced slice's `.xplane.pb`: its
`pinot:*` host spans (pinot_tpu/utils/trace.py) and the `pinot.*` scopes its
kernels carry in each device operation's `tf_op`.

`jax.profiler.ProfileData` shows an event's name, start and duration; the
scope of a device operation lives in the *metadata* of its event, which it
does not show. So this module walks the file's protobuf wire format itself,
over the five messages it needs (XSpace -> XPlane -> XLine -> XEvent, and the
plane's `event_metadata` and `stat_metadata` maps; tsl/profiler/protobuf/
xplane.proto). It imports nothing: the only parser the chip's host has is
TensorFlow's, which takes 19 s to import and is not wanted in the process
that owns the chip (my chip run, PR 26).

`ctx` carries no path, so `slice_of(ctx)` finds the file where run.py's
`trace_slice` left it: the newest under `<root>/.bench_work/<cell>/profile`,
`<cell>` being the directory run.py keeps there during a run. Every reader built on this
returns None where there is no `/device:TPU` plane (a CPU rehearsal), no
`bench:window` span, or no span or scope of the kind it reads (the parent of
PR 26 has none).

Times are nanoseconds since the profile began, as in trace_reduce.py; a
line's events start at `timestamp_ns + offset_ps / 1000`.
"""

import glob
import os
import struct

from . import readers, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPAN_PREFIX = "pinot:"
SCOPE_PREFIX = "pinot."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


# -- protobuf wire format ---------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint or a fixed
    field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif kind == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif kind == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield tag >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """An XStat as (name, value): a string, a number, or the name a
    `ref_value` points at."""
    name, value = None, None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f in (5, 6):
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f in (3, 4):
            value = v
    return name, value


def _plane(buf) -> dict:
    """{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns,
    stats)]}]}; an event's stats are its own and its metadata's, by name."""
    name, lines, meta_bufs, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta_bufs.append(v)
        elif f == 5:                      # map<int64, XStatMetadata>
            for g, w in _fields(v):
                if g == 2:
                    sid, sname = 0, ""
                    for h, x in _fields(w):
                        if h == 1:
                            sid = x
                        elif h == 2:
                            sname = _text(x)
                    stat_names[sid] = sname
    metadata = {}
    for entry in meta_bufs:               # map<int64, XEventMetadata>
        for g, w in _fields(entry):
            if g != 2:
                continue
            mid, mname, stats = 0, "", {}
            for h, x in _fields(w):
                if h == 1:
                    mid = x
                elif h == 2:
                    mname = _text(x)
                elif h == 5:
                    k, val = _stat(x, stat_names)
                    stats[k] = val
            metadata[mid] = (mname, stats)
    out = []
    for ln in lines:
        lname, t0_ns, events = "", 0, []
        for f, v in _fields(ln):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        parsed = []
        for ev in events:
            mid = offset_ps = dur_ps = 0
            own = None
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset_ps = v
                elif f == 3:
                    dur_ps = v
                elif f == 4:
                    k, val = _stat(v, stat_names)
                    own = own or {}
                    own[k] = val
            ename, mstats = metadata.get(mid, (str(mid), {}))
            parsed.append((ename, t0_ns + offset_ps / 1000.0, dur_ps / 1000.0,
                           dict(mstats, **own) if own else mstats))
        out.append({"name": lname, "events": parsed})
    return {"name": name, "lines": out}


def load(path: str) -> list:
    """The planes of an `.xplane.pb` (XSpace.planes)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v) for f, v in _fields(buf) if f == 1]


# -- the traced slice -------------------------------------------------------

def scope_of(tf_op) -> str:
    """The outermost `pinot.*` scope in an operation's `tf_op` path
    (`jit(pinot_groupby)/.../pinot.groupby.partitioned/...`), or ""."""
    for part in str(tf_op or "").split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return ""


def reduce(path: str) -> dict | None:
    """The slice under `bench:window`, or None where the trace has no TPU
    plane or no such span: {"lo", "hi" (ns), "busy": merged device-busy
    intervals clipped to the slice (first device), "ops": [(start, end,
    scope)] of its operations, "modules": {name: executions}, "spans":
    {name: [(start, end, stats, thread)]} of the `pinot:*` host spans}."""
    planes = load(path)
    devices = [p for p in planes if p["name"].startswith("/device:TPU:")]
    hosts = [p for p in planes if p["name"].startswith("/host:CPU")]
    window = [e for p in hosts for ln in p["lines"] for e in ln["events"]
              if e[0] == trace_reduce.SPAN_PREFIX + "window"]
    if not devices or not window:
        return None
    lo, hi = window[0][1], window[0][1] + window[0][2]
    ops, modules = [], {}
    for ln in devices[0]["lines"]:
        for name, start, dur, stats in ln["events"]:
            if start + dur <= lo or start >= hi:
                continue
            if ln["name"] == OPS_LINE:
                ops.append((max(start, lo), min(start + dur, hi),
                            scope_of(stats.get("tf_op"))))
            elif ln["name"] == MODULES_LINE:
                name = name.split("(")[0]
                modules[name] = modules.get(name, 0) + 1
    spans = {}
    for p in hosts:
        for ln in p["lines"]:
            for name, start, dur, stats in ln["events"]:
                if name.startswith(SPAN_PREFIX) and start < hi \
                        and start + dur > lo:
                    spans.setdefault(name, []).append(
                        (max(start, lo), min(start + dur, hi), stats,
                         ln["name"]))
    return {"lo": lo, "hi": hi, "ops": ops, "modules": modules,
            "spans": spans,
            "busy": trace_reduce.union([(s, e) for s, e, _ in ops])}


def slice_of(ctx) -> dict | None:
    """`reduce()` of the run's traced slice, kept on `ctx` so that the
    readers of one run parse the file once. The file is the newest
    `.xplane.pb` under a `.bench_work/<cell>/profile` (the solo replay's is
    under `profile_solo`; a directory that an ended run left behind is older)."""
    if "program_trace" not in ctx:
        found = glob.glob(os.path.join(
            ROOT, ".bench_work", "*", "profile", "plugins", "profile", "*",
            "*.xplane.pb"))
        ctx["program_trace"] = (
            reduce(max(found, key=os.path.getmtime))
            if ctx.get("trace") and found else None)
    return ctx["program_trace"]


# -- what the readers are made of -------------------------------------------

def length(merged) -> float:
    return sum(e - s for s, e in merged)


def intersect(a, b):
    """The overlap of two lists of merged intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(merged, lo, hi):
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if at < hi:
        out.append([at, hi])
    return out


def open_spans(t, names):
    """Merged intervals in which a span of one of `names` is open."""
    return trace_reduce.union([(s, e) for n in names
                               for s, e, _, _ in t["spans"].get(n, [])])


def scope_share(ctx, prefix: str) -> float | None:
    """Device time of the operations whose scope starts with `prefix`, as a
    share of the slice's device-busy time. The UNION of their intervals: a
    `while` and the fusions inside it are counted once. None where no
    operation carries a `pinot.*` scope at all (the program names none)."""
    t = slice_of(ctx)
    if not t or not t["busy"] or not any(scope for _, _, scope in t["ops"]):
        return None
    mine = [(s, e) for s, e, scope in t["ops"] if scope.startswith(prefix)]
    return 100.0 * length(trace_reduce.union(mine)) / length(t["busy"])


def idle_share_while(ctx, open_names, closed_names=()) -> float | None:
    """Share of the slice in which the device is idle, a span of `open_names`
    is open and none of `closed_names` is. None where the trace holds no
    `pinot:pipeline.*` span at all (the program wrote none)."""
    t = slice_of(ctx)
    if not t or not any(n.startswith(SPAN_PREFIX + "pipeline.")
                        for n in t["spans"]):
        return None
    idle = complement(t["busy"], t["lo"], t["hi"])
    held = intersect(idle, open_spans(t, open_names))
    if closed_names:
        held = intersect(held, complement(open_spans(t, closed_names),
                                          t["lo"], t["hi"]))
    return 100.0 * length(held) / (t["hi"] - t["lo"])


def fields(resp, *names):
    """The named response fields as floats, or None if any is absent (a
    program that does not report it)."""
    if any(n not in resp for n in names):
        return None
    return [float(resp[n]) for n in names]


def mean_sum(ctx, *names) -> float | None:
    """Mean over the window's answers of the sum of the named fields."""
    def pick(resp):
        v = fields(resp, *names)
        return sum(v) if v else None
    return readers._mean_of(ctx, pick)
