"""From a profiler trace (`.xplane.pb`) to device-busy time, idle share, time by
operation name and idle gaps labelled by the host span that covers them.

Reads the file with `jax.profiler.ProfileData` and nothing else of jax. A
device is a plane named `/device:TPU:<n>`; its operations are the events of
its "XLA Ops" line. Host spans are the `bench:*` TraceAnnotations that run.py
puts around the traced slice of the window (`bench:window`) and around each
query of the solo replay (`bench:solo:<template>`). Times in the trace are
nanoseconds since the profile began.

`cpu_stand_in=True` (a CPU rehearsal only, never a measurement) takes the CPU
client's executor threads for the device so that the code path can be driven
where no chip is.
"""

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
NAME_CHARS = 96      # an operation's name in the trace is its whole HLO text


def load(path: str, cpu_stand_in: bool = False) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans, seen = [], [], []
    for plane in data.planes:
        lines = list(plane.lines)
        seen.append((plane.name, [(ln.name, sum(1 for _ in ln.events))
                                  for ln in lines]))
        if plane.name.startswith("/device:TPU:"):
            ops = [(e.start_ns, e.duration_ns, e.name) for ln in lines
                   if ln.name == OPS_LINE for e in ln.events]
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:CPU"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns))
            if cpu_stand_in:
                ops = [(e.start_ns, e.duration_ns, e.name) for ln in lines
                       if ln.name.startswith("tf_XLAPjRtCpuClient")
                       for e in ln.events if e.duration_ns > 0
                       and not e.name.startswith("ThreadpoolListener")]
                devices.append({"name": "cpu-stand-in", "ops": ops})
    return {"devices": devices, "spans": spans, "seen": seen}


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi) -> float:
    """Length of the merged intervals inside [lo, hi)."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def reduce(path: str, cpu_stand_in: bool = False) -> dict:
    """{"window_s", "busy_s", "idle_share", "device_ops", "idle_gaps",
    "spans": {name: [{"start_s", "dur_s", "busy_s"}]}, "devices", "seen"}.
    The window is the `bench:window` span if the trace has one, else the
    extent of all device operations. busy_s is averaged over the devices."""
    t = load(path, cpu_stand_in)
    devices = [d for d in t["devices"] if d["ops"]]
    if not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "idle_share": None,
                "device_ops": [], "idle_gaps": [], "spans": {}, "devices": 0,
                "seen": t["seen"]}
    merged = [union([(s, s + d) for s, d, _ in dev["ops"]])
              for dev in devices]
    win = [sp for sp in t["spans"] if sp[0] == SPAN_PREFIX + "window"]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        lo = min(m[0][0] for m in merged)
        hi = max(m[-1][1] for m in merged)
    n = len(devices)
    busy = sum(covered(m, lo, hi) for m in merged) / n
    by_name = {}
    for dev in devices:
        for s, d, name in dev["ops"]:
            part = max(0.0, min(s + d, hi) - max(s, lo))
            if part > 0:
                name = name[:NAME_CHARS]
                by_name[name] = by_name.get(name, 0.0) + part / n
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    # idle gaps of the first device inside the window, each labelled by the
    # innermost host span over its middle
    gaps, at = [], lo
    for s, e in merged[0]:
        if s > at and s <= hi:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    labelled = {}
    for s, e in gaps:
        mid = (s + e) / 2
        over = [sp for sp in t["spans"] if sp[1] <= mid < sp[1] + sp[2]]
        label = min(over, key=lambda sp: sp[2])[0] if over else "no-span"
        total, longest, count = labelled.get(label, (0.0, 0.0, 0))
        labelled[label] = (total + e - s, max(longest, e - s), count + 1)
    idle = []
    for label, (total, longest, count) in labelled.items():
        idle.append([f"{label} all {count} gaps", total / 1e9])
        idle.append([f"{label} longest gap", longest / 1e9])
    idle.sort(key=lambda kv: -kv[1])
    spans = {}
    for name, s, d in t["spans"]:
        spans.setdefault(name, []).append({
            "start_s": s / 1e9, "dur_s": d / 1e9,
            "busy_s": sum(covered(m, s, s + d) for m in merged) / n / 1e9})
    window_s = (hi - lo) / 1e9
    return {"window_s": window_s, "busy_s": busy / 1e9,
            "idle_share": 100.0 * (1.0 - busy / (hi - lo)) if hi > lo else None,
            "device_ops": [[k, v / 1e9] for k, v in top],
            "idle_gaps": idle[:10], "spans": spans, "devices": n,
            "seen": t["seen"]}


def newest_xplane(log_dir: str) -> str:
    import glob
    import os
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)
