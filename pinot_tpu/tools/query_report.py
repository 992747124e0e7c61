#!/usr/bin/env python
"""Pretty-print one query's ExecutionStats as a phase waterfall.

Feed it the JSON a query response carries (the broker's `stats` block, a full
HTTP response body, or a slow-query log line — all three shapes are accepted):

    python tools/query_report.py response.json
    curl -s broker:8099/query -d '{"sql": "..."}' | python tools/query_report.py

Exported traces work too, so saved `GET /debug/traces` captures analyze
offline without a live cluster: a `{"traces": [...]}` listing, a single ring
entry (`{"traceId", "spans", ...}`), or the Chrome trace-event form
(`{"traceEvents": [...]}`) all render a per-span waterfall.

Output: a wall-clock waterfall of the broker phases (compile / scatter /
reduce) or of the trace's spans, the device-time breakdown inside the scatter
window (compile, exec, fetch, queue wait), and the scan/cache counters —
everything an operator needs to see WHERE a slow query spent its time without
attaching a profiler.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List

BAR_WIDTH = 40


def _extract_stats(doc: Any) -> Dict[str, Any]:
    """Accept a bare stats dict, a response body with a 'stats' block, or a
    slow-query log entry ('stats' + 'sql')."""
    if not isinstance(doc, dict):
        raise ValueError("expected a JSON object")
    if isinstance(doc.get("stats"), dict):
        inner = dict(doc["stats"])
        for k in ("sql", "timeUsedMs", "thresholdMs"):
            if k in doc and k not in inner:
                inner[k] = doc[k]
        return inner
    return doc


def _bar(ms: float, total: float) -> str:
    if total <= 0:
        return ""
    n = int(round(BAR_WIDTH * ms / total))
    return "#" * max(n, 1 if ms > 0 else 0)


def _fmt_ms(v: Any) -> str:
    try:
        return f"{float(v):10.3f} ms"
    except (TypeError, ValueError):
        return f"{v!s:>10}"


def render_report(stats: Dict[str, Any]) -> str:
    """The report body as a string (the CLI prints it; tests assert on it)."""
    out: List[str] = []
    sql = stats.get("sql")
    if sql:
        out.append(f"query: {sql}")
    total = float(stats.get("timeUsedMs") or 0.0)
    phases = stats.get("phaseTimesMs") or {}
    out.append(f"total wall time: {total:.3f} ms")
    out.append("")
    out.append("phase waterfall (broker wall clock)")
    scale = total or sum(float(v) for v in phases.values()) or 1.0
    for name in ("compile", "scatter", "reduce"):
        if name not in phases:
            continue
        ms = float(phases[name])
        out.append(f"  {name:<10} {_fmt_ms(ms)}  |{_bar(ms, scale):<{BAR_WIDTH}}|")
    accounted = sum(float(v) for v in phases.values())
    if total and phases:
        out.append(f"  {'other':<10} {_fmt_ms(max(total - accounted, 0.0))}")
    out.append("")
    out.append("device time (inside scatter, summed over servers)")
    for key, label in (("compileMs", "jit compile"),
                       ("deviceExecMs", "device exec"),
                       ("deviceFetchMs", "device fetch"),
                       ("queueWaitMs", "queue wait"),
                       ("devicePrepareMs", "pipeline prepare"),
                       ("deviceLaunchMs", "pipeline launch"),
                       ("deviceHandoffMs", "pipeline handoff"),
                       ("deviceDecodeMs", "pipeline decode"),
                       ("serverTimeMs", "server execute"),
                       ("muxFrameQueueMs", "mux frame queue"),
                       ("muxFlowControlMs", "mux flow ctl")):
        if key in stats:
            out.append(f"  {label:<16} {_fmt_ms(stats.get(key, 0))}")
    if "deviceSkewPct" in stats:
        try:
            skew = f"{float(stats['deviceSkewPct']):10.1f} %"
        except (TypeError, ValueError):
            skew = f"{stats['deviceSkewPct']!s:>10}"
        out.append(f"  {'device skew':<15} {skew}  (worst mesh launch)")
    # join section only when a join ran (joinStrategy is set by both the
    # funnel and the P2P multistage paths)
    if stats.get("joinStrategy") or any(
            float(stats.get(k) or 0) for k in
            ("joinBuildMs", "joinProbeMs", "joinShuffleBytes",
             "joinServedHostTier")):
        out.append("")
        out.append("join (device hash-join fast path)")
        if stats.get("joinStrategy"):
            out.append(f"  {'strategy':<15} {stats['joinStrategy']:>10}")
        for key, label in (("joinBuildMs", "build"),
                           ("joinProbeMs", "probe")):
            if key in stats:
                out.append(f"  {label:<15} {_fmt_ms(stats.get(key, 0))}")
        if "joinShuffleBytes" in stats:
            out.append(f"  {'shuffle bytes':<15} "
                       f"{int(float(stats['joinShuffleBytes'] or 0)):>10}")
        if "joinSkewPct" in stats:
            try:
                jskew = f"{float(stats['joinSkewPct']):10.1f} %"
            except (TypeError, ValueError):
                jskew = f"{stats['joinSkewPct']!s:>10}"
            out.append(f"  {'probe-key skew':<15} {jskew}  "
                       "(worst hot-bucket excess)")
        if "numSegmentsPrunedByJoinKey" in stats:
            out.append(f"  {'pruned by key':<15} "
                       f"{int(float(stats['numSegmentsPrunedByJoinKey'] or 0)):>10}"
                       "  (probe segments skipped by the build-key filter)")
        if float(stats.get("joinServedHostTier") or 0):
            out.append(f"  {'host-tier joins':<15} "
                       f"{int(float(stats['joinServedHostTier'])):>10}  "
                       "(admission gate priced the join off the device)")
    out.append("")
    out.append("counters")
    for key in ("numSegmentsQueried", "numSegmentsPruned",
                "numSegmentsPrunedByPartition", "numSegmentsPrunedByTime",
                "numSegmentsPrunedByRange", "numSegmentsPrunedByBloom",
                "numSegmentsMatched", "numDocsScanned", "scanRowsAvoided",
                "numGroupsTotal", "deviceLaunches", "fusedLaunches",
                "stagedLaunches", "meshLaunches", "scatterLaunches",
                "collectiveBytes", "routedSlots", "residentSlots",
                "scannedSlots", "mergedLaunches", "setBlockBytes",
                "dedupedLaunches", "stackedLaunches", "compileCacheHits",
                "compileCacheMisses", "bytesFetched", "deviceBatchSize",
                "numServersQueried", "numServersResponded"):
        if key in stats:
            out.append(f"  {key:<20} {stats[key]}")
    if stats.get("partialResult"):
        out.append("  ** PARTIAL RESULT — some servers/segments missing **")
    return "\n".join(out)


def _trace_entries(doc: Any) -> List[Dict[str, Any]]:
    """Detect an exported-trace document: a /debug/traces listing, a single
    ring entry, or a Chrome trace-event export. Returns normalized entries
    ({traceId, sql?, timeUsedMs?, spans: [{name, startMs, durationMs, depth,
    error?}]}), or [] when `doc` is not a trace document."""
    if not isinstance(doc, dict):
        return []
    if isinstance(doc.get("traces"), list):
        return [e for e in doc["traces"] if isinstance(e, dict)]
    if isinstance(doc.get("spans"), list) and "traceId" in doc:
        return [doc]
    if isinstance(doc.get("traceEvents"), list):
        # fold the Chrome form back: one entry per pid, µs back to ms
        by_pid: Dict[Any, Dict[str, Any]] = {}
        for ev in doc["traceEvents"]:
            if not isinstance(ev, dict):
                continue
            pid = ev.get("pid")
            entry = by_pid.setdefault(pid, {"traceId": f"pid{pid}",
                                            "spans": []})
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                entry["sql"] = (ev.get("args") or {}).get("name", "")
            elif ev.get("ph") == "X":
                entry["spans"].append({
                    "name": ev.get("name", ""),
                    "startMs": float(ev.get("ts", 0.0)) / 1000.0,
                    "durationMs": float(ev.get("dur", 0.0)) / 1000.0,
                    "depth": (ev.get("args") or {}).get("depth", 0),
                    "error": bool((ev.get("args") or {}).get("error")),
                })
        return list(by_pid.values())
    return []


def _events_for(trace_id: Any, events: Any) -> List[Dict[str, Any]]:
    """Journal events carrying this trace's id (the event journal stamps
    `traceId` from the ambient trace at emit time)."""
    if not trace_id or not isinstance(events, list):
        return []
    return [e for e in events
            if isinstance(e, dict) and e.get("traceId") == trace_id]


def render_events_section(events: List[Dict[str, Any]]) -> str:
    """Cluster-state transitions that fired DURING this query (same traceId),
    oldest first — a slow query that straddles a server.down or an admission
    flip shows the transition inline with its waterfall."""
    out: List[str] = ["journal events (same traceId)"]
    ordered = sorted(events, key=lambda e: (float(e.get("tsMs") or 0),
                                            str(e.get("node", "")),
                                            int(e.get("seq") or 0)))
    origin = min(float(e.get("tsMs") or 0) for e in ordered)
    for ev in ordered:
        offset = (float(ev.get("tsMs") or 0) - origin) / 1000.0
        subject = ev.get("segment") or ev.get("table") or ""
        attrs = ev.get("attrs") or {}
        detail = "  ".join(f"{k}={attrs[k]}" for k in sorted(attrs))
        line = (f"  +{offset:7.3f}s  {ev.get('node', '?'):<14} "
                f"{ev.get('kind', '?'):<24} {subject}")
        if detail:
            line = f"{line.rstrip()}  {detail}"
        out.append(line.rstrip())
    return "\n".join(out)


def render_trace(entry: Dict[str, Any],
                 events: Any = None) -> str:
    """Span waterfall for one retained trace: rows sorted by start, indented
    by nesting depth, bars on a shared wall-clock axis. Journal events with
    the same traceId (pass the `/debug/timeline` body's `events` list, or
    embed an `events` key in the document) interleave below the spans."""
    out: List[str] = []
    head = f"trace: {entry.get('traceId', '?')}"
    if entry.get("sql"):
        head += f"  {entry['sql']}"
    out.append(head)
    meta = [f"{k}={entry[k]}" for k in ("timeUsedMs", "sampled", "slow",
                                        "error") if k in entry]
    if meta:
        out.append("  " + "  ".join(meta))
    matched = _events_for(entry.get("traceId"), events)
    spans = sorted(entry.get("spans") or [],
                   key=lambda s: float(s.get("startMs", 0.0)))
    if not spans:
        out.append("  (no spans)")
        if matched:
            out.append("")
            out.append(render_events_section(matched))
        return "\n".join(out)
    end = max(float(s.get("startMs", 0.0)) + float(s.get("durationMs", 0.0))
              for s in spans)
    origin = min(float(s.get("startMs", 0.0)) for s in spans)
    scale = (end - origin) or 1.0
    out.append("")
    for s in spans:
        depth = int(s.get("depth", 0))
        name = "  " * depth + str(s.get("name", "?"))
        start = float(s.get("startMs", 0.0))
        dur = float(s.get("durationMs", 0.0))
        lead = int(round(BAR_WIDTH * (start - origin) / scale))
        bar = " " * lead + (_bar(dur, scale) or ("|" if dur >= 0 else ""))
        flag = "  !ERROR" if s.get("error") else ""
        out.append(f"  {name:<34} {_fmt_ms(dur)}  "
                   f"|{bar:<{BAR_WIDTH}}|{flag}")
    if matched:
        out.append("")
        out.append(render_events_section(matched))
    return "\n".join(out)


def main(argv: List[str]) -> int:
    if len(argv) > 1 and argv[1] not in ("-", "-h", "--help"):
        with open(argv[1]) as f:
            doc = json.load(f)
    elif len(argv) > 1 and argv[1] in ("-h", "--help"):
        print(__doc__)
        return 0
    else:
        doc = json.load(sys.stdin)
    # a `/debug/timeline` body (or an incident bundle) pasted alongside the
    # trace doc interleaves its journal events into each trace's report
    events = doc.get("events") if isinstance(doc, dict) else None
    traces = _trace_entries(doc)
    if traces:
        print("\n\n".join(render_trace(e, events=events) for e in traces))
        return 0
    stats = _extract_stats(doc)
    report = render_report(stats)
    matched = _events_for(stats.get("traceId"), events)
    if matched:
        report = f"{report}\n\n{render_events_section(matched)}"
    print(report)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
