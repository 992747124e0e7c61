"""Process-global cluster event journal: a causal timeline of state transitions.

The observability planes built before this one (metrics, tracing, the HBM
ledger, workload shapes) are all *level*-based — they say the cluster IS
degraded, not the ordered sequence of transitions that got it there. The
journal is the flight-recorder substrate underneath them: every interesting
state transition (segment lifecycle, tiering admit/evict, admission flips,
detector edges, deepstore quarantine, verdict-plane edges, fault firings)
calls `emit()` with a registered kind, and the bounded ring retains the most
recent window for `/debug/events` and the controller's merged
`/debug/timeline`.

Design points:

* one journal per process (`get_journal()`), mirroring the metrics registry
  singleton — all in-proc roles share it, each stamping its own `node`;
* per-node monotonic `seq` (exact under concurrency — assigned inside the
  ring lock), plus a journal-local arrival counter `gseq` used as the
  incremental-pull cursor for `GET /debug/events?since=<gseq>`;
* `KINDS` is the closed schema table: `emit()` of an unregistered kind
  raises, and the `event-kind-drift` graftcheck rule holds call sites and
  the README glossary to this table;
* the ring evicts strictly oldest-first (like `TraceRing`) and keeps
  emitted/evicted conservation counters so a test can assert
  `emitted == retained + evicted`;
* events emitted while a traced query is active on the calling thread
  inherit the trace id, so query reports can interleave cluster events
  into the waterfall.

The `emit()` fast path is a dataclass construction plus one lock-guarded
deque append and a cached counter increment.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional, Tuple

from .metrics import Counter, get_registry
from .trace import current_trace

#: severity levels, mildest first (used by timeline filters: a severity
#: filter admits its level and everything worse)
SEVERITIES: Tuple[str, ...] = ("INFO", "WARN", "ERROR")

#: the registered-kind schema table: kind -> (default severity, description).
#: This literal IS the contract — `emit()` rejects kinds not listed here,
#: the `event-kind-drift` graftcheck rule requires every call site to use a
#: registered kind and every registered kind to appear in the README
#: glossary. Keep it a plain dict literal (the rule reads it via `ast`).
KINDS: Dict[str, Tuple[str, str]] = {
    "segment.consuming.created": ("INFO", "new CONSUMING segment opened on a stream partition"),
    "segment.committed": ("INFO", "consuming segment sealed and committed to the deepstore"),
    "segment.online": ("INFO", "committed segment flipped CONSUMING->ONLINE in the ideal state"),
    "segment.cold.demoted": ("INFO", "segment demoted to the cold tier (forms dropped, deepstore-backed)"),
    "segment.cold.loaded": ("INFO", "cold segment lazily reloaded from the deepstore on query touch"),
    "segment.reassigned": ("WARN", "consuming segment moved off a dead server"),
    "tier.admission.rejected": ("WARN", "HBM admission rejected a segment load (headroom below floor)"),
    "tier.evicted": ("INFO", "tiering manager evicted a resident segment to reclaim HBM"),
    "tier.promoted": ("INFO", "queried cold segment promoted back to the hot tier"),
    "admission.state": ("WARN", "broker admission controller changed state (HEALTHY/SHEDDING/SATURATED)"),
    "backpressure.hold": ("WARN", "server 429 put it on backpressure hold (out of hedge/retry sets)"),
    "hedge.suppressed": ("WARN", "hedging suppressed because the broker itself is overloaded"),
    "server.down": ("ERROR", "failure detector marked a server unhealthy (probing started)"),
    "server.up": ("INFO", "failure detector restored a probed server to healthy routing"),
    "server.registered": ("INFO", "server handle registered with the broker"),
    "server.unregistered": ("INFO", "server handle unregistered from the broker"),
    "leader.elected": ("INFO", "controller won or took over the leadership lease"),
    "leader.lost": ("WARN", "controller lost the leadership lease"),
    "deepstore.quarantined": ("ERROR", "deepstore upload retries exhausted; segment quarantined"),
    "deepstore.healed": ("INFO", "quarantined/missing deepstore copy healed from a server peer"),
    "fault.fired": ("WARN", "graftfault injection fired at an instrumented site"),
    "verdict.ingestion": ("WARN", "ingestion health verdict changed for a table"),
    "verdict.slo": ("WARN", "freshness/latency SLO verdict changed for a table"),
    "verdict.memory": ("WARN", "device-memory health verdict changed for a table"),
    "verdict.workload": ("WARN", "workload shape regression verdict changed for a fingerprint"),
    "incident.captured": ("ERROR", "flight recorder captured an incident bundle"),
    "bench.probe": ("INFO", "synthetic event the ring's own tests emit"),
}


@dataclass
class Event:
    """One journal entry. `seq` is per-node monotonic (exact); `gseq` is the
    journal-local arrival counter used as the incremental-pull cursor."""
    __slots__ = ("seq", "gseq", "ts_ms", "node", "kind", "severity", "table",
                 "segment", "attrs", "trace_id")
    seq: int
    gseq: int
    ts_ms: int
    node: str
    kind: str
    severity: str
    table: str
    segment: str
    attrs: Dict[str, Any]
    trace_id: str

    def as_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "seq": self.seq, "gseq": self.gseq, "tsMs": self.ts_ms,
            "node": self.node, "kind": self.kind, "severity": self.severity,
        }
        if self.table:
            d["table"] = self.table
        if self.segment:
            d["segment"] = self.segment
        if self.attrs:
            d["attrs"] = self.attrs
        if self.trace_id:
            d["traceId"] = self.trace_id
        return d


class EventJournal:
    """Bounded, lock-guarded ring of typed events with strict oldest-first
    eviction (the `TraceRing` discipline: admit then popleft, so retention
    can never exceed `capacity`)."""

    def __init__(self, capacity: int = 512, node: str = "proc"):
        self.capacity = max(1, int(capacity))
        self.node = node
        self._lock = threading.Lock()
        self._entries: Deque[Event] = deque()       # oldest -> newest
        self._seqs: Dict[str, int] = {}
        self._gseq = 0
        self.emitted = 0
        self.evicted = 0
        #: per-kind Counter cache — emit() must not pay the registry's
        #: name+labels dict lookup on every transition
        self._counters: Dict[str, Counter] = {}

    def configure(self, node: Optional[str] = None,
                  capacity: Optional[int] = None) -> None:
        """Late (re)configuration by role services: the default node label
        and ring capacity (`events.ring.size`). Shrinking trims oldest-first
        immediately."""
        with self._lock:
            if node is not None:
                self.node = node
            if capacity is not None:
                self.capacity = max(1, int(capacity))
                while len(self._entries) > self.capacity:
                    self._entries.popleft()
                    self.evicted += 1

    def _counter(self, kind: str) -> Counter:
        c = self._counters.get(kind)
        if c is None:
            c = get_registry().counter("pinot_events_total", {"kind": kind})
            self._counters[kind] = c
        return c

    def emit(self, kind: str, node: Optional[str] = None, table: str = "",
             segment: str = "", severity: Optional[str] = None,
             trace_id: Optional[str] = None, **attrs: Any) -> Event:
        """Record one transition. `kind` must be registered in `KINDS`
        (closed schema — unregistered kinds raise so drift is loud, and the
        `event-kind-drift` rule catches it statically first). Severity
        defaults from the schema table; sites whose severity depends on
        direction (verdict edges, admission flips) override it. The trace id
        defaults to the calling thread's active query trace, if any."""
        spec = KINDS.get(kind)
        if spec is None:
            raise ValueError(f"unregistered event kind: {kind!r}")
        if trace_id is None:
            tr = current_trace()
            trace_id = tr.trace_id if tr is not None else ""
        ev_node = node if node is not None else self.node
        ts_ms = int(time.time() * 1000)
        with self._lock:
            seq = self._seqs.get(ev_node, 0) + 1
            self._seqs[ev_node] = seq
            self._gseq += 1
            ev = Event(seq, self._gseq, ts_ms, ev_node, kind,
                       severity if severity is not None else spec[0],
                       table, segment, attrs, trace_id)
            self._entries.append(ev)
            self.emitted += 1
            if len(self._entries) > self.capacity:
                self._entries.popleft()
                self.evicted += 1
        self._counter(kind).inc()
        return ev

    def events_since(self, since: int = 0,
                     limit: Optional[int] = None) -> Dict[str, Any]:
        """Incremental pull: events with `gseq > since`, oldest first, plus
        the cursor to pass next time. This is the `/debug/events` payload —
        the controller's timeline merge polls it exactly like the PR 14
        memory checker polls `/debug/memory`."""
        with self._lock:
            rows = [e for e in self._entries if e.gseq > since]
            cursor = self._gseq
        if limit is not None:
            rows = rows[-limit:]
        return {"events": [e.as_dict() for e in rows], "cursor": cursor}

    def entries(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Newest-first retained events (the human-facing read side)."""
        with self._lock:
            rows = list(self._entries)
        rows.reverse()
        rows = rows[:limit] if limit is not None else rows
        return [e.as_dict() for e in rows]

    def snapshot(self) -> Dict[str, Any]:
        """Conservation view: emitted == retained + evicted always holds
        (asserted by the bench lane's ring-eviction check)."""
        with self._lock:
            return {"node": self.node, "capacity": self.capacity,
                    "retained": len(self._entries), "emitted": self.emitted,
                    "evicted": self.evicted, "cursor": self._gseq}

    def clear(self) -> None:
        """Reset ring, sequences and conservation counters (tests/bench)."""
        with self._lock:
            self._entries.clear()
            self._seqs.clear()
            self._gseq = 0
            self.emitted = 0
            self.evicted = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# the process-wide journal (mirrors the metrics REGISTRY singleton)
JOURNAL = EventJournal()


def get_journal() -> EventJournal:
    return JOURNAL


def emit(kind: str, node: Optional[str] = None, table: str = "",
         segment: str = "", severity: Optional[str] = None,
         trace_id: Optional[str] = None, **attrs: Any) -> Event:
    """Record one transition on the process journal (see
    `EventJournal.emit`). Instrumented sites call this module function so
    they never hold a journal reference."""
    return JOURNAL.emit(kind, node=node, table=table, segment=segment,
                        severity=severity, trace_id=trace_id, **attrs)
