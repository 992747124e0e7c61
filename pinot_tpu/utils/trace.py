"""Per-request tracing: named spans with timings, across scatter threads.

Analog of the reference's trace SPI (`pinot-spi/src/main/java/org/apache/pinot/spi/
trace/Tracing.java:32` + `DefaultRequestContext`): a request-scoped recorder that
operators register phase timings into, surfaced in the broker response when the query
sets OPTION(trace=true) (reference: `CommonConstants.Request.TRACE`).

Design departure: the reference builds a tree of per-operator trace nodes per server
and merges them in the broker reduce. Here a single flat span list with depth markers
is shared by every thread working the request (the broker's scatter pool threads
`activate` the same Trace), which keeps the recorder lock-free on the read side and
needs no cross-process merge for the in-proc transport. Remote (HTTP) servers attach
their span lists to the serialized partial and the broker splices them in.

Always-on sampling layer (the broker owns one of each):

* every query gets a Trace (span recording is a dict append — cheap enough to
  leave on unconditionally), identified by a `trace_id` that rides the wire to
  servers and back in the response stats;
* `TraceSampler` — head-based probabilistic admission (`broker.trace.sample.rate`)
  deciding which traces are RETAINED; seedable for deterministic tests;
* `TraceRing` — the bounded retention ring behind `GET /debug/traces`. Queries
  crossing `broker.slow.query.ms` are force-admitted at the tail regardless of
  the head decision, so every slow-query log line resolves to a full trace;
* `to_chrome_trace` — renders ring entries as a Chrome trace-event JSON document
  (loadable in Perfetto / chrome://tracing) with one track per server hop.

Second sink, the profiler's clock: `span()` and `stage()` also open a
`jax.profiler.TraceAnnotation("pinot:<name>")`, so while a profiler session
runs the same spans land in its `/host:CPU` plane on the clock of the device
operations (a span of a request carries its `trace_id`). `stage()` is for
threads that serve many requests and so have no `Trace` of their own (the
device pipeline's dispatcher and fetcher). With no session an annotation is a
flag test. Both time their body on the wall clock (`ms`); a `stage(...,
cpu=True)` also on its thread's CPU clock (`cpu_ms`, `time.thread_time`):
wall far above CPU in a body that does no I/O is a thread waiting for the
GIL. The CPU clock is a system call (8.6 us a read on the chip's host, 0.35
in a sandbox: PR 38), so only the stages whose CPU lands on an answer read it.

The process's garbage collections are the third thing on that clock:
`install_gc_hook()` (once a process, by the server) counts every collection
and its pause (`gc_stats()`, on `/health`'s `device` block) and opens
`pinot:gc` around each generation-2 one.
"""

from __future__ import annotations

import gc
import random
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterable, List, Optional, Union

from jax.profiler import TraceAnnotation

#: every annotation this recorder opens in the profiler's trace starts so
ANNOTATION_PREFIX = "pinot:"

_local = threading.local()


def new_trace_id() -> str:
    """16-hex-char unique id (the W3C trace-context span-id width)."""
    return uuid.uuid4().hex[:16]


class Trace:
    """Request-scoped span recorder. Thread-safe appends; one instance per query."""

    def __init__(self, request_id: str = "", trace_id: Optional[str] = None):
        self.request_id = request_id
        self.trace_id = trace_id or new_trace_id()
        #: head-sampling decision (set by the broker); tail retention may admit
        #: the trace into the ring even when False
        self.sampled = False
        self.spans: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    def now_ms(self) -> float:
        """Milliseconds since this trace's origin — THE public clock. Span
        starts, remote rebasing, and pipeline attribution read this instead of
        reaching into `_t0`."""
        return (time.perf_counter() - self._t0) * 1000

    def record(self, name: str, start_ms: float, duration_ms: float,
               depth: int = 0, error: bool = False) -> None:
        span = {
            "name": name,
            "startMs": round(start_ms, 3),
            "durationMs": round(duration_ms, 3),
            "depth": depth,
        }
        if error:
            span["error"] = True
        with self._lock:
            self.spans.append(span)

    def splice(self, spans: List[Dict[str, Any]], prefix: str = "",
               offset_ms: float = 0.0, depth_offset: int = 0) -> None:
        """Merge a remote server's span list. Its startMs values are relative to the
        SERVER's request start; `offset_ms` (when the dispatch left this trace's
        timeline) rebases them so the merged view sorts on one axis. `depth_offset`
        rebases the remote depths the same way — the server recorded depth 0 at its
        own request root, but spliced spans nest under the dispatching span (pass
        `current_depth()` from inside it) so the merged tree renders correctly."""
        with self._lock:
            for s in spans:
                s = dict(s)
                if prefix:
                    s["name"] = f"{prefix}/{s['name']}"
                s["startMs"] = round(s.get("startMs", 0.0) + offset_ms, 3)
                s["depth"] = int(s.get("depth", 0)) + depth_offset
                self.spans.append(s)

    def to_rows(self) -> List[Dict[str, Any]]:
        with self._lock:
            return sorted(self.spans, key=lambda s: s["startMs"])

    @contextmanager
    def activate(self, depth: int = 0):
        """Make this trace current for the calling thread (scatter-pool workers).
        `depth` seeds the thread's nesting level — a server scheduler thread
        passes the dispatch-site depth so its spans nest under the dispatching
        span exactly like HTTP-spliced spans do."""
        prev = getattr(_local, "trace", None)
        prev_depth = getattr(_local, "depth", 0)
        _local.trace = self
        _local.depth = depth
        try:
            yield self
        finally:
            _local.trace = prev
            _local.depth = prev_depth


def current_trace() -> Optional[Trace]:
    return getattr(_local, "trace", None)


def current_depth() -> int:
    """The calling thread's span nesting depth — what a span opened NOW would
    record. Used to nest spliced remote spans under their dispatch span."""
    return getattr(_local, "depth", 0)


@contextmanager
def request_trace(enabled: bool, request_id: str = "",
                  trace_id: Optional[str] = None):
    """Start a trace for this request on the current thread; None when disabled —
    `span()` then degrades to a no-op so instrumented code never branches.
    `trace_id` carries a propagated wire context (server side of a dispatch)."""
    if not enabled:
        yield None
        return
    tr = Trace(request_id, trace_id=trace_id)
    with tr.activate():
        yield tr


@contextmanager
def span(name: str):
    """`with span("server.merge") as sp: ...` then `sp.ms`: a `stage()` that
    is also recorded on the current thread's active trace
    (only the annotation where there is none). A body that exits via
    exception marks the span `error: true` so failed phases are visible in
    exported timelines."""
    tr = getattr(_local, "trace", None)
    if tr is None:
        with stage(name) as st:
            yield st
        return
    depth = getattr(_local, "depth", 0)
    _local.depth = depth + 1
    start_ms = tr.now_ms()
    st = stage(name, trace_id=tr.trace_id)
    error = False
    try:
        with st:
            yield st
    except BaseException:
        error = True
        raise
    finally:
        _local.depth = depth
        tr.record(name, start_ms, st.ms, depth, error=error)


class stage:
    """`with stage("pipeline.fetch", batch=2) as st: ...` then `st.ms`: a span
    on a thread that has no request `Trace`. It opens the profiler annotation
    `pinot:<name>` with `attrs` and times the body on `perf_counter`, and with
    `cpu=True` on the thread's CPU clock too (`st.cpu_ms`; None without);
    where the milliseconds go (an item's stats, a histogram) is the caller's
    business."""

    __slots__ = ("_annotation", "_t0", "_c0", "ms", "cpu_ms")

    def __init__(self, name: str, cpu: bool = False, **attrs: Any):
        self._annotation = TraceAnnotation(ANNOTATION_PREFIX + name, **attrs)
        self.ms = 0.0
        self.cpu_ms = 0.0 if cpu else None

    def __enter__(self) -> "stage":
        self._annotation.__enter__()
        if self.cpu_ms is not None:
            self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def note(self, **attrs: Any) -> None:
        """Attributes known only once the body has run (a batch's size)."""
        self._annotation.set_metadata(**attrs)

    def __exit__(self, *exc) -> bool:
        self.ms = (time.perf_counter() - self._t0) * 1000
        if self.cpu_ms is not None:
            self.cpu_ms = (time.thread_time() - self._c0) * 1000
        self._annotation.__exit__(*exc)
        return False


# -- garbage collection on the same clock -------------------------------------

#: collections of every generation, and their pauses summed (ms)
_gc_counts = {"gcCollections": 0, "gcPauseMs": 0.0}
#: the collection in progress: (its start, its `pinot:gc` stage or None). A
#: collection runs on one thread and never overlaps another
_gc_open: list = []


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        st = None
        if info.get("generation") == 2:
            # a young collection is only counted: annotating each costs more
            # than it tells
            st = stage("gc", generation=2).__enter__()
        _gc_open.append((time.perf_counter(), st))
    elif _gc_open:
        t0, st = _gc_open.pop()
        if st is not None:
            st.__exit__(None, None, None)
        _gc_counts["gcCollections"] += 1
        _gc_counts["gcPauseMs"] += (time.perf_counter() - t0) * 1000


def install_gc_hook() -> None:
    """Count the process's collections and time their pauses (idempotent)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_stats() -> Dict[str, Any]:
    """`gcCollections`, `gcPauseMs` since the hook went in (counters: a
    reader takes deltas over its own window)."""
    return {"gcCollections": _gc_counts["gcCollections"],
            "gcPauseMs": round(_gc_counts["gcPauseMs"], 3)}


# -- sampling + retention -----------------------------------------------------

class TraceSampler:
    """Head-based probabilistic sampler. The rate is passed per call (the
    broker re-reads `broker.trace.sample.rate` from clusterConfig each query);
    inject a seeded `random.Random` for deterministic tests."""

    def __init__(self, rng: Optional[random.Random] = None):
        self._rng = rng if rng is not None else random.Random()
        self._lock = threading.Lock()

    def sample(self, rate: float) -> bool:
        if rate <= 0.0:
            return False
        if rate >= 1.0:
            return True
        with self._lock:
            return self._rng.random() < rate


class TraceRing:
    """Bounded ring of retained traces, keyed by trace id. Head-sampled traces
    and tail-retained (slow / errored) traces both land here; eviction is
    strictly oldest-first so the ring can never grow past `capacity`."""

    def __init__(self, capacity: int = 256):
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._entries: "deque" = deque()        # oldest -> newest
        self._by_id: Dict[str, Dict[str, Any]] = {}

    def admit(self, trace: Trace, **meta: Any) -> Dict[str, Any]:
        """Retain one finished trace; `meta` carries query-level context
        (sql, timeUsedMs, slow/error flags)."""
        entry: Dict[str, Any] = {
            "traceId": trace.trace_id,
            "requestId": trace.request_id,
            "sampled": bool(trace.sampled),
            "spans": trace.to_rows(),
        }
        entry.update(meta)
        with self._lock:
            self._entries.append(entry)
            self._by_id[entry["traceId"]] = entry
            while len(self._entries) > self.capacity:
                dead = self._entries.popleft()
                if self._by_id.get(dead["traceId"]) is dead:
                    del self._by_id[dead["traceId"]]
        return entry

    def get(self, trace_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._by_id.get(trace_id)

    def entries(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Newest-first retained entries (bounded by `limit` when given)."""
        with self._lock:
            rows = list(self._entries)
        rows.reverse()
        return rows[:limit] if limit is not None else rows

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# -- Chrome trace-event export ------------------------------------------------

def to_chrome_trace(entries: Union[Dict[str, Any], Iterable[Dict[str, Any]]]
                    ) -> Dict[str, Any]:
    """Render ring entries as a Chrome trace-event JSON document (the
    `{"traceEvents": [...]}` format Perfetto and chrome://tracing load).

    Each retained query becomes one pid; span tracks split by hop — the
    broker's own spans on one tid, each `server:<id>/...` spliced hop on its
    own — so the broker↔server decomposition reads as parallel timelines.
    All events are complete events (`ph: "X"`, microsecond ts/dur) plus
    metadata events naming the process/threads."""
    if isinstance(entries, dict):
        entries = [entries]
    events: List[Dict[str, Any]] = []
    for pid, entry in enumerate(entries, start=1):
        label = entry.get("sql") or entry.get("requestId") or ""
        events.append({"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                       "args": {"name": f"query {entry.get('traceId', '')} "
                                        f"{label}".strip()}})
        tids: Dict[str, int] = {}
        for s in entry.get("spans", ()):
            name = str(s.get("name", ""))
            track = (name.split("/", 1)[0]
                     if name.startswith("server:") and "/" in name
                     else "broker")
            if track not in tids:
                tids[track] = len(tids) + 1
                events.append({"name": "thread_name", "ph": "M", "pid": pid,
                               "tid": tids[track], "args": {"name": track}})
            args: Dict[str, Any] = {"depth": int(s.get("depth", 0))}
            if s.get("error"):
                args["error"] = True
            events.append({
                "name": name,
                "cat": "query",
                "ph": "X",
                "ts": round(max(float(s.get("startMs", 0.0)), 0.0) * 1000.0, 3),
                "dur": round(max(float(s.get("durationMs", 0.0)), 0.0) * 1000.0, 3),
                "pid": pid,
                "tid": tids[track],
                "args": args,
            })
        # device-memory residency rides the same timeline as counter events
        # (`ph: "C"` renders as a filled area track under the spans), so a
        # trace shows HBM residency next to the work that created it
        for sample in entry.get("memory") or ():
            ts = round(max(float(sample.get("tsMs", 0.0)), 0.0) * 1000.0, 3)
            for series, value in (sample.get("series") or {}).items():
                events.append({"name": str(series), "cat": "memory",
                               "ph": "C", "ts": ts, "pid": pid, "tid": 0,
                               "args": {"bytes": value}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}
