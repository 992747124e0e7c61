"""Broker role: SQL entry, routing, scatter/gather, reduce.

Analog of the reference's broker request path (SURVEY.md §3.1 top half):
`BaseBrokerRequestHandler.handleRequest` compile + routing split, `QueryRouter`
scatter, `BrokerReduceService` reduce. The scatter here calls server objects directly
(in-proc) or via the HTTP transport's server proxies; per-server calls run on a thread
pool like the reference's async Netty channels, and failed servers are reported as
partial results + marked unhealthy (reference: `ConnectionFailureDetector` ->
`excludeServerFromRouting`, `SingleConnectionBrokerRequestHandler.java:169-175`).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import threading
import time
from collections import deque
from concurrent.futures import (FIRST_COMPLETED, Future, ThreadPoolExecutor,
                                as_completed)
from concurrent.futures import TimeoutError as FutureTimeoutError
from concurrent.futures import wait as futures_wait
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple, Union

from ..query import stats as qstats
from ..query.aggregates import make_agg
from ..query.context import (SOLE_SERVER, QueryContext, QueryValidationError,
                             compile_query)
from ..query.reduce import SegmentResult, merge_segment_results, reduce_to_result
from ..query.result import ResultTable
from ..sql.ast import to_sql
from ..table import TableType
from ..utils.events import emit as emit_event
from .catalog import Catalog, InstanceInfo
from .routing import RoutingManager

# server handle: execute_partial(table, ctx, segment_names, time_filter) -> SegmentResult
ServerHandle = Callable[..., SegmentResult]

from ..constants import UNBOUNDED_LIMIT


class FailureDetector:
    """Exponential-backoff re-probing of unhealthy servers (reference:
    `BaseExponentialBackoffRetryFailureDetector`): a server excluded from
    routing after a transport failure is probed on a growing interval and
    returned to rotation when its probe succeeds — without this, one blip
    removes a server until an operator intervenes."""

    def __init__(self, routing, initial_interval_s: float = 0.5,
                 backoff_factor: float = 2.0, max_interval_s: float = 30.0,
                 probe_timeout_s: float = 10.0, node: str = ""):
        self.routing = routing
        self._node = node          # event journal label (the broker's id)
        self.initial_interval_s = initial_interval_s
        self.backoff_factor = backoff_factor
        self.max_interval_s = max_interval_s
        self.probe_timeout_s = probe_timeout_s
        self._probes: Dict[str, Callable[[], bool]] = {}
        # server -> (next probe time, current interval)
        self._pending: Dict[str, Tuple[float, float]] = {}
        # server -> consecutive failed probes since it was last healthy
        self._fail_counts: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def register_probe(self, server_id: str, probe: Callable[[], bool]) -> None:
        with self._lock:
            self._probes[server_id] = probe

    def notify_unhealthy(self, server_id: str) -> None:
        newly_down = False
        with self._lock:
            if server_id in self._probes and server_id not in self._pending:
                self._pending[server_id] = (
                    time.time() + self.initial_interval_s,
                    self.initial_interval_s)
                newly_down = True
        if newly_down:
            # edge, not level: repeated failures while probing stay silent
            emit_event("server.down", node=self._node or None,
                       server=server_id)

    def notify_healthy(self, server_id: str) -> None:
        with self._lock:
            self._pending.pop(server_id, None)
            self._fail_counts.pop(server_id, None)

    def remove(self, server_id: str) -> None:
        """Forget a decommissioned server entirely: its probe closure must not
        be retained (a reused port answering 2xx would re-admit a dead id)."""
        with self._lock:
            self._probes.pop(server_id, None)
            self._pending.pop(server_id, None)
            self._fail_counts.pop(server_id, None)

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        """Operator view per registered server: `state` (healthy | probing),
        consecutive failed probes, and seconds until the next probe (absent
        for healthy servers). Feeds the broker /debug panel and cluster_top."""
        now = time.time()
        with self._lock:
            out: Dict[str, Dict[str, object]] = {}
            for server_id in self._probes:
                entry = self._pending.get(server_id)
                if entry is None:
                    out[server_id] = {"state": "healthy",
                                      "consecutiveFailures": 0}
                else:
                    out[server_id] = {
                        "state": "probing",
                        "consecutiveFailures":
                            self._fail_counts.get(server_id, 0),
                        "nextProbeInS": round(max(0.0, entry[0] - now), 3),
                    }
            return out

    def tick(self, now: Optional[float] = None) -> None:
        """Probe every due server once (tests drive this deterministically;
        `start()` runs it on a daemon thread). Probes run CONCURRENTLY: one
        unreachable host's timeout must not serialize behind it the recovery
        of every other server."""
        now = time.time() if now is None else now
        with self._lock:
            due = [(s, iv) for s, (t, iv) in self._pending.items() if t <= now]
            # snapshot: probe closures are registered/removed under the lock
            # from other threads; the fan-out below must not read the live map
            probes = dict(self._probes)
        if not due:
            return

        def run_probe(server_id: str) -> bool:
            probe = probes.get(server_id)
            try:
                return bool(probe()) if probe else False
            except Exception:
                return False

        pool = ThreadPoolExecutor(max_workers=min(8, len(due)),
                                  thread_name_prefix="fd-probe")
        try:
            # graftcheck: ignore[admission-bypass] -- fan-out is len(due)
            # health probes per tick (bounded by cluster size, not query
            # load) and the pool is shut down before the tick returns
            futs = {s: pool.submit(run_probe, s) for s, _ in due}
            results = {}
            for s, f in futs.items():
                try:
                    # a probe closure stuck past its own transport timeout
                    # counts as a failed probe — the tick must not wedge
                    results[s] = f.result(timeout=self.probe_timeout_s)
                except FutureTimeoutError:
                    results[s] = False
        finally:
            # wait=False: a wedged probe thread must not block the tick
            # (it is abandoned; the NEXT tick probes through a fresh pool)
            pool.shutdown(wait=False)
        for server_id, interval in due:
            ok = results[server_id]
            with self._lock:
                if server_id not in self._pending:
                    continue  # raced with notify_healthy/remove
                if ok:
                    self._pending.pop(server_id, None)
                    self._fail_counts.pop(server_id, None)
                else:
                    nxt = min(interval * self.backoff_factor,
                              self.max_interval_s)
                    self._pending[server_id] = (now + nxt, nxt)
                    self._fail_counts[server_id] = \
                        self._fail_counts.get(server_id, 0) + 1
            if ok:
                self.routing.mark_server_healthy(server_id)
                emit_event("server.up", node=self._node or None,
                           server=server_id)

    def start(self, tick_s: float = 0.25) -> None:
        def loop():
            while not self._stop.wait(tick_s):
                self.tick()
        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="failure-detector")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        thread = getattr(self, "_thread", None)
        if thread is not None:
            thread.join(timeout=5.0)  # loop wakes within tick_s of the event


class _DispatchUnit:
    """One scatter work unit: a primary dispatch to a server plus, when the
    hedging machinery duplicates it, one hedge dispatch to an alternate
    replica. Resolution is FIRST SUCCESS WINS — the loser's partial is dropped
    unmerged, so merged stats (`numSegmentsQueried` and friends) never
    double-count a hedged unit's segments."""

    __slots__ = ("server", "segments", "primary", "t0", "hedge",
                 "hedge_server", "hedge_exhausted", "failed")

    def __init__(self, server: str, segments: List[str], primary: Future):
        self.server = server
        self.segments = segments
        self.primary = primary
        self.t0 = time.monotonic()
        self.hedge: Optional[Future] = None
        self.hedge_server: Optional[str] = None
        self.hedge_exhausted = False   # no eligible alternate replica
        self.failed: Dict[Future, BaseException] = {}


class Broker:
    def __init__(self, instance_id: str, catalog: Catalog,
                 max_scatter_threads: int = 8):
        self.instance_id = instance_id
        self.catalog = catalog
        self.routing = RoutingManager(catalog)
        self._servers: Dict[str, ServerHandle] = {}
        self._explain: Dict[str, Callable] = {}
        self._stage: Dict[str, Callable] = {}
        self._pool = ThreadPoolExecutor(max_workers=max_scatter_threads,
                                        thread_name_prefix=f"{instance_id}-scatter")
        self._urls: Dict[str, str] = {}   # server_id -> HTTP endpoint (P2P shuffle)
        # per-stage dispatch timeout for the mailbox shuffle
        self.stage_timeout_s = 120.0
        # data-plane memory cap for the legacy broker-funnel multistage path:
        # a query that would materialize more than this many (estimated) bytes
        # of inter-stage data IN BROKER MEMORY fails with a clear error instead
        # of OOMing the broker (None = uncapped; the mailbox shuffle path never
        # buffers inter-stage data here, so it is not subject to the cap)
        self.max_data_plane_bytes: Optional[int] = None
        # slow-query observability: queries over `broker.slow.query.ms`
        # (clusterConfig) emit one structured log line and land in this ring,
        # surfaced with the query rollups on the HTTP /debug endpoint
        self._recent_slow: "deque" = deque(maxlen=32)
        self._query_rollup: Dict[str, float] = {
            "numQueries": 0, "numExceptions": 0, "numSlowQueries": 0,
            "totalTimeMs": 0.0, "maxTimeMs": 0.0,
        }
        self._obs_lock = threading.Lock()
        # always-on sampled tracing: EVERY query records a Trace (span appends
        # are cheap); the head sampler (`broker.trace.sample.rate`) only
        # decides ring RETENTION, and slow/errored queries tail-retain
        # regardless so every slow-query log line resolves at /debug/traces
        from ..utils.trace import TraceRing, TraceSampler
        self.trace_sampler = TraceSampler()
        self.trace_ring = TraceRing(capacity=256)
        # per-table cumulative resource rollup behind the pinot_table_* gauge
        # family and the /debug tableStats panel (under _obs_lock); dropped
        # tables are swept lazily against the live catalog
        self._table_rollup: Dict[str, Dict[str, float]] = {}
        self._table_sweep_countdown = 0
        self._lock = threading.RLock()
        from ..query.scheduler import QueryQuotaManager
        from .admission import AdmissionController
        self.quota = QueryQuotaManager(catalog)
        self.admission = AdmissionController(catalog, node=instance_id)
        # server_id -> monotonic time until which the server is considered in
        # backpressure (fed by Retry-After hints on 429s); hedges and retry
        # rounds avoid these servers instead of amplifying their overload
        self._backpressure_until: Dict[str, float] = {}
        self.failure_detector = FailureDetector(self.routing,
                                                node=instance_id)
        # workload intelligence plane: per-shape profiles keyed by plan
        # fingerprint, LRU-bounded with overflow counters (/debug/workload)
        from .workload import WorkloadRegistry
        self.workload = WorkloadRegistry(catalog)
        catalog.register_instance(InstanceInfo(instance_id, "broker"))

    def register_server_handle(self, server_id: str, handle: ServerHandle,
                               explain_handle=None, probe=None,
                               stage_handle=None, url: Optional[str] = None
                               ) -> None:
        """Wire a server's execute entry (direct object in-proc, HTTP proxy remote).
        `explain_handle(table, ctx, segments) -> rows` serves EXPLAIN PLAN;
        `probe() -> bool` lets the failure detector re-admit the server after a
        transport failure (no probe = manual recovery only);
        `stage_handle(spec, left, right, agg=None) -> block | SegmentResult`
        runs one multistage stage partition on the server — the hash join,
        plus the partial GROUP BY when `agg` (an AggStageSpec) is given (the
        worker-mailbox + partial-AggregateOperator analog);
        `url` is the server's HTTP endpoint — when every routed server has
        one, multistage queries run the peer-to-peer mailbox shuffle instead
        of funneling inter-stage data through this broker."""
        with self._lock:
            self._servers[server_id] = handle
            if explain_handle is not None:
                self._explain[server_id] = explain_handle
            if stage_handle is not None:
                self._stage[server_id] = stage_handle
            if url is not None:
                self._urls[server_id] = url.rstrip("/")
            else:
                self._urls.pop(server_id, None)
        if probe is not None:
            self.failure_detector.register_probe(server_id, probe)
        self.failure_detector.notify_healthy(server_id)
        self.routing.mark_server_healthy(server_id)
        emit_event("server.registered", node=self.instance_id,
                   server=server_id)

    def unregister_server(self, server_id: str) -> None:
        """Forget a decommissioned server: every handle map + detector entry
        (a retained stage/query handle would keep dispatching to a dead URL)."""
        with self._lock:
            self._servers.pop(server_id, None)
            self._explain.pop(server_id, None)
            self._stage.pop(server_id, None)
            self._urls.pop(server_id, None)
        self.failure_detector.remove(server_id)
        self.routing.mark_server_unhealthy(server_id)
        emit_event("server.unregistered", node=self.instance_id,
                   server=server_id)

    # ------------------------------------------------------------------
    def handle_query(self, sql: str, stmt=None) -> ResultTable:
        """Full broker path: compile -> resolve physical tables -> scatter -> reduce.

        Join queries delegate to the multistage engine with a cluster-wide leaf-scan
        provider (reference: `BrokerRequestHandlerDelegate` picking
        `MultiStageBrokerRequestHandler`). Emits broker metrics (reference:
        BrokerMeter QUERIES/...EXCEPTIONS) and, under OPTION(trace=true), a span
        trace in `stats["traceInfo"]` (reference: Tracing.java request tracing)."""
        from ..utils import trace as tracing
        from ..utils.metrics import get_registry
        reg = get_registry()
        t0 = time.perf_counter()
        tr = None
        table = None
        shape = None
        # in-flight depth is the admission state machine's primary signal;
        # begin/end bracket the WHOLE request so multistage joins count too
        self.admission.begin()
        try:
            try:
                if stmt is None:
                    from ..sql.parser import parse_query
                    stmt = parse_query(sql)
                stmt = self._rewrite_subqueries(stmt)
                table = stmt.table
                with tracing.span("broker.fingerprint"):
                    shape = self._plan_shape(stmt)
                trace_on = _truthy(stmt.options.get("trace"))
                # always-on: the trace records regardless, the sampler only
                # gates ring retention; OPTION(trace=true) force-samples AND
                # returns the spans inline (traceInfo), exactly as before
                with tracing.request_trace(True) as tr:
                    tr.sampled = trace_on or self.trace_sampler.sample(
                        self._trace_sample_rate())
                    from ..multistage.planner import stmt_has_in_subquery
                    if stmt.joins or stmt_has_in_subquery(stmt):
                        result = (self._explain_multistage(stmt)
                                  if stmt.explain
                                  else self._handle_multistage(stmt))
                    else:
                        result = self._handle_single(stmt, t0)
                    if trace_on:
                        result.stats["traceInfo"] = tr.to_rows()
                    result.stats["traceId"] = tr.trace_id
            except Exception:
                reg.counter("pinot_broker_query_exceptions").inc()
                elapsed_ms = (time.perf_counter() - t0) * 1000
                with self._obs_lock:
                    self._query_rollup["numExceptions"] += 1
                if table:
                    self._table_account(table, elapsed_ms, error=True)
                if tr is not None and tr.sampled:
                    # errored traces tail-retain so failures are inspectable
                    meta = dict(sql=sql, error=True,
                                timeUsedMs=round(elapsed_ms, 3),
                                memory=self._memory_samples(elapsed_ms))
                    if shape is not None:
                        meta["workloadFingerprint"] = shape.fingerprint
                    self.trace_ring.admit(tr, **meta)
                raise
        finally:
            self.admission.end()
        elapsed_ms = (time.perf_counter() - t0) * 1000
        result.stats["timeUsedMs"] = round(elapsed_ms, 3)
        if shape is not None:
            result.stats[qstats.WORKLOAD_FINGERPRINT] = shape.fingerprint
        reg.counter("pinot_broker_queries").inc()
        reg.timer("pinot_broker_query_latency_ms").update(elapsed_ms)
        with tracing.span("broker.account"):
            self._account_query(sql, result, elapsed_ms, tr=tr, table=table,
                                shape=shape)
        return result

    @staticmethod
    def _plan_shape(stmt):
        """Normalize the parsed plan into its PlanShape (sql/fingerprint.py).
        Best-effort: fingerprinting must never fail a query, so an exotic
        statement the normalizer chokes on just goes unprofiled."""
        from ..sql.fingerprint import fingerprint_statement
        try:
            return fingerprint_statement(stmt)
        except Exception:
            return None

    # log channel for queries over the `broker.slow.query.ms` threshold: one
    # machine-parseable JSON object per slow query (reference: the slow-query
    # "Processed requestId=..." WARN in BaseSingleStageBrokerRequestHandler)
    SLOW_QUERY_LOGGER = "pinot_tpu.broker.slow_query"

    def _slow_threshold_ms(self) -> Optional[float]:
        prop = self.catalog.get_property("clusterConfig/broker.slow.query.ms")
        try:
            return float(prop) if prop not in (None, "") else None
        except (TypeError, ValueError):
            return None

    def _trace_sample_rate(self) -> float:
        """`broker.trace.sample.rate` (clusterConfig): fraction of queries
        whose traces are retained in the /debug/traces ring. 0 (the default)
        disables head sampling; slow/errored queries still tail-retain."""
        prop = self.catalog.get_property(
            "clusterConfig/broker.trace.sample.rate")
        try:
            return float(prop) if prop not in (None, "") else 0.0
        except (TypeError, ValueError):
            return 0.0

    def _slo_latency_target_ms(self) -> Optional[float]:
        """`slo.latency.p99.ms` (clusterConfig): the per-query latency target
        behind the SLO layer — queries over it count into the per-table
        `numOverSlo` rollup that the controller's burn-rate check consumes."""
        prop = self.catalog.get_property("clusterConfig/slo.latency.p99.ms")
        try:
            return float(prop) if prop not in (None, "") else None
        except (TypeError, ValueError):
            return None

    @staticmethod
    def _memory_samples(elapsed_ms: float) -> List[Dict[str, object]]:
        """HBM residency counter samples for the Chrome-trace export,
        timestamped trace-relative (query completion) so the counter track
        lines up with the span timeline. In-proc clusters see the process
        ledger; an OS-process broker holds no device residency and reports
        zeros — the servers' /debug/memory is the authoritative view there."""
        from ..utils.memledger import get_ledger
        snap = get_ledger().snapshot()
        return [{"tsMs": round(elapsed_ms, 3),
                 "series": {"hbm_resident_bytes": snap["totalBytes"],
                            "hbm_transient_peak_bytes":
                                snap["transientPeakBytes"]}}]

    def _account_query(self, sql: str, result: ResultTable,
                       elapsed_ms: float, tr=None, table=None,
                       shape=None) -> None:
        """Per-query bookkeeping after a successful response: rollups for
        /debug, per-table resource attribution, workload-shape profiling,
        trace-ring retention, plus the slow-query log when over threshold
        (exactly one structured line per slow query)."""
        with self._obs_lock:
            self._query_rollup["numQueries"] += 1
            self._query_rollup["totalTimeMs"] += elapsed_ms
            self._query_rollup["maxTimeMs"] = max(
                self._query_rollup["maxTimeMs"], elapsed_ms)
        thr = self._slow_threshold_ms()
        slow = thr is not None and elapsed_ms > thr
        if table:
            self._table_account(table, elapsed_ms, result=result, slow=slow)
        if shape is not None:
            self.workload.observe(shape, elapsed_ms, result.stats)
        if tr is not None and (tr.sampled or slow):
            # head-sampled OR tail-retained (slow): land in the bounded ring
            # behind GET /debug/traces
            meta = dict(sql=sql, slow=slow, timeUsedMs=round(elapsed_ms, 3),
                        memory=self._memory_samples(elapsed_ms))
            if shape is not None:
                meta["workloadFingerprint"] = shape.fingerprint
            self.trace_ring.admit(tr, **meta)
        if not slow:
            return
        entry = {
            "sql": sql,
            "timeUsedMs": round(elapsed_ms, 3),
            "thresholdMs": thr,
            "brokerId": self.instance_id,
            "stats": {k: v for k, v in result.stats.items()
                      if isinstance(v, (int, float, bool, str))},
        }
        if shape is not None:
            # joinable against /debug/workload without re-parsing the SQL
            entry["workloadFingerprint"] = shape.fingerprint
        trace_rows = result.stats.get("traceInfo")
        if trace_rows:
            entry["traceSpans"] = trace_rows
        with self._obs_lock:
            self._query_rollup["numSlowQueries"] += 1
            self._recent_slow.append(entry)
        from ..utils.metrics import get_registry
        get_registry().counter("pinot_broker_slow_queries").inc()
        logging.getLogger(self.SLOW_QUERY_LOGGER).warning(
            json.dumps(entry, default=str))

    # cumulative per-table counters -> labeled gauge family. Gauges (set from
    # the rollup), not counters, so a dropped table's whole series removes
    # cleanly; the latency histogram is the one true distribution.
    _TABLE_GAUGES = {
        "numQueries": "pinot_table_queries",
        "numErrors": "pinot_table_errors",
        "numSlowQueries": "pinot_table_slow_queries",
        "numOverSlo": "pinot_table_over_slo",
        "totalTimeMs": "pinot_table_time_ms",
        "deviceExecMs": "pinot_table_device_exec_ms",
        "bytesFetched": "pinot_table_bytes_fetched",
        "rowsScanned": "pinot_table_rows_scanned",
        "queueWaitMs": "pinot_table_queue_wait_ms",
    }

    def _table_account(self, table: str, elapsed_ms: float, result=None,
                       slow: bool = False, error: bool = False) -> None:
        """Attribute one query's resources to its logical table: broker time,
        device exec, bytes fetched, rows scanned, queue wait, slow/error/SLO
        counts — the tenant-attribution panel cluster_top and the controller
        SLO check read."""
        from ..utils.metrics import get_registry
        reg = get_registry()
        stats = result.stats if result is not None else {}

        def _num(key):
            v = stats.get(key)
            return float(v) if isinstance(v, (int, float)) \
                and not isinstance(v, bool) else 0.0

        slo_target = self._slo_latency_target_ms()
        with self._obs_lock:
            roll = self._table_rollup.setdefault(table, {
                k: 0.0 for k in self._TABLE_GAUGES})
            roll["numQueries"] += 1
            roll["totalTimeMs"] += elapsed_ms
            roll["numErrors"] += 1 if error else 0
            roll["numSlowQueries"] += 1 if slow else 0
            if slo_target is not None and elapsed_ms > slo_target:
                roll["numOverSlo"] += 1
            roll["deviceExecMs"] += _num("deviceExecMs")
            roll["bytesFetched"] += _num("bytesFetched")
            roll["rowsScanned"] += _num("numDocsScanned")
            roll["queueWaitMs"] += _num("queueWaitMs")
            snapshot = dict(roll)
        labels = {"table": table}
        for key, gname in self._TABLE_GAUGES.items():
            reg.gauge(gname, labels).set(round(snapshot[key], 3))
        reg.histogram("pinot_table_latency_ms", labels).observe(elapsed_ms)
        self._maybe_sweep_dropped_tables()

    def _maybe_sweep_dropped_tables(self, force: bool = False) -> None:
        """Lazily reconcile the per-table rollup against the live catalog:
        series for dropped tables are removed from both the rollup and the
        registry (every 64 queries, plus on each /debug read)."""
        with self._obs_lock:
            self._table_sweep_countdown -= 1
            if not force and self._table_sweep_countdown > 0:
                return
            self._table_sweep_countdown = 64
            tracked = set(self._table_rollup)
        live = set()
        for name in list(self.catalog.table_configs):
            live.add(name)
            # rollups key on the LOGICAL table name; configs on name_TYPE
            for suffix in ("_OFFLINE", "_REALTIME"):
                if name.endswith(suffix):
                    live.add(name[: -len(suffix)])
        dead = tracked - live
        if not dead:
            return
        from ..utils.metrics import get_registry
        reg = get_registry()
        with self._obs_lock:
            for table in dead:
                self._table_rollup.pop(table, None)
        for table in dead:
            labels = {"table": table}
            for gname in self._TABLE_GAUGES.values():
                reg.remove(gname, labels)
            reg.remove("pinot_table_latency_ms", labels)

    def debug_stats(self) -> Dict:
        """Rollups for the HTTP /debug endpoint: lifetime query counters,
        per-table resource attribution, broker-scoped registry metrics, and
        the recent slow-query ring."""
        from ..utils.metrics import get_registry
        self._maybe_sweep_dropped_tables(force=True)
        reg = get_registry()
        snap = reg.snapshot()
        with self._obs_lock:
            rollup = dict(self._query_rollup)
            recent = list(self._recent_slow)
            tables = {t: dict(r) for t, r in self._table_rollup.items()}
        n = rollup["numQueries"]
        rollup["avgTimeMs"] = round(rollup["totalTimeMs"] / n, 3) if n else 0.0
        rollup["totalTimeMs"] = round(rollup["totalTimeMs"], 3)
        rollup["maxTimeMs"] = round(rollup["maxTimeMs"], 3)
        for t, r in tables.items():
            nq = r["numQueries"]
            r["avgTimeMs"] = round(r["totalTimeMs"] / nq, 3) if nq else 0.0
            r["p99LatencyMs"] = round(
                reg.histogram("pinot_table_latency_ms",
                              {"table": t}).percentile(0.99), 3)
            for k in list(r):
                if isinstance(r[k], float):
                    r[k] = round(r[k], 3)
        return {
            "instanceId": self.instance_id,
            "queryStats": rollup,
            "tableStats": tables,
            "slowQueryThresholdMs": self._slow_threshold_ms(),
            "recentSlowQueries": recent,
            "traceRing": {"retained": len(self.trace_ring),
                          "capacity": self.trace_ring.capacity,
                          "sampleRate": self._trace_sample_rate()},
            "workload": self.workload.summary(),
            "brokerMetrics": {k: v for k, v in sorted(snap.items())
                              if k.startswith("pinot_broker_")},
            "failureDetector": self.failure_detector.snapshot(),
            "admission": self.admission.snapshot(),
            "hedgedRequests": int(
                reg.counter("pinot_broker_hedged_requests").value),
            "gaugeHistories": get_registry().gauge_histories("pinot_broker"),
        }

    def _rewrite_subqueries(self, stmt):
        """`IN_SUBQUERY(expr, 'inner sql')` -> run the inner query through this
        broker, splice its serialized id-set in as `IN_ID_SET(expr, '...')`
        (reference: BaseBrokerRequestHandler.java:782 subquery recursion; the
        inner query is expected to produce one IDSET(...) value). Nested
        subqueries resolve naturally — each handle_query call rewrites its own
        statement first."""
        import dataclasses

        from ..sql.ast import Function, Literal

        def rw(e):
            if not isinstance(e, Function):
                return e
            if e.name in ("in_subquery", "in_partitioned_subquery"):
                from ..sql.ast import Subquery
                if len(e.args) == 2 and isinstance(e.args[1], Subquery):
                    # `x IN (SELECT ...)` AST form: the multistage planner
                    # lowers it to a SEMI join — not the id-set rewrite
                    return e
                if len(e.args) != 2 or not isinstance(e.args[1], Literal):
                    raise QueryValidationError(
                        f"IN_SUBQUERY(expr, 'sql') expected: {e!r}")
                sub = self.handle_query(str(e.args[1].value))
                if len(sub.rows) != 1 or len(sub.rows[0]) != 1 \
                        or not isinstance(sub.rows[0][0], str):
                    raise QueryValidationError(
                        "IN_SUBQUERY inner query must return exactly one serialized "
                        "id-set (use IDSET(col))")
                return Function("in_id_set", (rw(e.args[0]), Literal(sub.rows[0][0])))
            return Function(e.name, tuple(rw(a) for a in e.args))

        from ..sql.ast import walk
        if stmt.where is None or not any(
                isinstance(n, Function) and n.name in ("in_subquery",
                                                       "in_partitioned_subquery")
                for n in walk(stmt.where)):
            return stmt
        return dataclasses.replace(stmt, where=rw(stmt.where))

    def _handle_single(self, stmt, t0: float) -> ResultTable:
        from ..utils.trace import current_depth, current_trace, span
        with span("broker.compile"):
            stmt_ctx = compile_query(stmt)  # schema resolved below per physical table
        raw_table = stmt_ctx.table
        t_compile = time.perf_counter()

        physical = self._physical_tables(raw_table)
        if not physical:
            raise QueryValidationError(f"unknown table {raw_table!r}")
        disabled = [t for t in physical
                    if self.catalog.get_property(f"tableState/{t}") == "disabled"]
        if disabled:
            # reference: ChangeTableState disable — table stays loaded but
            # stops serving queries until re-enabled
            raise QueryValidationError(f"table {raw_table!r} is disabled")
        # per-table QPS quota, all-or-refund across hybrid halves (reference:
        # QueryQuotaManager)
        if not self.quota.try_acquire_all(physical):
            from ..query.scheduler import QueryRejectedError
            from ..utils.metrics import get_registry
            get_registry().counter("pinot_broker_queries_throttled").inc()
            raise QueryRejectedError(f"table {raw_table!r} exceeded its query quota")
        schema = self.catalog.schemas.get(self.catalog.table_configs[physical[0]].name)
        ctx = compile_query(stmt, schema)

        # deadline propagation: stamp an absolute wall-clock budget so every
        # downstream stage (server scheduler slot, device pipeline wait) can
        # clamp to the REMAINING time instead of restarting a full budget —
        # a faulted/slow server then fails fast rather than serializing the
        # whole stage timeout behind it
        if "deadlineEpochMs" not in ctx.options:
            t_ms = ctx.options.get("timeoutMs")
            budget_s = (float(t_ms) / 1000.0 if t_ms is not None
                        else self.stage_timeout_s)
            ctx.options["deadlineEpochMs"] = (time.time() + budget_s) * 1000.0

        if ctx.analyze:
            return self._handle_analyze(stmt, ctx, physical, t0)
        if ctx.explain:
            return self._handle_explain(ctx, physical)

        # adaptive admission: the shed-state machine plus the deadline-budget
        # check (placed after the deadline stamp so the budget is visible). A
        # shed refunds the QPS tokens taken above — a rejected query must not
        # burn its table's quota
        try:
            self.admission.admit(raw_table, ctx)
        except Exception:
            for t in physical:
                self.quota.refund(t)
            raise

        if self._should_distribute_groupby(ctx, physical):
            from ..multistage.shuffle import P2PUnavailable, coordinate_groupby
            try:
                result = coordinate_groupby(self, ctx, physical,
                                            self._num_partitions(stmt))
                result.stats["timeUsedMs"] = round(
                    (time.perf_counter() - t0) * 1000, 3)
                return result
            except P2PUnavailable:
                # in-proc handles or transiently-unhealthy workers: fall
                # through to the broker-merge scatter (correct, just not
                # distributed) — visibly, so operators can see the regression
                from ..utils.metrics import get_registry
                get_registry().counter("pinot_broker_p2p_fallbacks").inc()

        aggs = [make_agg(f) for f in ctx.aggregations]
        group_exprs = ([e for e, _ in ctx.select_items] if ctx.distinct
                       else list(ctx.group_by))

        partials: List[SegmentResult] = []
        # per-query telemetry record: server partials fold their wire stats in
        # as they arrive; an EXPLAIN ANALYZE wrapper may have installed one on
        # this thread already — keep accumulating into it in that case
        exec_stats = qstats.current_stats()
        if exec_stats is None:
            exec_stats = qstats.ExecutionStats()
        servers_queried = servers_failed = 0
        uncovered_segments: List[str] = []
        query_errors: List[Exception] = []
        error_segments: Set[str] = set()
        boundary = self._time_boundary(physical)
        tr = current_trace()

        def _traced(handle, server_id):
            # scatter-pool threads share the request's trace (activate is
            # per-thread), nested where the dispatch happens: under the scatter
            if tr is None:
                return handle
            depth = current_depth()

            def call(*args):
                with tr.activate(depth=depth), span(f"server:{server_id}"):
                    return handle(*args)
            return call

        with span("broker.scatter"):
            for table in physical:
                tf_expr = _boundary_expr(boundary, table)
                tf = to_sql(tf_expr) if tf_expr is not None else None
                unroutable: List[str] = []
                prune_counts: Dict[str, float] = {}
                routing = self.routing.route_query(table, ctx, extra_filter=tf_expr,
                                                   uncovered=unroutable,
                                                   prune_stats=prune_counts)
                _record_prune_stats(exec_stats, prune_counts)
                uncovered_segments.extend(f"{table}:{s}" for s in sorted(unroutable))
                missing: Dict[str, Set[str]] = {}  # segment -> servers that missed it
                units: List[_DispatchUnit] = []
                # one table routed to one server: its partial is the whole
                # answer (retries and hedges keep the plain context)
                sole_ctx = dataclasses.replace(
                    ctx, options=dict(ctx.options, **{SOLE_SERVER: True})) \
                    if len(physical) == 1 and len(routing) == 1 else ctx
                for server_id, segments in routing.items():
                    handle = self._servers.get(server_id)
                    if handle is None:
                        # routed to a server whose handle was unregistered between
                        # route_query and dispatch — its segments enter the retry
                        # round like any other miss, never silently dropped
                        for seg in segments:
                            missing.setdefault(seg, set()).add(server_id)
                        continue
                    fut = self._dispatch_partial(handle, server_id, _traced,
                                                 table, sole_ctx, segments, tf)
                    units.append(_DispatchUnit(server_id, list(segments), fut))
                q, f = self._gather_units(table, ctx, tf, _traced, units, partials,
                                          exec_stats, missing, query_errors,
                                          error_segments)
                servers_queried += q
                servers_failed += f
                if missing:
                    # a replica mid segment-transition (commit adoption, move) can
                    # briefly serve without a segment it was routed — ONE retry
                    # round on the other replicas keeps results complete instead
                    # of silently short (counts must never regress mid-commit)
                    retry_results, retry_failed = self._retry_missing(
                        table, ctx, missing, tf, _traced, exec_stats=exec_stats)
                    partials.extend(r for r, _ in retry_results)
                    for r, _ in retry_results:
                        exec_stats.merge(r.stats)
                    servers_queried += len(retry_results) + retry_failed
                    servers_failed += retry_failed
                    # coverage audit: a segment can stay unserved even after the
                    # retry round (no eligible candidate, retry target crashed, or
                    # the retry partial's own served list omits it) — surface it
                    # as a partial result instead of silently returning short
                    uncovered = _uncovered_after_retry(missing, retry_results)
                    if query_errors and error_segments & uncovered:
                        # a query-error server's segments failed on EVERY replica
                        # tried: the error is deterministic, not replica-local —
                        # propagate it instead of a misleading partial result
                        raise query_errors[0]
                    uncovered_segments.extend(
                        f"{table}:{s}" for s in sorted(uncovered))

        t_scatter = time.perf_counter()
        with span("broker.reduce"):
            merged = merge_segment_results(partials, aggs)
            if not partials:
                merged.kind = ("groups" if group_exprs else
                               "scalar" if aggs else "selection")
            result = reduce_to_result(ctx, merged, aggs, group_exprs)
        t_reduce = time.perf_counter()
        if uncovered_segments:
            from ..utils.metrics import get_registry as _reg
            _reg().counter("pinot_broker_segments_unavailable").inc(
                len(uncovered_segments))
            result.stats["segmentsUnavailable"] = uncovered_segments
        exec_stats.add_operator("COMBINE", rows=merged.num_docs_scanned,
                                ms=(t_scatter - t_compile) * 1000)
        exec_stats.add_operator("BROKER_REDUCE", rows=len(result.rows),
                                ms=(t_reduce - t_scatter) * 1000)
        result.stats.update(exec_stats.to_public_dict())
        result.stats.update({
            "numServersQueried": servers_queried,
            "numServersResponded": servers_queried - servers_failed,
            "partialResult": servers_failed > 0 or bool(uncovered_segments),
            # per-phase wall times (reference: BrokerQueryPhase REQUEST_COMPILATION /
            # QUERY_ROUTING+SCATTER / REDUCE)
            "phaseTimesMs": {
                "compile": round((t_compile - t0) * 1000, 3),
                "scatter": round((t_scatter - t_compile) * 1000, 3),
                "reduce": round((t_reduce - t_scatter) * 1000, 3),
            },
        })
        return result

    def stream_query(self, sql: str, stmt=None):
        """Streaming results: yields ("schema", columns) once, then
        ("rows", batch) per server partial as they arrive (reference: the
        gRPC streaming transport for selection-only queries, server.proto:42 /
        StreamingSelectionOnlyCombineOperator). Streamable = plain selection
        with no aggregation/group/order/offset/join; anything else falls back
        to one buffered batch of the normal path — same results, no streaming
        win."""
        from ..sql.parser import parse_query
        from ..utils.metrics import get_registry
        if stmt is None:
            stmt = parse_query(sql)
        stmt = self._rewrite_subqueries(stmt)
        probe = compile_query(stmt)
        streamable = (not stmt.joins and not probe.is_aggregation_query
                      and not probe.distinct and not probe.order_by
                      and not probe.offset and not probe.explain)
        if not streamable:
            result = self.handle_query(sql, stmt=stmt)  # already parsed/rewritten
            yield ("schema", result.columns)
            if result.rows:
                yield ("rows", result.rows)
            return

        physical = self._physical_tables(probe.table)
        if not physical:
            raise QueryValidationError(f"unknown table {probe.table!r}")
        # same admin controls as the buffered path: disable + quota must not
        # be bypassable through the streaming endpoint
        if any(self.catalog.get_property(f"tableState/{t}") == "disabled"
               for t in physical):
            raise QueryValidationError(f"table {probe.table!r} is disabled")
        if not self.quota.try_acquire_all(physical):
            from ..query.scheduler import QueryRejectedError
            get_registry().counter("pinot_broker_queries_throttled").inc()
            raise QueryRejectedError(
                f"table {probe.table!r} exceeded its query quota")
        try:
            # streaming exports are selection scans — exactly the expensive
            # class the SHEDDING state exists to shed first
            self.admission.admit(probe.table, probe)
        except Exception:
            for t in physical:
                self.quota.refund(t)
            raise
        get_registry().counter("pinot_broker_queries").inc()
        schema = self.catalog.schemas.get(
            self.catalog.table_configs[physical[0]].name)
        ctx = compile_query(stmt, schema)
        empty = reduce_to_result(ctx, SegmentResult("selection"), [], [])
        yield ("schema", empty.columns)
        remaining = ctx.limit if ctx.limit is not None else UNBOUNDED_LIMIT
        boundary = self._time_boundary(physical)
        for table in physical:
            if remaining <= 0:
                return
            tf_expr = _boundary_expr(boundary, table)
            tf = to_sql(tf_expr) if tf_expr is not None else None
            unroutable: List[str] = []
            routing = self.routing.route_query(table, ctx, extra_filter=tf_expr,
                                               uncovered=unroutable)
            if unroutable:
                raise RuntimeError(
                    f"streaming export incomplete: segments "
                    f"{sorted(unroutable)} have no healthy replica")
            for server_id, segments in routing.items():
                if remaining <= 0:
                    return
                handle = self._servers.get(server_id)
                partial = None
                missed: Set[str] = set(segments)
                query_error: Optional[Exception] = None
                if handle is not None:
                    try:
                        partial = handle(table, ctx, segments, tf)
                        missed = (set(segments) - set(partial.served)
                                  if partial.served is not None else set())
                    except Exception as e:
                        if _is_transport_failure(e):
                            self.routing.mark_server_unhealthy(server_id)
                            self.failure_detector.notify_unhealthy(server_id)
                        elif not _is_backpressure(e):
                            # same failover policy as the buffered path: the
                            # segments retry on another replica; only an error
                            # that survives the retry (deterministic) raises
                            query_error = e
                if missed:
                    # same completeness contract as the buffered path: retry
                    # unserved segments on another replica; an export that
                    # cannot be completed ERRORS instead of silently ending
                    retries, failed = self._retry_missing(
                        table, ctx, {s: {server_id} for s in missed}, tf,
                        lambda h, s: h)
                    uncovered = _uncovered_after_retry(
                        {s: set() for s in missed}, retries)
                    if failed or uncovered:
                        if query_error is not None:
                            raise query_error
                        raise RuntimeError(
                            f"streaming export incomplete: segments "
                            f"{sorted(uncovered)} unavailable on all replicas")
                    for r, _ in retries:
                        rows = reduce_to_result(ctx, r, [], []).rows[:remaining]
                        if rows:
                            remaining -= len(rows)
                            yield ("rows", rows)
                if partial is not None:
                    rows = reduce_to_result(ctx, partial, [], []).rows[:remaining]
                    if rows:
                        remaining -= len(rows)
                        yield ("rows", rows)

    def _dispatch_partial(self, handle, server_id: str, traced, table, ctx,
                          segments, tf) -> Future:
        """Dispatch one server partial, async-first: a mux-capable handle's
        `submit_async` returns a Future WITHOUT occupying a scatter-pool
        thread for the round trip, so the in-flight fan-out is bounded by
        the servers' flow-control windows instead of `self._pool`'s worker
        count — concurrent queries to one server share an exchange and feed
        the device pipeline bigger batches. Legacy handles (or a disabled /
        peer-unsupported mux, signalled by submit_async returning None) fall
        back to one pool thread per call; a synchronous dispatch failure
        becomes a failed Future so the gather loop's failure taxonomy
        (`_is_transport_failure` / `_is_backpressure`) sees it like any
        other."""
        submit = getattr(handle, "submit_async", None)
        if submit is not None:
            try:
                fut = submit(table, ctx, segments, tf,
                             span_name=f"server:{server_id}")
            except Exception as e:
                fut = Future()
                fut.set_exception(e)
                return fut
            if fut is not None:
                return fut
        call = traced(handle, server_id) if traced is not None else handle
        return self._pool.submit(call, table, ctx, segments, tf)

    #: hedge delay used before the dispatch-latency histogram has samples
    HEDGE_DEFAULT_DELAY_MS = 50.0

    def _hedge_params(self) -> Tuple[bool, float, int]:
        """(enabled, delay seconds, max hedges per query) from the
        `broker.hedge.*` clusterConfig knobs. delay.ms <= 0 (the default)
        derives the delay from the observed dispatch-latency p99 — a dispatch
        that has outlived p99 is a straggler worth duplicating."""
        if not _truthy(self.catalog.get_property(
                "clusterConfig/broker.hedge.enabled", False)):
            return False, 0.0, 0
        try:
            delay_ms = float(self.catalog.get_property(
                "clusterConfig/broker.hedge.delay.ms", 0) or 0)
        except (TypeError, ValueError):
            delay_ms = 0.0
        if delay_ms <= 0:
            from ..utils.metrics import get_registry
            p99 = get_registry().histogram(
                "pinot_broker_dispatch_latency_ms").percentile(0.99)
            delay_ms = p99 if p99 > 0 else self.HEDGE_DEFAULT_DELAY_MS
        try:
            budget = int(self.catalog.get_property(
                "clusterConfig/broker.hedge.max", 2))
        except (TypeError, ValueError):
            budget = 2
        return True, delay_ms / 1000.0, max(0, budget)

    #: how long a 429 without a Retry-After hint keeps a server out of the
    #: hedge/retry candidate set
    BACKPRESSURE_DEFAULT_S = 0.25
    #: ceiling on honored Retry-After hints (a misbehaving server must not be
    #: able to exempt itself from traffic indefinitely)
    BACKPRESSURE_MAX_S = 5.0

    def _note_backpressure(self, server_id: str,
                           hint_ms: Optional[float]) -> None:
        """Remember a 429's Retry-After: the server stays out of hedge and
        retry candidate sets until the hint expires."""
        hold_s = (min(hint_ms / 1000.0, self.BACKPRESSURE_MAX_S)
                  if hint_ms is not None and hint_ms > 0
                  else self.BACKPRESSURE_DEFAULT_S)
        self._backpressure_until[server_id] = time.monotonic() + hold_s
        emit_event("backpressure.hold", node=self.instance_id,
                   server=server_id, holdMs=round(hold_s * 1000.0, 3))

    def _backpressured_servers(self) -> Set[str]:
        now = time.monotonic()
        expired = [s for s, t in list(self._backpressure_until.items())
                   if t <= now]
        for s in expired:
            self._backpressure_until.pop(s, None)
        return {s for s, t in list(self._backpressure_until.items())
                if t > now}

    def _hedge_target(self, table: str, primary: str,
                      segments: Sequence[str]) -> Optional[str]:
        """An alternate healthy registered replica serving EVERY segment of
        the unit, or None (a unit spanning replica groups can't hedge as one
        dispatch — it stays on the retry-round path instead). Replicas in
        backpressure are excluded: a hedge against an already-shedding server
        only deepens its overload."""
        unhealthy = self.routing.unhealthy_servers()
        backpressured = self._backpressured_servers()
        candidates: Optional[Set[str]] = None
        for seg in segments:
            cands = {c for c in self.routing.segment_candidates(table, seg)
                     if c != primary and c in self._servers
                     and c not in unhealthy and c not in backpressured}
            candidates = cands if candidates is None else candidates & cands
            if not candidates:
                return None
        return min(candidates) if candidates else None

    def _gather_units(self, table: str, ctx, tf, traced,
                      units: List[_DispatchUnit],
                      partials: List[SegmentResult], exec_stats,
                      missing: Dict[str, Set[str]],
                      query_errors: List[Exception],
                      error_segments: Set[str]) -> Tuple[int, int]:
        """Gather one table's scatter round, hedging stragglers.

        Failure taxonomy matches the old as_completed loop exactly — transport
        failures leave routing via the failure detector, backpressure is the
        server working as designed, anything else is a remembered query error;
        every failed unit's segments enter the retry round. On top of that,
        when `broker.hedge.enabled` is on, a unit whose dispatch outlives the
        hedge delay (p99-based by default) is duplicated onto an alternate
        replica: first response wins, the loser is discarded unmerged, and a
        unit only counts failed when EVERY copy failed. Returns
        (units resolved, units failed)."""
        from ..utils.metrics import get_registry
        reg = get_registry()
        disp_hist = reg.histogram("pinot_broker_dispatch_latency_ms")
        hedge_on, hedge_delay_s, hedge_budget = self._hedge_params()
        if hedge_on and self.admission.overloaded():
            # degradation, not amplification: while the broker itself is
            # shedding, duplicating dispatches would double the very load
            # that pushed it past HEALTHY
            hedge_on = False
            reg.counter("pinot_broker_hedges_suppressed").inc()
            emit_event("hedge.suppressed", node=self.instance_id, table=table)
        hedges_sent = 0
        queried = failed = 0
        owner: Dict[Future, _DispatchUnit] = {u.primary: u for u in units}
        unresolved = set(units)
        deadline = time.monotonic() + self.stage_timeout_s

        def classify(u: _DispatchUnit, server_id: str,
                     exc: BaseException) -> None:
            if _is_transport_failure(exc):
                self.routing.mark_server_unhealthy(server_id)
                self.failure_detector.notify_unhealthy(server_id)
            elif _is_backpressure(exc):
                # the server is working as designed — remember its Retry-After
                # so hedges/retries back off instead of re-hitting the 429
                self._note_backpressure(server_id, _retry_after_ms(exc))
            else:
                query_errors.append(exc)          # type: ignore[arg-type]
                error_segments.update(u.segments)

        while unresolved:
            now = time.monotonic()
            if now >= deadline:
                break
            wait_set: List[Future] = []
            next_due: Optional[float] = None
            for u in unresolved:
                if u.primary not in u.failed:
                    wait_set.append(u.primary)
                if u.hedge is not None and u.hedge not in u.failed:
                    wait_set.append(u.hedge)
                if hedge_on and hedges_sent < hedge_budget \
                        and u.hedge is None and not u.hedge_exhausted \
                        and u.primary not in u.failed:
                    due = u.t0 + hedge_delay_s
                    next_due = due if next_due is None else min(next_due, due)
            timeout = deadline - now
            if next_due is not None:
                timeout = min(timeout, max(next_due - now, 0.0))
            done = futures_wait(wait_set, timeout=timeout,
                                return_when=FIRST_COMPLETED)[0] \
                if wait_set else set()
            for fut in done:
                u = owner[fut]
                if u not in unresolved:
                    continue   # the duplicate already won: drop unmerged
                is_hedge = fut is u.hedge
                server_id = u.hedge_server if is_hedge else u.server
                try:
                    # graftcheck: ignore[blocking-result-no-timeout] -- fut is
                    # from futures_wait's done set: already resolved, no block
                    partial = fut.result()
                except Exception as e:
                    u.failed[fut] = e
                    classify(u, server_id, e)
                    other = u.primary if is_hedge else u.hedge
                    if other is not None and other not in u.failed:
                        continue   # the other copy may still answer
                    unresolved.discard(u)
                    queried += 1
                    failed += 1
                    for seg in u.segments:
                        missing.setdefault(seg, set()).add(u.server)
                        if u.hedge_server is not None:
                            missing[seg].add(u.hedge_server)
                    continue
                unresolved.discard(u)
                queried += 1
                disp_hist.observe((time.monotonic() - u.t0) * 1000)
                partials.append(partial)
                exec_stats.merge(partial.stats)
                if partial.served is not None:
                    for seg in set(u.segments) - set(partial.served):
                        missing.setdefault(seg, set()).add(server_id)
            if hedge_on and hedges_sent < hedge_budget:
                now = time.monotonic()
                for u in list(unresolved):
                    if hedges_sent >= hedge_budget:
                        break
                    if u.hedge is not None or u.hedge_exhausted \
                            or u.primary in u.failed \
                            or now - u.t0 < hedge_delay_s:
                        continue
                    alt = self._hedge_target(table, u.server, u.segments)
                    if alt is None:
                        u.hedge_exhausted = True
                        continue
                    hf = self._dispatch_partial(self._servers[alt], alt,
                                                traced, table, ctx,
                                                u.segments, tf)
                    owner[hf] = u
                    u.hedge, u.hedge_server = hf, alt
                    hedges_sent += 1
                    exec_stats.add(qstats.HEDGED_REQUESTS)
                    reg.counter("pinot_broker_hedged_requests").inc()
        # stage deadline expired with units still outstanding: each straggler
        # is treated like a transport failure — marked unhealthy, its segments
        # sent into the retry round on another replica (never silently
        # dropped); sides that already failed got their taxonomy above
        for u in unresolved:
            queried += 1
            failed += 1
            for server_id, fut in ((u.server, u.primary),
                                   (u.hedge_server, u.hedge)):
                if fut is None or fut in u.failed:
                    continue
                self.routing.mark_server_unhealthy(server_id)
                self.failure_detector.notify_unhealthy(server_id)
            for seg in u.segments:
                missing.setdefault(seg, set()).add(u.server)
                if u.hedge_server is not None:
                    missing[seg].add(u.hedge_server)
        return queried, failed

    #: cap on how long a retry round waits out replicas' Retry-After hints
    RETRY_DEFER_CAP_S = 0.5

    def _retry_missing(self, table: str, ctx, missing: Dict[str, Set[str]],
                       tf: Optional[str], traced, exec_stats=None
                       ) -> Tuple[List[Tuple[SegmentResult, List[str]]], int]:
        """One retry round for segments a routed replica didn't serve: dispatch
        each to a different healthy replica, in parallel on the scatter pool
        with per-server trace spans like the first round. Returns
        ([(partial, segments dispatched to that target)], failed count) — a
        crashed retry target counts as a failed server (partial result) and
        leaves routing via the failure detector, like a first-round failure.

        strictReplicaGroup tables (including upsert, where that routing is
        auto-mandated) never retry per segment: serving one segment from a
        different replica than the rest of its partition reads valid-doc
        bitmaps that are not mutually consistent and can double-count or drop
        primary keys mid upsert propagation — the segments are returned
        uncovered and the caller surfaces them (partial result / export
        error) instead."""
        if self.routing.selector_for(table) == "strictreplicagroup":
            return [], 0
        backpressured = self._backpressured_servers()
        now = time.monotonic()
        by_server: Dict[str, List[str]] = {}
        defer_until = 0.0
        for seg, missed_on in missing.items():
            cands = [c for c in self.routing.segment_candidates(table, seg)
                     if c not in missed_on and c in self._servers
                     and c not in self.routing.unhealthy_servers()]
            ready = [c for c in cands if c not in backpressured]
            if ready:
                by_server.setdefault(ready[0], []).append(seg)
            elif cands:
                # every live replica is in backpressure: honor the soonest
                # Retry-After instead of retrying blind into another 429
                c = min(cands,
                        key=lambda s: self._backpressure_until.get(s, 0.0))
                defer_until = max(defer_until,
                                  self._backpressure_until.get(c, 0.0))
                by_server.setdefault(c, []).append(seg)
        if defer_until > now:
            delay = min(defer_until - now, self.RETRY_DEFER_CAP_S,
                        max(0.0, _deadline_remaining_s(ctx)))
            if delay > 0:
                time.sleep(delay)
                if exec_stats is not None:
                    exec_stats.add(qstats.ADMISSION_DEFER_MS,
                                   round(delay * 1000, 3))
        futures = {self._dispatch_partial(self._servers[s], s, traced, table,
                                          ctx, segs, tf): (s, segs)
                   for s, segs in by_server.items()}
        out: List[Tuple[SegmentResult, List[str]]] = []
        failed = 0
        pending = set(futures)
        try:
            for fut in as_completed(futures, timeout=self.stage_timeout_s):
                pending.discard(fut)
                server_id, segs = futures[fut]
                try:
                    out.append((fut.result(), segs))
                except Exception as e:
                    failed += 1
                    if _is_transport_failure(e):
                        self.routing.mark_server_unhealthy(server_id)
                        self.failure_detector.notify_unhealthy(server_id)
        except FutureTimeoutError:
            # retry deadline: stragglers' segments stay uncovered (the caller
            # surfaces a partial result) and the slow replicas leave routing
            for fut in pending:
                server_id, _segs = futures[fut]
                failed += 1
                self.routing.mark_server_unhealthy(server_id)
                self.failure_detector.notify_unhealthy(server_id)
        return out, failed

    def _handle_explain(self, ctx, physical: List[str]) -> ResultTable:
        """EXPLAIN PLAN: ask ONE server per physical table for its operator plan
        (reference: v2 explain gathers server plans; identical replicas make one
        representative server per table sufficient). Hybrid tables show BOTH
        halves, each under the same time-boundary predicate the real query
        applies, spliced under a single broker prefix."""
        import dataclasses

        from ..sql.ast import Function
        boundary = self._time_boundary(physical)
        merged: Optional[List[list]] = None
        for table in physical:
            tf_expr = _boundary_expr(boundary, table)
            ctx_t = ctx if tf_expr is None else dataclasses.replace(
                ctx, filter=tf_expr if ctx.filter is None
                else Function("and", (ctx.filter, tf_expr)))
            routing = self.routing.route_query(table, ctx_t, extra_filter=None)
            rows = None
            for server_id, segments in routing.items():
                handle = self._explain.get(server_id)
                if handle is None or not segments:
                    continue
                rows = [list(r) for r in handle(table, ctx_t, segments)]
                break
            if not rows or len(rows) < 2:
                continue
            if merged is None:
                merged = rows
            else:
                # splice this table's SEGMENT_PLAN subtrees (everything past the
                # 2-row BROKER_REDUCE/COMBINE prefix) under the merged COMBINE
                shift = len(merged) - 2
                for op, op_id, parent in rows[2:]:
                    merged.append([op, op_id + shift,
                                   1 if parent == 1 else parent + shift])
        if merged is None:
            # no segments anywhere: answer with the broker-level operators only
            from ..query.explain import explain_result
            return explain_result(ctx, [])
        return ResultTable(["Operator", "Operator_Id", "Parent_Id"], merged,
                           {"explain": True})

    def _handle_analyze(self, stmt, ctx, physical: List[str],
                        t0: float) -> ResultTable:
        """EXPLAIN ANALYZE: run the real query through the normal scatter path
        with a telemetry record installed on this thread, then annotate the
        distributed EXPLAIN plan with the per-operator rows/ms it collected.
        The query genuinely executes (and counts against quota) — the response
        is the annotated plan, with the full stats record riding alongside."""
        import dataclasses

        from ..query.explain import ANALYZE_COLUMNS, annotate_plan_rows
        run_stmt = dataclasses.replace(stmt, explain=False, analyze=False)
        with qstats.collect_stats() as st:
            inner = self._handle_single(run_stmt, t0)
        total_ms = (time.perf_counter() - t0) * 1000
        plan = self._handle_explain(ctx, physical)
        rows = annotate_plan_rows(plan.rows, st, len(inner.rows), total_ms)
        prune_row = _broker_prune_row(st, parent_id=0, next_id=len(rows))
        if prune_row is not None:
            rows.append(prune_row)
        res = ResultTable(list(ANALYZE_COLUMNS), rows, dict(inner.stats))
        res.stats.update(st.to_public_dict())
        res.stats["explain"] = True
        res.stats["analyze"] = True
        return res

    def _explain_multistage(self, stmt) -> ResultTable:
        """EXPLAIN for a JOIN query: describe the stage plan WITHOUT executing
        (reference: v2 EXPLAIN prints the logical stage tree)."""
        from ..multistage.planner import choose_join_strategy, plan_multistage
        from ..multistage.shuffle import _broadcast_max_bytes
        from ..sql.ast import to_sql
        plan = plan_multistage(stmt, lambda t: (
            self.catalog.schema_for_table(self._physical_tables(t)[0])
            if self._physical_tables(t) else None))

        def est_bytes(alias: str) -> int:
            scan = plan.scans[alias]
            docs = sum(int(getattr(m, "num_docs", 0))
                       for t in self._physical_tables(scan.table)
                       for m in self.catalog.segments.get(t, {}).values())
            return docs * max(1, len(scan.columns)) * 8

        bmax = _broadcast_max_bytes(self)
        rows: List[list] = [["MULTISTAGE_REDUCE", 0, -1]]
        parent = 0
        for j in reversed(plan.joins):
            keys = ", ".join(f"{l}={r}" for l, r in
                             zip(j.left_keys, j.right_keys))
            strategy = choose_join_strategy(
                j.join_type, est_bytes(j.right_alias), bmax)
            rows.append([f"HASH_JOIN(type:{j.join_type}; keys:[{keys}]; "
                         f"strategy:{strategy})", len(rows), parent])
            parent = len(rows) - 1
        for alias in [plan.base_alias] + [j.right_alias for j in plan.joins]:
            scan = plan.scans[alias]
            label = f"TABLE_SCAN(table:{scan.table}; alias:{alias}"
            if scan.filter is not None:
                label += f"; pushdownFilter:{to_sql(scan.filter)}"
            rows.append([label + ")", len(rows), parent])
        return ResultTable(["Operator", "Operator_Id", "Parent_Id"], rows,
                           {"explain": True})

    # -- peer-to-peer mailbox shuffle support -------------------------------

    def _num_partitions(self, stmt) -> int:
        from ..multistage.runtime import DEFAULT_PARTITIONS
        num_partitions = DEFAULT_PARTITIONS
        for key, v in (stmt.options or {}).items():
            if key.lower() in ("numpartitions", "stageparallelism"):
                try:
                    num_partitions = max(1, int(v))
                except (TypeError, ValueError):
                    raise QueryValidationError(
                        f"OPTION({key}=...) must be an integer, got {v!r}"
                    ) from None
        return num_partitions

    def _stage_workers(self, p: int) -> List[Tuple[str, str]]:
        """Exactly p (server_id, url) worker slots, round-robin over healthy
        HTTP-reachable servers (reference: the v2 dispatcher assigning stage
        workers from the live server list)."""
        from ..multistage.shuffle import P2PUnavailable
        unhealthy = self.routing.unhealthy_servers()
        with self._lock:
            cands = sorted((sid, u) for sid, u in self._urls.items()
                           if sid in self._servers and sid not in unhealthy)
        if not cands:
            raise P2PUnavailable("no HTTP-reachable stage workers")
        return [cands[i % len(cands)] for i in range(p)]

    def _route_leaf_table(self, table: str, ctx, boundary, routes: list
                          ) -> None:
        """Shared per-physical-table leaf routing: coverage check, HTTP-
        endpoint check, LeafRoute build. Appends to `routes`."""
        from ..multistage.shuffle import LeafRoute, P2PUnavailable
        tf_expr = _boundary_expr(boundary, table)
        tf = to_sql(tf_expr) if tf_expr is not None else None
        unroutable: List[str] = []
        routing = self.routing.route_query(table, ctx, extra_filter=tf_expr,
                                           uncovered=unroutable)
        if unroutable:
            raise RuntimeError(
                f"distributed scan incomplete: segments "
                f"{sorted(unroutable)} have no healthy replica")
        for server_id, segments in routing.items():
            url = self._urls.get(server_id)
            if url is None:
                raise P2PUnavailable(
                    f"server {server_id} has no HTTP endpoint")
            if segments:
                routes.append(LeafRoute(server_id, url, table,
                                        list(segments), tf))

    def _leaf_routes(self, raw_table: str, columns, filt):
        """Leaf dispatch units for a multistage join scan. Raises
        P2PUnavailable (caller falls back to the funnel path) when a routed
        server has no HTTP endpoint. Quota is NOT acquired here — the
        coordinator acquires it once after EVERY alias routes, so a fallback
        never double-charges a table's QPS budget."""
        from ..sql.ast import Identifier
        physical = self._physical_tables(raw_table)
        if not physical:
            raise QueryValidationError(f"unknown table {raw_table!r}")
        boundary = self._time_boundary(physical)
        routes: list = []
        for table in physical:
            ctx = QueryContext(
                table=table,
                select_items=[(Identifier(c), c) for c in columns],
                filter=filt, group_by=[], aggregations=[], having=None,
                order_by=[], limit=UNBOUNDED_LIMIT, offset=0, distinct=False)
            self._route_leaf_table(table, ctx, boundary, routes)
        return routes

    def _acquire_scan_quota(self, raw_tables) -> None:
        """One QPS-quota acquisition per logical table (same accounting as the
        funnel path's per-scan acquisition)."""
        from ..query.scheduler import QueryRejectedError
        for raw in raw_tables:
            if not self.quota.try_acquire_all(self._physical_tables(raw)):
                raise QueryRejectedError(
                    f"table {raw!r} exceeded its query quota")

    def _leaf_routes_groupby(self, ctx, physical: List[str]):
        """Leaf dispatch units for a distributed single-table GROUP BY."""
        boundary = self._time_boundary(physical)
        routes: list = []
        for table in physical:
            self._route_leaf_table(table, ctx, boundary, routes)
        return routes

    def _post_leaf_task(self, url: str, path: str, task) -> Dict:
        from .http_service import http_call
        from .wire import decode_value, encode_value
        resp = http_call("POST", f"{url}/{path}", encode_value(task),
                         timeout=self.stage_timeout_s,
                         content_type="application/octet-stream")
        return decode_value(resp)

    def _should_distribute_groupby(self, ctx, physical: List[str]) -> bool:
        """Route a single-table aggregation through the partitioned mailbox
        exchange (reference: PinotAggregateExchangeNodeInsertRule deciding to
        insert an agg exchange). Triggers: an explicit
        OPTION(useMultistageEngine/distributedGroupBy=true), or the cluster
        config `broker.distributedGroupByDocThreshold` when the routed doc
        count (a cheap proxy for key cardinality) exceeds it."""
        if ctx.explain or ctx.gapfill is not None:
            return False
        group_exprs = ctx.group_by or (
            [e for e, _ in ctx.select_items] if ctx.distinct else [])
        if not group_exprs:
            return False
        opt = {str(k).lower(): v for k, v in (ctx.options or {}).items()}
        if "distributedgroupby" in opt:
            return _truthy(opt["distributedgroupby"])
        if _truthy(opt.get("usemultistageengine")):
            return True
        thr = self.catalog.get_property(
            "clusterConfig/broker.distributedGroupByDocThreshold")
        if thr:
            docs = sum(m.num_docs for t in physical
                       for m in self.catalog.segments.get(t, {}).values())
            return docs > int(thr)
        return False

    def _data_plane_cap(self) -> Optional[int]:
        cap = self.max_data_plane_bytes
        if cap is None:
            prop = self.catalog.get_property(
                "clusterConfig/broker.maxDataPlaneBytes")
            cap = int(prop) if prop else None
        return cap

    def _handle_multistage(self, stmt) -> ResultTable:
        """Join query: peer-to-peer mailbox shuffle when every routed server
        is HTTP-reachable (inter-stage data streams server->server and the
        broker receives only final-stage partials); otherwise the in-proc
        multistage engine over a scatter-based leaf-scan provider (the legacy
        broker-funnel path, subject to the data-plane memory cap)."""
        from ..multistage import execute_multistage
        from ..sql.ast import Identifier

        # cluster knob `server.join.device.enabled`: operators can force the
        # join build/probe onto the host path fleet-wide (e.g. while a device
        # regression is being chased) without restarting servers
        dev = self.catalog.get_property(
            "clusterConfig/server.join.device.enabled")
        if dev is not None:
            from ..multistage.runtime import configure_device_join
            configure_device_join(enabled=str(dev).strip().lower()
                                  not in ("false", "0", "no", "off"))

        opt = {str(k).lower(): v for k, v in (stmt.options or {}).items()}
        use_mailbox = ("usemailboxshuffle" not in opt
                       or _truthy(opt["usemailboxshuffle"]))
        if use_mailbox:
            from ..multistage.shuffle import P2PUnavailable, coordinate_join
            try:
                return coordinate_join(self, stmt, self._num_partitions(stmt))
            except P2PUnavailable:
                # in-proc handles (tests) or mixed cluster: funnel path
                from ..utils.metrics import get_registry
                get_registry().counter("pinot_broker_p2p_fallbacks").inc()

        def schema_for(raw_table: str):
            phys = self._physical_tables(raw_table)
            return self.catalog.schema_for_table(phys[0]) if phys else None

        def stage_runner():
            """Round-robin dispatch of join(+partial-agg) partitions to
            HEALTHY server workers (the reference's intermediate-stage
            workers); local fallback when no worker is wired or a dispatch
            fails mid-query."""
            import itertools

            from ..multistage.runtime import run_join_stage
            from ..utils.metrics import get_registry
            unhealthy = self.routing.unhealthy_servers()
            with self._lock:
                workers = [(sid, h) for sid, h in self._stage.items()
                           if sid not in unhealthy]
            if not workers:
                return None
            rr = itertools.count()
            lock = threading.Lock()

            def run(spec, lp, rp, agg=None):
                with lock:
                    pool = list(workers)
                if not pool:
                    return run_join_stage(spec, lp, rp, agg)
                sid, h = pool[next(rr) % len(pool)]
                try:
                    return h(spec, lp, rp, agg)
                except Exception as e:
                    # degrade to broker-local execution, but VISIBLY: a
                    # transport-failed worker leaves routing until its probe
                    # passes, the meter shows the regression, and THIS query
                    # stops sending further partitions into the dead worker's
                    # timeout. A query error re-raises from the local run.
                    get_registry().counter(
                        "pinot_broker_stage_dispatch_failures").inc()
                    if _is_transport_failure(e):
                        self.routing.mark_server_unhealthy(sid)
                        self.failure_detector.notify_unhealthy(sid)
                        with lock:
                            workers[:] = [(s, hh) for s, hh in workers
                                          if s != sid]
                    return run_join_stage(spec, lp, rp, agg)
            return run

        # data-plane accounting for THIS query: the funnel path materializes
        # every leaf row in broker memory, so meter it and enforce the cap
        # (the mailbox path above never reaches this closure)
        moved = {"bytes": 0}
        cap = self._data_plane_cap()

        def account(nbytes: int) -> None:
            from ..utils.metrics import get_registry
            moved["bytes"] += nbytes
            get_registry().counter("pinot_broker_data_plane_bytes").inc(nbytes)
            if cap is not None and moved["bytes"] > cap:
                raise RuntimeError(
                    f"broker data-plane memory cap exceeded "
                    f"({moved['bytes']} > {cap} bytes buffered at the broker); "
                    f"run servers with HTTP endpoints so the mailbox shuffle "
                    f"streams inter-stage data server-to-server")

        def scan(raw_table: str, columns, filt):
            from ..sql.ast import _sql_ident, to_sql
            if not self.quota.try_acquire_all(self._physical_tables(raw_table)):
                from ..query.scheduler import QueryRejectedError
                raise QueryRejectedError(
                    f"table {raw_table!r} exceeded its query quota")
            schema = schema_for(raw_table)
            rows: List[tuple] = []
            # synthesized SQL lets remote (HTTP) server handles recompile the leaf;
            # identifiers are quoted as needed (keywords, special chars)
            leaf_sql = (f"SELECT {', '.join(_sql_ident(c) for c in columns)} "
                        f"FROM {_sql_ident(raw_table)}")
            if filt is not None:
                leaf_sql += f" WHERE {to_sql(filt)}"
            leaf_sql += f" LIMIT {UNBOUNDED_LIMIT}"
            physical = self._physical_tables(raw_table)
            boundary = self._time_boundary(physical)
            for table in physical:
                ctx = QueryContext(
                    table=table,
                    select_items=[(Identifier(c), c) for c in columns],
                    filter=filt, group_by=[], aggregations=[], having=None,
                    order_by=[], limit=UNBOUNDED_LIMIT, offset=0, distinct=False,
                    sql=leaf_sql)
                tf_expr = _boundary_expr(boundary, table)
                tf = to_sql(tf_expr) if tf_expr is not None else None
                routing = self.routing.route_query(table, ctx, extra_filter=tf_expr)
                futures = {}
                for server_id, segments in routing.items():
                    handle = self._servers.get(server_id)
                    if handle is None:
                        continue
                    futures[self._dispatch_partial(
                        handle, server_id, None, table, ctx, segments,
                        tf)] = server_id
                try:
                    for fut in as_completed(futures,
                                            timeout=self.stage_timeout_s):
                        server_id = futures[fut]
                        try:
                            partial = fut.result()
                            account(len(partial.rows) * max(1, len(columns))
                                    * 16)
                            rows.extend(partial.rows)
                        except Exception as e:
                            if _is_transport_failure(e):
                                self.routing.mark_server_unhealthy(server_id)
                                self.failure_detector.notify_unhealthy(
                                    server_id)
                            raise
                except FutureTimeoutError:
                    # a leaf scan cannot be partial — mark the stragglers and
                    # surface the timeout to the multistage caller
                    for f, server_id in futures.items():
                        if not f.done():
                            self.routing.mark_server_unhealthy(server_id)
                            self.failure_detector.notify_unhealthy(server_id)
                    raise
            import numpy as np
            out = {}
            for j, c in enumerate(columns):
                vals = [r[j] for r in rows]
                dt = schema.field_spec(c).data_type
                out[c] = (np.asarray(vals, dtype=dt.numpy_dtype) if dt.is_numeric
                          else np.asarray(vals, dtype=object))
            return out

        # shuffle width is per-query tunable (reference: the v2 engine's
        # stage parallelism query options)
        from ..multistage.shuffle import _broadcast_max_bytes
        return execute_multistage(stmt, scan, schema_for,
                                  num_partitions=self._num_partitions(stmt),
                                  stage_runner=stage_runner(),
                                  broadcast_max_bytes=_broadcast_max_bytes(
                                      self))

    def _physical_tables(self, raw_table: str) -> List[str]:
        """Resolve a logical name to physical tables; hybrid tables hit both OFFLINE
        and REALTIME halves, split at the time boundary (`_time_boundary`)."""
        out = []
        for t in (f"{raw_table}_{TableType.OFFLINE.value}",
                  f"{raw_table}_{TableType.REALTIME.value}"):
            if t in self.catalog.table_configs:
                out.append(t)
        if raw_table in self.catalog.table_configs:
            out.append(raw_table)
        return out

    def _time_boundary(self, physical: List[str]):
        """Hybrid split point (reference: TimeBoundaryManager): OFFLINE answers
        `time <= boundary`, REALTIME answers `time > boundary`, where boundary is the
        max offline end time — data copied realtime->offline is then never counted
        twice while the realtime copies await retention."""
        offline = [t for t in physical if t.endswith(f"_{TableType.OFFLINE.value}")]
        if len(physical) < 2 or not offline:
            return None
        cfg = self.catalog.table_configs.get(offline[0])
        if cfg is None or not cfg.time_column:
            return None
        # only segments that are actually SERVABLE move the boundary: metadata lands
        # before any server loads the segment, and advancing on metadata alone would
        # transiently hide that window's realtime rows (reference:
        # TimeBoundaryManager updates on external-view changes for the same reason)
        ev = self.catalog.external_view.get(offline[0], {})
        from .catalog import ONLINE
        ends = [m.end_time_ms
                for name, m in self.catalog.segments.get(offline[0], {}).items()
                if m.end_time_ms is not None
                and any(st == ONLINE for st in ev.get(name, {}).values())]
        if not ends:
            return None
        return (cfg.time_column, max(ends))


def _record_prune_stats(exec_stats, prune_counts: Dict[str, float]) -> None:
    """Fold the routing pruner's per-kind rejection counts into the query's
    ExecutionStats: the per-kind breakdown, the numSegmentsPruned total, and
    the pruned segments' doc count as scanRowsAvoided."""
    if not prune_counts:
        return
    from .routing import PRUNE_ROWS_AVOIDED, PRUNER_KINDS
    total = 0
    for kind in PRUNER_KINDS:
        n = int(prune_counts.get(kind, 0))
        if n:
            exec_stats.add(qstats.PRUNED_BY_KIND[kind], n)
            total += n
    if total:
        exec_stats.add(qstats.NUM_SEGMENTS_PRUNED, total)
    rows = int(prune_counts.get(PRUNE_ROWS_AVOIDED, 0))
    if rows:
        exec_stats.add(qstats.SCAN_ROWS_AVOIDED, rows)


def _broker_prune_row(st, parent_id: int, next_id: int):
    """EXPLAIN ANALYZE row summarising broker-side metadata pruning: one
    BROKER_PRUNE(kind:N, ...) operator under the root whose Rows column is the
    total number of segments the router rejected before fan-out. Returns None
    when routing pruned nothing (the common unfiltered case)."""
    pub = st.to_public_dict()
    parts = []
    total = 0
    for kind, key in qstats.PRUNED_BY_KIND.items():
        n = int(pub.get(key, 0))
        if n:
            parts.append(f"{kind}:{n}")
            total += n
    if not total:
        return None
    return [f"BROKER_PRUNE({', '.join(parts)})", next_id, parent_id,
            total, None]


def _boundary_expr(boundary, table: str):
    """The boundary as a predicate AST — the single source of truth: routing prunes
    with the AST, servers get `to_sql(expr)` of the same node."""
    if boundary is None:
        return None
    col, b = boundary
    from ..sql.ast import Function, Identifier, Literal
    if table.endswith(f"_{TableType.OFFLINE.value}"):
        return Function("lte", (Identifier(col), Literal(b)))
    if table.endswith(f"_{TableType.REALTIME.value}"):
        return Function("gt", (Identifier(col), Literal(b)))
    return None


def _uncovered_after_retry(missing, retry_results) -> Set[str]:
    """Segments still unserved after the retry round. An explicit served list
    is positive evidence; a served-less partial (older peer) is assumed to
    have covered exactly the segments dispatched to IT — never forgiveness
    for segments sent elsewhere."""
    uncovered = set(missing)
    for r, segs in retry_results:
        uncovered -= (set(segs) if r.served is None else set(r.served))
    return uncovered


def _truthy(v) -> bool:
    return str(v).lower() in ("true", "1") if v is not None else False


def _is_backpressure(e: BaseException) -> bool:
    from ..query.scheduler import QueryRejectedError, QueryTimeoutError
    if isinstance(e, (QueryRejectedError, QueryTimeoutError)):
        return True
    from .http_service import HttpError
    return isinstance(e, HttpError) and getattr(e, "status", None) in (408, 429)


def _retry_after_ms(e: BaseException) -> Optional[float]:
    """Retry-After hint carried by a backpressure error: the attribute set by
    the scheduler / mux decoder when present, else parsed out of a legacy
    HttpError message (whose text is the raw 429 JSON body)."""
    v = getattr(e, "retry_after_ms", None)
    if v is not None:
        try:
            return float(v)
        except (TypeError, ValueError):
            return None
    s = str(e)
    i = s.find("{")
    if i >= 0:
        try:
            v = json.loads(s[i:]).get("retryAfterMs")
            return float(v) if v is not None else None
        except (ValueError, TypeError):
            return None
    return None


def _deadline_remaining_s(ctx) -> float:
    """Seconds left on the query's absolute deadline (inf when unstamped)."""
    d = (ctx.options or {}).get("deadlineEpochMs")
    if d is None:
        return float("inf")
    try:
        return float(d) / 1000.0 - time.time()
    except (TypeError, ValueError):
        return float("inf")


def _is_transport_failure(e: BaseException) -> bool:
    """Server unreachable or crashed (take it out of routing) vs a QUERY error
    the server computed and reported (the server is healthy — propagate the
    error to the caller). An HttpError is a response FROM a live server, so a
    handler exception (500) is a query error, never grounds for removal:
    removing healthy servers on a bad query would let one malformed request
    silently empty the routing table and turn every later query into 0 rows."""
    return isinstance(e, (ConnectionError, TimeoutError, OSError))
