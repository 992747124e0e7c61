"""Device-backed serving: the TPU lives INSIDE the server role.

In the reference, the engine is embedded in the serving process:
`ServerInstance` owns the `QueryExecutor`/`QueryScheduler` and the Netty/gRPC
query endpoints over the same segment buffers
(`pinot-server/src/main/java/org/apache/pinot/server/starter/ServerInstance.java:55,120-186`),
and `BaseServerStarter` gates query serving on data readiness
(`BaseServerStarter.java:467-560`). The TPU analog: a `ServerNode` configured
with a `DeviceQueryPipeline` answers broker-routed queries through the
`MeshQueryExecutor` over HBM-resident `SegmentSetBlock`s — segments are
device_put once at first touch with their mesh sharding and stay scan-ready,
the data-readiness analog of the reference's mmap-resident buffers.

THE PIPELINE IS THE SCHEDULER. One dispatcher thread owns the device; HTTP
handler threads submit (ctx, segments) items and block on futures. Every
query of a drain is PREPARED (plan + build inputs, no launch) before its
batch launches: as it is taken, while the batch before is still being
fetched (the device is busy then, so the host work costs it nothing), else
when the drain closes. The prepared work is grouped before touching the
device:

  * items with equal `dedupe_key` are byte-identical dispatches — they share
    ONE kernel launch and ONE fetched result;
  * items with equal `stack_key` (same `KernelSpec.signature()` executable
    over the same segment block, differing only in runtime scalars) stack
    into ONE batched kernel launch instead of N sequential dispatches;
  * everything dispatched in a drain is fetched with ONE host sync, so under
    concurrency the host round trip amortizes across the batch
    (reference: `QueryScheduler.java:56` bounds per-server concurrency — here
    batching is what concurrency buys, because the device serializes dispatches
    anyway).

Queries whose plan cannot ride the device (host-only functions, doc-set
divergence, upsert masks, selections without a device-eligible ORDER BY)
resolve to the DEVICE_FALLBACK sentinel and the caller runs the per-segment
host path.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from concurrent.futures import CancelledError, Future, InvalidStateError
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Dict, List, Optional, Sequence

from ..query import stats as qstats
from ..utils.faults import FaultInjected, fault_point
from ..utils.metrics import get_registry
from ..utils.trace import stage


log = logging.getLogger(__name__)


class _Sentinel:
    def __repr__(self):  # pragma: no cover - debug only
        return "<DEVICE_FALLBACK>"


#: resolved value when the query must take the host path instead
DEVICE_FALLBACK = _Sentinel()

#: pipeline stages timed per drain (ms); exported under
#: pinot_server_device_pipeline_<stage>_ms via /metrics
_STAGES = ("queue_wait", "dispatch", "prepare", "launch", "handoff", "fetch",
           "decode")

#: what a launch records from the static shapes its program was built with,
#: also summed over the launches in `stats()`: on more than one device, what
#: crosses the chips; past 2^24 rows a device, a matmul GROUP BY slab by slab;
#: an aggregate's INT arithmetic widened where it would leave int32; a GROUP
#: BY of a handful of key cells as the masked reduce; one past the dense key
#: space from its sorted groups, and its ORDER BY ... LIMIT cut on the device;
#: over a resident set, the slots routed, held and read, and the id space
_SHAPE_KEYS = (qstats.MESH_LAUNCHES, qstats.SCATTER_LAUNCHES,
               qstats.COLLECTIVE_BYTES, qstats.SLABBED_LAUNCHES,
               qstats.WIDENED_AGG_LAUNCHES, qstats.MASKED_GROUPBY_LAUNCHES,
               qstats.SPARSE_GROUPBY_LAUNCHES, qstats.DEVICE_TRIMMED_LAUNCHES,
               qstats.DENSE_TRIMMED_LAUNCHES, qstats.SORTED_MINMAX_LAUNCHES,
               qstats.ROUTED_SLOTS, qstats.RESIDENT_SLOTS,
               qstats.SCANNED_SLOTS, qstats.MERGED_LAUNCHES)

#: what a prepare that had to stage records: set blocks built and the bytes
#: put into them, folded into the query that staged and summed in `stats()`
_STAGE_KEYS = (qstats.SET_BLOCKS_STAGED, qstats.SET_BLOCK_BYTES)

#: which decode branch and which sort a sort-regime GROUP BY launch ran, and which rows a matmul rung contracted: known
#: once its outputs are fetched (`qstats.decode_branch`), summed over the launches in `stats()`; the decode hook puts the same key on each answer's partial
_DECODE_KEYS = tuple(k for keys in qstats.DECODE_FLAGS.values() for k in keys)

#: what the kernel cache, the first-call fence, the executor's launch
#: accounting and the prepare's two halves record on the dispatcher thread,
#: folded from a scratch record into the items a prepare or launch answers
_LAUNCH_KEYS = (qstats.COMPILE_MS, qstats.COMPILE_CACHE_MISSES,
                qstats.COMPILE_CACHE_HITS, qstats.DEVICE_LAUNCHES,
                qstats.GATHER_FREE_LAUNCHES, qstats.DEVICE_PLAN_MS,
                qstats.DEVICE_INPUTS_MS) + _SHAPE_KEYS + _STAGE_KEYS

#: what the fetcher puts on the dispatcher's queue when a fetch ends: a
#: drain holding its batch open for that fetch wakes and closes at once
_FETCH_DONE = object()

#: how long the gather waits on an empty queue before it looks again at the
#: fetch and the burst window (a fetch's end does not wait for it)
_GATHER_POLL_S = 0.005

#: the pipeline's per-query phases in the order a query passes them: the
#: item.stats key of each and the request-Trace span `execute_partial` rebuilds
_PHASES = ((qstats.QUEUE_WAIT_MS, "pipeline:queue_wait"),
           (qstats.DEVICE_PREPARE_MS, "pipeline:prepare"),
           (qstats.DEVICE_LAUNCH_MS, "pipeline:launch"),
           (qstats.DEVICE_HANDOFF_MS, "pipeline:handoff"),
           (qstats.DEVICE_FETCH_MS, "pipeline:fetch"),
           (qstats.DEVICE_DECODE_MS, "pipeline:decode"))


def _resolve(future: Future, value, exc: Optional[BaseException] = None) -> None:
    """set_result/set_exception tolerant of a caller that already timed out
    and CANCELLED the future (racing a cancel with resolution is inherent to
    the timeout path — losing the race must not kill the pipeline thread)."""
    if future.done():
        return
    try:
        if exc is not None:
            future.set_exception(exc)
        else:
            future.set_result(value)
    except (InvalidStateError, CancelledError):
        pass


def _fold(stats: dict, recorded: dict) -> None:
    """Add what a scratch record caught on the dispatcher thread to an item."""
    for k in _LAUNCH_KEYS:
        v = recorded.get(k)
        if v:
            stats[k] = round(stats.get(k, 0) + v, 3)


def _items(launches):
    return (item for _, _, groups in launches for group in groups
            for item, _ in group)


class _Item:
    __slots__ = ("ctx", "segments", "resident", "future", "t_enqueue",
                 "t_resolved", "stats", "trace_id", "prepared", "ahead")

    def __init__(self, ctx, segments, trace_id: str = "", resident=None):
        self.ctx = ctx
        self.segments = segments
        self.resident = resident
        self.trace_id = trace_id
        self.future: Future = Future()
        self.t_enqueue = time.perf_counter()
        # when the fetcher resolved the future: the handler thread's wake-up
        # is measured from here, on its own side
        self.t_resolved = 0.0
        # per-item launch attribution (queue wait, dedupe/stack flags): the
        # pipeline threads serve MANY queries per drain, so per-query stats
        # can't ride thread-locals — they attach to the decoded partial
        self.stats: dict = {}
        # its PreparedDispatch where the gather prepared it while a fetch was
        # in flight, and whether that prepare ended before the fetch did
        self.prepared = None
        self.ahead = False


class DeviceQueryPipeline:
    """Single-owner device dispatch loop with whole-queue batched fetches."""

    def __init__(self, mesh_exec=None, max_batch: int = 64,
                 submit_timeout_s: float = 120.0, max_inflight: int = 4,
                 stack: bool = True, start: bool = True,
                 burst_window_s: float = 0.0):
        if mesh_exec is None:
            from ..parallel.combine import MeshQueryExecutor
            mesh_exec = MeshQueryExecutor()
        self.mesh_exec = mesh_exec
        self._prepared_api = hasattr(mesh_exec, "prepare_partial")
        # chips one launch enqueues on and one fetch reads (fakes have none)
        self.devices = getattr(mesh_exec, "n_devices", 1)
        self.max_batch = max_batch
        self.submit_timeout_s = submit_timeout_s
        self.stack = stack
        # stacking burst window (server.fused.burst.window.ms): how long the
        # dispatcher lingers after the first queued query so a burst of
        # same-signature queries coalesces into ONE stacked persistent
        # launch even when the fetcher is idle. 0 keeps the original
        # drain-what's-there behavior.
        self.burst_window_s = burst_window_s
        # graftcheck: ignore[admission-bypass] -- producers block in submit()
        # with submit_timeout_s and the dispatcher drains continuously; the
        # real bound is _fetchq's max_inflight window right below. Holds
        # _Items and the fetcher's _FETCH_DONE
        self._q: "queue.Queue" = queue.Queue()
        # dispatched-but-unfetched batches: bounded so a slow fetch applies
        # backpressure to dispatch instead of piling device work up
        self._fetchq: "queue.Queue[list]" = queue.Queue(maxsize=max_inflight)
        self._fetch_busy = threading.Event()
        self._stop = threading.Event()
        # observability: batch sizes prove pipelining happened, launch counts
        # prove dedupe/stacking happened (the e2e bench and tests read these
        # through the server /metrics endpoint)
        self.batches = 0
        self.dispatched = 0
        self.fallbacks = 0
        # the device path RAISED (vs. `fallbacks`: the plan is not
        # device-eligible) — the host still answers, but never silently
        self.device_errors = 0
        self._error_counter = get_registry().counter(
            "pinot_server_device_errors")
        self.timeouts = 0
        self.launches = 0
        self.dedupe_hits = 0
        self.stacked_launches = 0
        self.fused_launches = 0
        self.by_shape = dict.fromkeys(_SHAPE_KEYS + _STAGE_KEYS, 0)
        self.decodes = dict.fromkeys(_DECODE_KEYS, 0)
        # how the batches form: drains that held one live query, why each
        # drain closed (`_drain`), and hand-offs that met a full fetch queue
        self.batches_of_one = 0
        self.drains_closed_idle = 0
        self.drains_closed_full = 0
        self.drains_closed_burst = 0
        self.handoff_blocked = 0
        # live queries whose prepare ended while the batch before was fetched
        self.prepared_ahead = 0
        # per-stage wall times: the process registry histograms back /metrics
        self._hists = {s: get_registry().histogram(
            f"pinot_server_device_pipeline_{s}_ms") for s in _STAGES}
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="device-pipeline")
        self._fetcher = threading.Thread(target=self._fetch_loop, daemon=True,
                                         name="device-fetcher")
        if start:
            self.start()

    def start(self) -> None:
        """Start the dispatcher/fetcher threads (idempotent). Tests construct
        with start=False, pre-load the queue, then start — making "N
        concurrent submissions coalesce into one drain" deterministic."""
        if not self._thread.is_alive():
            self._thread.start()
        if not self._fetcher.is_alive():
            self._fetcher.start()

    def record_error(self, where: str) -> None:
        """Call from an `except` block on the device path: log the traceback
        and count it apart from plan fallbacks (`deviceErrors` in stats(),
        `pinot_server_device_errors` in /metrics)."""
        self.device_errors += 1
        self._error_counter.inc()
        log.exception("device path raised in %s; the host path answers",
                      where)

    def _observe(self, stage_name: str, ms: float) -> None:
        self._hists[stage_name].observe(ms)

    # -- caller side ------------------------------------------------------
    def execute_partial(self, ctx, segments: Sequence, resident=None):
        """Submit and wait; returns a SegmentResult partial or DEVICE_FALLBACK.
        `segments` are the members the query was routed to; `resident`, where
        the caller knows it, the set the server holds of the table: what the
        executor stages and plans (`MeshQueryExecutor.prepare_partial`)."""
        from ..utils.trace import current_depth, current_trace
        tr = current_trace()
        item = _Item(ctx, list(segments),
                     trace_id=tr.trace_id if tr is not None else "",
                     resident=resident)
        submit_ms = tr.now_ms() if tr is not None else 0.0
        # deadline propagation: never wait on the device past the broker's
        # stamped deadline — timing out here cancels the item, and the
        # dispatcher/fetcher skip cancelled work before burning a launch or a
        # host sync on a result nobody is waiting for
        timeout_s = self.submit_timeout_s
        d_ms = ctx.options.get("deadlineEpochMs") \
            if getattr(ctx, "options", None) else None
        if d_ms is not None:
            timeout_s = max(0.0, min(timeout_s,
                                     float(d_ms) / 1000.0 - time.time()))
        self._q.put(item)
        try:
            result = item.future.result(timeout=timeout_s)
            if item.t_resolved and isinstance(getattr(result, "stats", None),
                                              dict):
                result.stats[qstats.DEVICE_WAKE_MS] = round(
                    (time.perf_counter() - item.t_resolved) * 1000, 3)
            if tr is not None and result is not DEVICE_FALLBACK:
                # the pipeline threads can't see this query's trace; rebuild
                # its phases from the item's attribution, laid end to end from
                # the submit (the time between them, other queries' prepares
                # and decodes, is not shown; the profiler's `pinot:pipeline.*`
                # spans are the true timeline)
                depth = current_depth()
                s = getattr(result, "stats", None) or {}
                at_ms = submit_ms
                for key, name in _PHASES:
                    if key in s:
                        ms = float(s[key] or 0.0)
                        tr.record(name, at_ms, ms, depth=depth)
                        at_ms += ms
            return result
        except FutureTimeoutError:
            # cancel so the dispatcher/fetcher SKIP the stale item instead of
            # planning + dispatching + decoding a result nobody will read
            # (under overload that duplicated work compounds the overload)
            item.future.cancel()
            self.timeouts += 1
            return DEVICE_FALLBACK

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        if self._fetcher.is_alive():
            self._fetcher.join(timeout=5.0)
        # resolve anything stranded in either queue: blocked handler threads
        # must fall back to the host path immediately, not wait out their
        # 120s future timeout holding segment references
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if isinstance(item, _Item):
                _resolve(item.future, DEVICE_FALLBACK)
        while True:
            try:
                entry, _ = self._fetchq.get_nowait()
            except queue.Empty:
                break
            for item in _items(entry):
                _resolve(item.future, DEVICE_FALLBACK)

    # -- dispatcher thread ------------------------------------------------
    def _fetch_in_flight(self) -> bool:
        return self._fetch_busy.is_set() or not self._fetchq.empty()

    def _drain(self) -> Optional[list]:
        """Gather the next batch: everything already queued, plus — while a
        fetch is still in flight — whatever arrives before it completes.
        Dispatching earlier than that wins nothing (the fetcher is busy for
        a full host round trip anyway) and would shatter the batch into
        singleton fetches, each paying its own round trip. An item taken
        while that fetch is in flight is prepared at once (`_prepare`), so
        its plan and inputs are built while the device runs the batch
        before; the fetch's end (`_FETCH_DONE`) wakes the gather, which then
        closes without waiting for its next poll.

        Why the drain closed is counted: `drainsClosedFull` (`max_batch`),
        `drainsClosedBurst` (the queue was empty and nothing in flight, and the
        burst window had held the drain open until it ran out),
        `drainsClosedIdle` (queue empty and no fetch in flight)."""
        with stage("pipeline.wait"):
            try:
                item = self._q.get(timeout=0.05)
            except queue.Empty:
                return None
        if not isinstance(item, _Item):
            return None
        batch = []
        deadline = (time.perf_counter() + self.burst_window_s
                    if self.burst_window_s > 0 else None)
        held_by_window = False
        with stage("pipeline.gather") as gather:
            while True:
                if isinstance(item, _Item):
                    batch.append(item)
                    if self._prepared_api and self._fetch_in_flight():
                        item.prepared = self._prepare(item)
                        item.ahead = (item.prepared is not None
                                      and self._fetch_in_flight())
                if len(batch) >= self.max_batch:
                    self.drains_closed_full += 1
                    break
                try:
                    item = self._q.get_nowait()
                    continue
                except queue.Empty:
                    pass
                if not self._fetch_in_flight():
                    if deadline is None or time.perf_counter() >= deadline:
                        if held_by_window:
                            self.drains_closed_burst += 1
                        else:
                            self.drains_closed_idle += 1
                        break
                    held_by_window = True
                try:
                    item = self._q.get(timeout=_GATHER_POLL_S)
                except queue.Empty:
                    item = None
            gather.note(n=len(batch))
        return batch

    def _loop(self) -> None:
        """Dispatcher: drain (preparing what it takes while a fetch is in
        flight) -> prepare the rest + group -> launch -> hand to fetcher.

        Two-stage pipelining: while the fetcher blocks in the host sync for
        batch N (one host round trip), batch N+1's kernels are ALREADY
        dispatched and executing on the device — the round trip overlaps
        compute instead of serializing behind it."""
        while not self._stop.is_set():
            batch = self._drain()
            if batch is None:
                continue
            try:
                # graftfault: a slow spec stalls the drain (device contention /
                # recompile storm); a failing spec means the device path is
                # down — the whole drain downgrades to host execution,
                # availability over the fast path, never a dead dispatcher
                fault_point("device.launch.slow")
            except FaultInjected:
                for item in batch:
                    self.fallbacks += 1
                    _resolve(item.future, DEVICE_FALLBACK)
                continue
            t0 = time.perf_counter()
            if self._prepared_api:
                entry, n_live = self._dispatch_grouped(batch)
            else:
                entry, n_live = self._dispatch_legacy(batch, t0)
            if not entry:
                continue
            t_launched = time.perf_counter()
            self._observe("dispatch", (t_launched - t0) * 1000)
            self.batches += 1
            self.batches_of_one += n_live == 1
            self.dispatched += n_live
            self.launches += len(entry)
            for item in _items(entry):
                item.stats[qstats.DEVICE_BATCH_SIZE] = n_live
            handed_off = False
            try:
                self._fetchq.put_nowait((entry, t_launched))
                handed_off = True
            except queue.Full:
                self.handoff_blocked += 1
                with stage("pipeline.handoff"):
                    while not handed_off and not self._stop.is_set():
                        try:
                            # fetcher backlogged: backpressure dispatch
                            self._fetchq.put((entry, t_launched), timeout=0.2)
                            handed_off = True
                        except queue.Full:
                            continue
            if not handed_off:
                # stopping with the fetch queue full: these futures would
                # otherwise dangle past stop()'s drain for the full submit
                # timeout — resolve them to the host path now
                for item in _items(entry):
                    _resolve(item.future, DEVICE_FALLBACK)

    def _prepare(self, item):
        """Plan one live item and build its inputs (`prepare_partial`), no
        launch. Returns its PreparedDispatch, or None where the item was
        resolved instead: its caller timed out, its plan is not
        device-eligible (a `fallbacks`), or the prepare raised (a
        `deviceErrors`); the host path answers those."""
        if item.future.done():
            # caller already timed out and cancelled: don't burn a
            # device dispatch on a result nobody will read
            return None
        # this thread serves many queries: what the kernel cache records
        # while it plans THIS one goes to a scratch record, then the item
        scratch = qstats.ExecutionStats()
        try:
            with qstats.activate(scratch), \
                    stage("pipeline.prepare", cpu=True,
                          trace_id=item.trace_id) as prep:
                p = self.mesh_exec.prepare_partial(
                    item.ctx, item.segments, item.resident)
        except Exception:
            # planning RAISED on the device path: the host path still
            # answers the query, but as a counted, logged device error —
            # not as a plan fallback
            self.record_error("prepare_partial")
            _resolve(item.future, DEVICE_FALLBACK)
            return None
        self._observe("prepare", prep.ms)
        item.stats[qstats.DEVICE_PREPARE_MS] = round(prep.ms, 3)
        item.stats[qstats.DEVICE_PREPARE_CPU_MS] = round(prep.cpu_ms, 3)
        _fold(item.stats, scratch.counters)
        for k in _STAGE_KEYS:
            self.by_shape[k] += int(scratch.counters.get(k, 0))
        if p is None:
            self.fallbacks += 1
            _resolve(item.future, DEVICE_FALLBACK)
            return None
        if not self.stack:
            p.stackable = False
        return p

    def _dispatch_grouped(self, batch):
        """Prepare every live item the drain has not, collapse identical
        dispatches, launch the dedupe representatives (stacking where
        executables align), in arrival order. Returns (fetch entry, live
        item count); the entry is a list of launches
        `(outs_dev, finish, groups)` where `groups[i]` holds the
        (item, decode) pairs answered by the launch's i-th result."""
        reps = []          # dedupe-group representative PreparedDispatch
        rep_groups: List[list] = []   # aligned [(item, decode), ...] lists
        dedupe_index: Dict[tuple, int] = {}
        for item in batch:
            if item.future.done():
                # cancelled by its caller (its early prepare notwithstanding)
                # or resolved by that prepare
                continue
            p = item.prepared if item.prepared is not None \
                else self._prepare(item)
            item.prepared = None    # the launch keeps what it needs of it
            if p is None:
                continue
            if p.dedupe_key is not None and p.dedupe_key in dedupe_index:
                rep_groups[dedupe_index[p.dedupe_key]].append(
                    (item, p.decode))
                self.dedupe_hits += 1
                item.stats["dedupedLaunches"] = 1
                continue
            if p.dedupe_key is not None:
                dedupe_index[p.dedupe_key] = len(reps)
            reps.append(p)
            rep_groups.append([(item, p.decode)])
        if not reps:
            return [], 0
        live = [item for group in rep_groups for item, _ in group]
        t_launch = time.perf_counter()
        for item in live:
            # enqueue -> its batch's launch, less its own prepare: the six
            # phases tile the item's time in the pipeline, and what it waited
            # through of its neighbours' prepares is queue wait
            wait_ms = max(0.0, (t_launch - item.t_enqueue) * 1000
                          - item.stats[qstats.DEVICE_PREPARE_MS])
            self._observe("queue_wait", wait_ms)
            item.stats[qstats.QUEUE_WAIT_MS] = round(wait_ms, 3)
        try:
            with stage("pipeline.launch", cpu=True, batch=len(live),
                       devices=self.devices) as launch:
                launches = self.mesh_exec.dispatch_prepared(reps)
                launch.note(launches=len(launches))
        except Exception:
            # a grouped launch failing downgrades the whole drain to host
            # execution (availability over the fast path) — logged and
            # counted as ONE device error, not as plan fallbacks
            self.record_error("dispatch_prepared")
            for item in live:
                _resolve(item.future, DEVICE_FALLBACK)
            return [], 0
        self._observe("launch", launch.ms)
        self.prepared_ahead += sum(item.ahead for item in live)
        self.stacked_launches += sum(1 for _, _, idxs, _ in launches
                                     if len(idxs) > 1)
        for _, _, idxs, recorded in launches:
            stacked = len(idxs) > 1
            fused = any(getattr(getattr(reps[i], "spec", None),
                                "fused_cols", ()) for i in idxs)
            if fused:
                self.fused_launches += 1
            for k in _SHAPE_KEYS:
                self.by_shape[k] += int(recorded.get(k, 0))
            for i in idxs:
                for item, _ in rep_groups[i]:
                    item.stats[qstats.DEVICE_LAUNCH_MS] = round(launch.ms, 3)
                    item.stats[qstats.DEVICE_LAUNCH_CPU_MS] = round(
                        launch.cpu_ms, 3)
                    _fold(item.stats, recorded)
                    if fused:
                        item.stats["fusedLaunches"] = 1
                    if stacked:
                        item.stats["stackedLaunches"] = 1
        entry = [(outs_dev, finish, [rep_groups[i] for i in idxs])
                 for outs_dev, finish, idxs, _ in launches]
        return entry, len(live)

    def _dispatch_legacy(self, batch, t0):
        """One launch per item for executors without the prepared API (fakes,
        older mesh executors): preserves batched fetching, skips
        dedupe/stacking."""
        entry = []
        for item in batch:
            if item.future.done():
                continue
            wait_ms = (t0 - item.t_enqueue) * 1000
            self._observe("queue_wait", wait_ms)
            item.stats[qstats.QUEUE_WAIT_MS] = round(wait_ms, 3)
            try:
                with stage("pipeline.launch", cpu=True, batch=1,
                           trace_id=item.trace_id) as launch:
                    dp = self.mesh_exec.dispatch_partial(item.ctx,
                                                         item.segments)
            except Exception:
                self.record_error("dispatch_partial")
                _resolve(item.future, DEVICE_FALLBACK)
                continue
            if dp is None:
                self.fallbacks += 1
                _resolve(item.future, DEVICE_FALLBACK)
                continue
            self._observe("launch", launch.ms)
            item.stats[qstats.DEVICE_LAUNCH_MS] = round(launch.ms, 3)
            item.stats[qstats.DEVICE_LAUNCH_CPU_MS] = round(launch.cpu_ms, 3)
            item.stats[qstats.DEVICE_LAUNCHES] = 1
            entry.append((dp[0], (lambda host: [host]),
                          [[(item, dp[1])]]))
        return entry, len(entry)

    # -- fetcher thread ---------------------------------------------------
    def _fetch_loop(self) -> None:
        import jax
        fetch = getattr(self.mesh_exec, "fetch", None) or jax.device_get
        while not self._stop.is_set():
            try:
                entry, t_launched = self._fetchq.get(timeout=0.05)
            except queue.Empty:
                continue
            self._fetch_busy.set()
            handoff_ms = (time.perf_counter() - t_launched) * 1000
            self._observe("handoff", handoff_ms)
            try:
                # launches whose every caller timed out are dead weight:
                # dropping them BEFORE the host sync keeps a storm of
                # cancellations from paying host round trips for nothing
                live = [L for L in entry
                        if any(not item.future.done()
                               for group in L[2] for item, _ in group)]
                if not live:
                    continue
                n_items = sum(len(group) for L in live for group in L[2])
                try:
                    # ONE host sync for the whole dispatched batch
                    with stage("pipeline.fetch", batch=n_items,
                               launches=len(live),
                               devices=self.devices) as sync:
                        fetched = fetch([L[0] for L in live])
                except Exception as e:
                    for item in _items(live):
                        _resolve(item.future, None, exc=e)
                    continue
                self._observe("fetch", sync.ms)
                waited = {qstats.DEVICE_HANDOFF_MS: round(handoff_ms, 3),
                          qstats.DEVICE_FETCH_MS: round(sync.ms, 3)}
                with stage("pipeline.decode", batch=n_items) as dec:
                    for (_, finish, groups), host in zip(live, fetched):
                        self._decode_launch(finish, groups, host, waited)
                self._observe("decode", dec.ms)
            finally:
                self._fetch_busy.clear()
                self._q.put(_FETCH_DONE)

    def _decode_launch(self, finish, groups, host, waited: dict) -> None:
        """Unpack one launch and resolve the queries it answers. `waited` is
        what every item of the batch waited for before this: the hand-off and
        the batched host sync (wall, shared)."""
        t0 = time.perf_counter()
        try:
            outs_list = finish(host)
        except Exception as e:
            for group in groups:
                for item, _ in group:
                    _resolve(item.future, None, exc=e)
            return
        for outs, group in zip(outs_list, groups):
            for took in qstats.decode_branch(outs):
                self.decodes[took] += 1
            # its share of the batch's fetch: what the host sync moved for it
            fetched = {qstats.BYTES_FETCHED: sum(
                int(getattr(v, "nbytes", 0)) for v in outs.values())} \
                if isinstance(outs, dict) else {}
            for item, decode in group:
                if item.future.done():
                    continue  # caller timed out mid-fetch: skip the decode
                try:
                    r = decode(outs)
                except Exception as e:
                    _resolve(item.future, None, exc=e)
                    continue
                if r is DEVICE_FALLBACK:
                    # the device result is unusable (e.g. NaN order keys,
                    # candidate overflow) — host path decides
                    self.fallbacks += 1
                elif hasattr(r, "stats"):
                    # attach this item's launch attribution to its partial
                    # BEFORE resolving: the query thread folds it into the
                    # per-query ExecutionStats (the fetcher thread has no
                    # query-scoped thread-locals to publish into).
                    # deviceDecodeMs runs from the start of its launch's
                    # decode to this answer
                    s = dict(item.stats, **waited, **fetched)
                    s[qstats.DEVICE_DECODE_MS] = round(
                        (time.perf_counter() - t0) * 1000, 3)
                    s.update(r.stats or {})
                    r.stats = s
                item.t_resolved = time.perf_counter()
                _resolve(item.future, r)

    def stats(self) -> dict:
        return {"batches": self.batches, "dispatched": self.dispatched,
                "fallbacks": self.fallbacks,
                "deviceErrors": self.device_errors, "timeouts": self.timeouts,
                "launches": self.launches, "dedupeHits": self.dedupe_hits,
                "stackedLaunches": self.stacked_launches,
                "fusedLaunches": self.fused_launches, **self.by_shape,
                **self.decodes,
                "batchesOfOne": self.batches_of_one,
                "drainsClosedIdle": self.drains_closed_idle,
                "drainsClosedFull": self.drains_closed_full,
                "drainsClosedBurst": self.drains_closed_burst,
                "handoffBlocked": self.handoff_blocked,
                "preparedAhead": self.prepared_ahead}


def pipeline_from_config(cfg) -> Optional[DeviceQueryPipeline]:
    """Build the device pipeline from `server.device.*` keys; None when
    device serving is disabled (the default — e.g. CPU-only test clusters
    that want the host engine)."""
    if not cfg.get_bool("server.device.enabled", False):
        return None
    mesh_exec = None
    n_mesh = cfg.get_int("server.mesh.devices", 0)
    if n_mesh > 0:
        # explicit mesh width (0 = every visible device): a server can pin its
        # pipeline to a sub-mesh, e.g. to split chips between serving replicas
        from ..parallel.combine import MeshQueryExecutor
        from ..parallel.mesh import default_mesh
        mesh_exec = MeshQueryExecutor(default_mesh(n_mesh))
    return DeviceQueryPipeline(
        mesh_exec=mesh_exec,
        max_batch=cfg.get_int("server.device.max.batch", 64),
        submit_timeout_s=cfg.get_float("server.device.timeout.seconds", 120.0),
        max_inflight=cfg.get_int("server.device.max.inflight", 4),
        stack=cfg.get_bool("server.device.stacking.enabled", True),
        burst_window_s=cfg.get_float("server.fused.burst.window.ms", 0.0)
        / 1000.0)
