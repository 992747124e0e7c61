"""Server role: segment lifecycle management + query execution.

Analog of the reference's server stack (SURVEY.md §2.6): `BaseServerStarter` boot,
`HelixInstanceDataManager` (add/replace/drop segments on state transitions,
`server/starter/helix/HelixInstanceDataManager.java:78,164`), per-table data managers
with refcounted acquire/release (`BaseTableDataManager`), and the query executor half of
`ServerQueryExecutorV1Impl`. State transitions arrive as catalog ideal-state watch events
instead of Helix messages; the server reconciles desired vs loaded and reports the
external view.
"""

from __future__ import annotations

import dataclasses
import os
import re
import threading
from typing import Dict, List, Optional, Sequence, Union

from ..query import stats as qstats
from ..query.aggregates import make_agg
from ..query.context import SOLE_SERVER, QueryContext, compile_query
from ..parallel.combine import device_topk_screen
from ..query.executor import ServerQueryExecutor
from ..query.reduce import SegmentResult, merge_segment_results
from ..segment.reader import ImmutableSegment, load_segment
from ..utils.events import emit as emit_event
from ..utils.faults import fault_point
from ..utils.trace import install_gc_hook
from .catalog import (COLD, CONSUMING, DROPPED, OFFLINE, ONLINE, Catalog,
                      InstanceInfo)
from .deepstore import DeepStoreFS, untar_segment
from .tiering import PRESSURE_INTERVAL_S, TieringManager


def _push_order(name: str) -> list:
    return [int(t) if t.isdigit() else t for t in re.split(r"(\d+)", name)]


class TableDataManager:
    """Per-table loaded segments with refcounting (reference: BaseTableDataManager)."""

    def __init__(self, table: str, data_dir: str):
        self.table = table
        self.data_dir = data_dir
        self._segments: Dict[str, ImmutableSegment] = {}
        self._refcounts: Dict[str, int] = {}
        # segments unloaded while a query still held a ref: their device
        # block + ledger release defers until release() drains the refcount
        self._deferred: Dict[str, ImmutableSegment] = {}
        # names in push order, reckoned by `resident()` and forgotten when
        # the set changes
        self._order: Optional[List[str]] = None
        self._lock = threading.RLock()

    def add_segment(self, name: str, segment: ImmutableSegment) -> None:
        with self._lock:
            # a deferred copy being replaced (reload swap) releases NOW: the
            # fresh reader takes over, and acquired refs point at the old
            # object which stays valid until its holders release it
            old = self._deferred.pop(name, None)
            self._segments[name] = segment
            self._order = None
            self._refcounts.setdefault(name, 0)
        if old is not None and old is not segment:
            from ..engine.datablock import release_block
            release_block(old)
        # table attribution for staging sites that only know the segment
        # (engine.datablock): offline segment names carry no table prefix
        from ..utils.memledger import get_ledger
        get_ledger().bind_segment(self.table, name)

    def remove_segment(self, name: str) -> None:
        with self._lock:
            seg = self._segments.pop(name, None)
            self._order = None
            if seg is not None and self._refcounts.get(name, 0) > 0:
                # unload-vs-in-flight-query race: a running query acquired
                # this segment — yanking the device block now would fail its
                # kernels mid-flight. Park it; release() frees it when the
                # refcount drains to zero.
                self._deferred[name] = seg
                return
            self._refcounts.pop(name, None)
        if seg is not None:
            # unload = free: drop the cached device block and its ledger
            # entries, not just the host-side reader
            from ..engine.datablock import release_block
            release_block(seg)

    def acquire(self, names: Optional[Sequence[str]] = None) -> List[ImmutableSegment]:
        with self._lock:
            targets = list(self._segments) if names is None else \
                [n for n in names if n in self._segments]
            for n in targets:
                self._refcounts[n] = self._refcounts.get(n, 0) + 1
            return [self._segments[n] for n in targets]

    def release(self, segments: Sequence[ImmutableSegment]) -> None:
        doomed: List[ImmutableSegment] = []
        with self._lock:
            for seg in segments:
                if seg.name in self._refcounts:
                    self._refcounts[seg.name] -= 1
                    if (self._refcounts[seg.name] <= 0
                            and seg.name in self._deferred):
                        # last holder of an unloaded segment: free it now
                        doomed.append(self._deferred.pop(seg.name))
                        self._refcounts.pop(seg.name, None)
        if doomed:
            from ..engine.datablock import release_block
            for seg in doomed:
                release_block(seg)

    def resident(self) -> List[ImmutableSegment]:
        """Every loaded segment in push order (names compared with their
        digit runs as numbers: `t_2` before `t_10`), whatever order they
        were loaded in: the set the device pipeline stages and plans, in
        which time-pruned subsets are contiguous."""
        with self._lock:
            if self._order is None:
                self._order = sorted(self._segments, key=_push_order)
            return [self._segments[n] for n in self._order]

    def refcount(self, name: str) -> int:
        """In-flight acquisitions of `name` — the tiering eviction loop's
        never-evict-under-a-running-query check."""
        with self._lock:
            return self._refcounts.get(name, 0)

    def get(self, name: str) -> Optional[ImmutableSegment]:
        with self._lock:
            return self._segments.get(name)

    @property
    def segment_names(self) -> List[str]:
        with self._lock:
            return list(self._segments)


class ServerNode:
    """One server instance (reference: HelixServerStarter + ServerInstance)."""

    def __init__(self, instance_id: str, catalog: Catalog, deepstore: DeepStoreFS,
                 data_dir: str, tags: Optional[List[str]] = None, completion=None,
                 scheduler=None, auto_consume: bool = False,
                 device_pipeline=None):
        self.instance_id = instance_id
        self.catalog = catalog
        self.deepstore = deepstore
        self.data_dir = data_dir
        # the process's collections, counted on /health's `device` block and
        # spanned as `pinot:gc` (generation 2) under a profiler session
        install_gc_hook()
        # device bitmap filter indexes default on; operators can force the
        # LUT/interval filter path cluster-wide (e.g. to bisect a wrong-result
        # report) without redeploying servers
        bitmap_on = str(catalog.get_property(
            "clusterConfig/server.index.bitmap.enabled", "true")).lower() != "false"
        self.executor = ServerQueryExecutor(bitmap_enabled=bitmap_on)
        # host-tier executor: never stages device blocks — what unadmitted
        # segments run on when the HBM admission gate rejects them
        self.host_executor = ServerQueryExecutor(use_device=False,
                                                 bitmap_enabled=bitmap_on)
        # HBM capacity override knob (env PINOT_TPU_HBM_CAPACITY_BYTES is the
        # process-level equivalent): lets tests/bench pin a tiny budget
        cap_raw = catalog.get_property(
            "clusterConfig/server.hbm.capacity.bytes", None)
        if cap_raw is not None:
            try:
                from ..utils.memledger import get_ledger
                get_ledger().set_capacity(int(cap_raw))
            except (TypeError, ValueError):
                pass  # malformed knob: keep the probed capacity
        # tiered-storage lifecycle: HBM admission gate + pressure eviction
        self.tiering = TieringManager(catalog, node=instance_id)
        self._pressure_scheduler = None
        # optional admission control (reference: QueryScheduler wrapping the
        # executor; None = direct execution, the single-tenant test default)
        self.scheduler = scheduler
        # device-backed serving: when set, broker-routed partials execute on
        # the TPU through the mesh executor with batched fetches
        # (cluster/device_server.py; reference: ServerInstance owning the
        # engine, ServerInstance.java:55,120-186)
        self.device_pipeline = device_pipeline
        # True in real server processes: realtime managers run their background
        # consume loop (reference: PartitionConsumer threads); False in tests,
        # which drive pump/complete deterministically
        self.auto_consume = auto_consume
        self.tables: Dict[str, TableDataManager] = {}
        # per-table EWMA of bytesFetched per partial: the scheduler's fair
        # queue charges each tenant by predicted bytes so a scan-heavy table
        # consumes its share faster than a cheap-aggregation one
        self._table_bytes_ewma: Dict[str, float] = {}
        self._lock = threading.RLock()
        self._realtime_managers: Dict[str, object] = {}
        self._load_locks: Dict[tuple, threading.Lock] = {}
        self.completion = completion  # LLCSegmentManager handle (in-proc or HTTP proxy)
        # lifecycle: STARTING -> UP -> SHUTTING_DOWN (reference: ServiceStatus +
        # BaseServerStarter's startupServiceStatusCheck gate)
        self.status = "STARTING"
        os.makedirs(data_dir, exist_ok=True)
        catalog.register_instance(InstanceInfo(instance_id, "server", tags=tags
                                               or ["DefaultTenant"]))
        catalog.subscribe(self._on_catalog_event)
        # catch up with pre-existing ideal state (reference: startup reconciliation)
        for table in list(catalog.ideal_state):
            self.reconcile(table)
        self.status = "UP"

    # -- lifecycle ----------------------------------------------------------
    def startup_status(self) -> Dict[str, object]:
        """Readiness: every segment the ideal state assigns to this server is
        actually served/consuming (reference: BaseServerStarter.java:542-549 —
        no queries before all assigned segments are loaded)."""
        assigned = loaded = 0
        # snapshot under the catalog lock: the in-proc Catalog mutates ideal
        # state dicts in place, and a health probe racing update_ideal_state
        # would die with "dictionary changed size during iteration"
        with self.catalog._lock:
            ideal = {t: {s: dict(a) for s, a in ist.items()}
                     for t, ist in self.catalog.ideal_state.items()}
        for table, ist in ideal.items():
            mgr = self.tables.get(table)
            served = set(mgr.segment_names) if mgr else set()
            rt = self._realtime_managers.get(table)
            consuming = set(rt.consumers) if rt is not None else set()
            for seg, assignment in ist.items():
                state = assignment.get(self.instance_id)
                if state in (ONLINE, CONSUMING):
                    assigned += 1
                    if seg in served or seg in consuming:
                        loaded += 1
        ready = self.status == "UP" and loaded == assigned
        return {"status": self.status, "assignedSegments": assigned,
                "loadedSegments": loaded, "ready": ready}

    def shutdown(self) -> None:
        """Graceful stop: deregister from routing, stop consumers/scheduler
        (reference: BaseServerStarter.stop -> shutdownGracefully)."""
        self.status = "SHUTTING_DOWN"
        try:
            self.catalog.set_instance_alive(self.instance_id, False)
        # graftcheck: ignore[exception-hygiene] -- shutdown teardown: the
        # controller being gone already achieves what this call wanted
        except Exception:
            pass  # controller may already be gone during teardown
        for handler in list(self._realtime_managers.values()):
            handler.stop()
        if self.scheduler is not None:
            self.scheduler.stop()
        if self.device_pipeline is not None:
            self.device_pipeline.stop()
        self.stop_pressure_loop()

    def start_pressure_loop(self) -> None:
        """Run the HBM pressure sweep as a background periodic task — called
        by ServerService (real server processes); tests drive
        `tiering.run_pressure_sweep()` directly for determinism."""
        from ..utils.periodic import PeriodicTask, PeriodicTaskScheduler
        if self._pressure_scheduler is not None:
            return
        sched = PeriodicTaskScheduler()
        sched.register(PeriodicTask("HbmPressureLoop", PRESSURE_INTERVAL_S,
                                    self.tiering.run_pressure_sweep))
        sched.start()
        self._pressure_scheduler = sched

    def stop_pressure_loop(self) -> None:
        if self._pressure_scheduler is not None:
            self._pressure_scheduler.stop()
            self._pressure_scheduler = None

    # -- state transitions -------------------------------------------------
    def _on_catalog_event(self, event: str, table: str) -> None:
        if event == "ideal_state":
            self.reconcile(table)
        elif event == "table" and self.catalog.table_configs.get(table) is None:
            # table DROPPED: the final config removal arrives as a 'table'
            # event (ideal-state events already emptied the segments); one
            # last reconcile tears down the realtime manager + its loop
            self.reconcile(table)
        elif event == "property" and table.startswith("pause/"):
            # controller pause/resume consumption (reference: the pause state
            # servers observe in ideal state)
            t = table.split("/", 1)[1]
            rt = self._realtime_managers.get(t)
            if rt is not None:
                rt.set_paused(self.catalog.get_property(table) is not None)
        elif event == "property" and table.startswith("reload/"):
            # controller-triggered segment reload (reference: the Helix RELOAD
            # message driving SegmentPreProcessor on each server). Never let a
            # reload failure propagate: it would kill the catalog watch thread.
            try:
                self.reload_table(table.split("/", 1)[1])
            # graftcheck: ignore[exception-hygiene] -- reload_table already
            # isolates + reports per-segment errors; this guard only keeps
            # the catalog watch thread alive on a wholesale failure
            except Exception:
                pass  # per-segment errors are already isolated + reported below

    def reload_table(self, table: str) -> List[str]:
        """Reconcile every loaded immutable segment's aux indexes with the CURRENT
        table config (reference: HelixInstanceDataManager.reloadSegment ->
        SegmentPreProcessor), swapping in fresh readers so new indexes are used.

        Index REMOVALS are deferred until after the fresh reader is swapped in and
        the old reader's refcount drains, so in-flight queries holding the old
        reader never lazily open a deleted file (the reference likewise destroys
        old index buffers only after segment release)."""
        from ..segment.preprocess import preprocess_segment
        cfg = self.catalog.table_configs.get(table)
        if cfg is None:
            return []
        mgr = self._table_manager(table)
        changes: List[str] = []
        schema = self.catalog.schema_for_table(table)
        segments = mgr.acquire()
        try:
            for seg in segments:
                if getattr(seg, "is_mutable", False) or not getattr(seg, "path", None):
                    continue
                deferred: List[str] = []
                try:
                    ch = preprocess_segment(
                        seg.path, cfg.indexing, defer_removals=deferred,
                        schema=schema)
                except Exception as e:  # one bad segment must not stop the rest
                    changes.append(f"{seg.name}: ERROR {type(e).__name__}: {e}")
                    ch = None
                # reap deferred removals even when a later step failed:
                # preprocess_segment already recorded a CRC that excludes them,
                # so leaving the files on disk would fail CRC verification
                # until some unrelated reload rewrote it
                if deferred:
                    self._remove_after_release(mgr, seg, deferred)
                if ch:
                    mgr.add_segment(seg.name, load_segment(seg.path))
                    changes.extend(f"{seg.name}/{c}" for c in ch)
        finally:
            mgr.release(segments)
        return changes

    def _remove_after_release(self, mgr: TableDataManager, old_seg,
                              paths: List[str]) -> None:
        """Delete superseded index files once the old reader is no longer acquired
        (bounded wait; open mmaps survive unlink on POSIX, so this is belt and
        braces against first-touch-after-delete)."""
        def reap():
            import time as _t
            deadline = _t.time() + 5.0
            while _t.time() < deadline:
                with mgr._lock:
                    # our caller still holds one ref during reload_table
                    if mgr._refcounts.get(old_seg.name, 0) <= 1:
                        break
                _t.sleep(0.05)
            for p in paths:
                try:
                    if os.path.exists(p):
                        os.remove(p)
                except OSError:
                    pass
        # graftcheck: ignore[thread-no-join] -- one-shot reaper bounded by its
        # own 5s deadline; joining would stall reload_table on file cleanup
        threading.Thread(target=reap, daemon=True, name="reload-reap").start()

    def reconcile(self, table: str) -> None:
        """Converge loaded segments to the ideal state (reference: Helix transitions
        OFFLINE->ONLINE / ONLINE->OFFLINE / ->DROPPED in
        SegmentOnlineOfflineStateModelFactory)."""
        ist = self.catalog.ideal_state.get(table, {})
        mgr = self._table_manager(table)
        desired = {seg: assignment[self.instance_id]
                   for seg, assignment in ist.items() if self.instance_id in assignment}

        for seg_name, state in desired.items():
            if state == ONLINE and seg_name not in mgr.segment_names:
                try:
                    # CONSUMING -> ONLINE: adopt the local build when offsets allow,
                    # else download the committed copy (reference:
                    # onBecomeOnlineFromConsuming, CONSUMING->ONLINE transition :91)
                    handler = self._realtime_managers.get(table)
                    local_dir = handler.on_segment_online(seg_name) if handler else None
                    try:
                        if local_dir:
                            mgr.add_segment(seg_name, load_segment(local_dir))
                        else:
                            self._load_online_segment(table, seg_name, mgr)
                    finally:
                        # handoff second half: retire the retained post-commit
                        # consumer whether the load succeeded (immutable now
                        # serves) or failed (ERROR state must not keep a
                        # closed consumer and its buffer alive forever)
                        if handler is not None:
                            handler.retire_consumer(seg_name)
                    self.catalog.report_state(table, seg_name, self.instance_id, ONLINE)
                except Exception:
                    self.catalog.report_state(table, seg_name, self.instance_id, "ERROR")
                    raise
            elif state == CONSUMING and seg_name not in mgr.segment_names:
                handler = self._ensure_realtime_manager(table)
                if handler is not None:
                    handler.start_consuming(seg_name)
                    self.catalog.report_state(table, seg_name, self.instance_id,
                                              CONSUMING)
            elif state == COLD:
                # cold demotion: the deep store holds the bytes; unload the
                # local copy. The segment stays registered + routable — first
                # query lazily re-downloads it (_run_partial cold path).
                # Transition-edge only (external view not yet COLD): a later
                # reconcile must NOT unload a copy the cold path just lazily
                # re-downloaded.
                ev_state = self.catalog.external_view.get(table, {}) \
                    .get(seg_name, {}).get(self.instance_id)
                if ev_state != COLD:
                    if seg_name in mgr.segment_names:
                        busy = mgr.refcount(seg_name) > 0
                        mgr.remove_segment(seg_name)
                        self.tiering.forget(seg_name)
                        if not busy:
                            # an in-flight query may lazily open column files
                            # off its deferred reader — only reclaim disk when
                            # no one holds the segment
                            import shutil
                            shutil.rmtree(os.path.join(self.data_dir, table,
                                                       seg_name),
                                          ignore_errors=True)
                    self.catalog.report_state(table, seg_name,
                                              self.instance_id, COLD)

        for seg_name in list(mgr.segment_names):
            if seg_name not in desired:
                mgr.remove_segment(seg_name)
                self.tiering.forget(seg_name)
                with self._lock:  # prune the load lock with the segment
                    self._load_locks.pop((table, seg_name), None)
                self.catalog.report_state(table, seg_name, self.instance_id, None)

        # CONSUMING segments removed from the ideal state (segment deletion,
        # shrink) must stop consuming too — they live in the realtime manager,
        # not the TableDataManager the loop above sweeps
        rt = self._realtime_managers.get(table)
        if rt is not None:
            for seg_name in list(rt.consumers):
                if seg_name not in desired:
                    consumer = rt.stop_consuming(seg_name)
                    if consumer is not None:
                        consumer.close()
                    self.catalog.report_state(table, seg_name,
                                              self.instance_id, None)

        if self.catalog.table_configs.get(table) is None:
            # table dropped: the realtime manager (and its auto_consume loop)
            # must die with it — a stale handler would keep fetching from the
            # old stream and shadow a recreated table's new config — and the
            # empty TableDataManager entry goes too
            with self._lock:
                handler = self._realtime_managers.pop(table, None)
                self.tables.pop(table, None)
                for key in [k for k in self._load_locks if k[0] == table]:
                    del self._load_locks[key]
            if handler is not None:
                handler.stop()
            # belt-and-braces ledger teardown: any residency still attributed
            # to the dropped table (consuming staging a racing stop missed)
            # must not survive as stale gauges
            from ..utils.memledger import get_ledger
            get_ledger().release(table=table)

        self._refresh_dim_table(table, mgr)

    def _refresh_dim_table(self, table: str, mgr: TableDataManager) -> None:
        """(Re)load a dimension table's PK map after segment changes (reference:
        DimensionTableDataManager rebuilds its map on every segment add/remove)."""
        cfg = self.catalog.table_configs.get(table)
        if cfg is None or not cfg.is_dim_table:
            return
        from ..query.lookup import register_dim_table_from_segments
        schema = self.catalog.schema_for_table(table)
        pk = schema.primary_key_columns if schema else []
        if not pk:
            return
        segments = mgr.acquire()
        try:
            register_dim_table_from_segments(cfg.name, pk, segments)
        finally:
            mgr.release(segments)

    def _ensure_realtime_manager(self, table: str):
        with self._lock:
            handler = self._realtime_managers.get(table)
            if handler is None:
                cfg = self.catalog.table_configs.get(table)
                if cfg is None or cfg.stream is None or self.completion is None:
                    return None
                from ..ingest.realtime import RealtimeTableManager
                handler = RealtimeTableManager(self, table, cfg, self.completion)
                self._realtime_managers[table] = handler
                if self.auto_consume:
                    handler.start_loop()
            return handler

    def realtime_manager(self, table: str):
        return self._realtime_managers.get(table)

    def ingestion_snapshot(self) -> Dict[str, Dict[str, object]]:
        """{table: ingestion rollup} across every realtime manager on this
        server — the payload behind /debug/consuming, and what the
        controller's ingestion status check polls (in-proc clusters register
        this method directly as the poller)."""
        return {table: handler.ingestion_status()
                for table, handler in list(self._realtime_managers.items())}

    def memory_snapshot(self) -> Dict[str, object]:
        """Device-memory residency rollup — the payload behind /debug/memory
        and what the controller's memory status check polls (in-proc clusters
        register this method directly as the poller). The ledger is
        process-global, so in-proc multi-server clusters all report the one
        process view — which is also what jax reports, keeping
        reconciliation honest."""
        from ..utils.memledger import get_ledger
        snap = get_ledger().snapshot()
        snap["instanceId"] = self.instance_id
        snap["tiering"] = self.tiering.snapshot()
        return snap

    def _load_online_segment(self, table: str, seg_name: str, mgr: TableDataManager) -> None:
        # per-segment load lock (reference: SegmentLocks): concurrent
        # reconciles — an ideal-state notify racing a rebalance notify — must
        # not double-download/untar into the same directory (one thread's
        # cleanup deletes the tar under the other, and a racing untar could be
        # read half-written)
        with self._segment_load_lock(table, seg_name):
            meta = self.catalog.segments.get(table, {}).get(seg_name)
            local_dir = os.path.join(self.data_dir, table, seg_name)
            if not os.path.isdir(local_dir):
                if meta is None or not meta.download_path:
                    raise FileNotFoundError(f"no deep-store path for {table}/{seg_name}")
                tar_local = f"{local_dir}.{threading.get_ident()}.tar.gz"
                from .peers import download_segment_tar
                download_segment_tar(self.deepstore, self.catalog, table,
                                     seg_name, tar_local, meta.download_path,
                                     exclude_instance=self.instance_id)
                try:
                    untar_segment(tar_local, os.path.dirname(local_dir))
                finally:
                    if os.path.exists(tar_local):
                        os.remove(tar_local)
            mgr.add_segment(seg_name, load_segment(local_dir))

    def _cold_unloaded(self, table: str,
                       segment_names: Optional[Sequence[str]],
                       mgr: TableDataManager) -> List[str]:
        """Segments the query wants that are assigned COLD to this server
        with no loaded copy — the cold-tier lazy-load set. Snapshot under the
        catalog lock (startup_status idiom: the in-proc catalog mutates its
        dicts in place)."""
        with self.catalog._lock:
            ist = {s: dict(a) for s, a in
                   self.catalog.ideal_state.get(table, {}).items()}
        loaded = set(mgr.segment_names)
        wanted = list(ist) if segment_names is None else list(segment_names)
        return [s for s in wanted
                if s not in loaded
                and ist.get(s, {}).get(self.instance_id) == COLD]

    def local_segment_dir(self, table: str, seg_name: str) -> Optional[str]:
        """On-disk directory of a LOADED segment (peer download serves from
        it); None when this server doesn't serve the segment."""
        mgr = self.tables.get(table)
        if mgr is None:
            return None
        seg = mgr.get(seg_name)
        path = getattr(seg, "path", None)
        return path if path and os.path.isdir(path) else None

    def _segment_load_lock(self, table: str, seg_name: str) -> threading.Lock:
        key = (table, seg_name)
        with self._lock:
            lock = self._load_locks.get(key)
            if lock is None:
                lock = self._load_locks[key] = threading.Lock()
            return lock

    def add_local_segment(self, table: str, segment: ImmutableSegment) -> None:
        """Directly register an already-built local segment (used by realtime commit)."""
        self._table_manager(table).add_segment(segment.name, segment)

    def _table_manager(self, table: str) -> TableDataManager:
        with self._lock:
            if table not in self.tables:
                self.tables[table] = TableDataManager(
                    table, os.path.join(self.data_dir, table))
            return self.tables[table]

    # -- query execution ---------------------------------------------------

    #: minimum remaining deadline budget accepted at submit: below this the
    #: queue hop alone would eat the budget, so the query rejects typed (408
    #: with the stamped deadline) instead of enqueueing doomed work
    MIN_DEADLINE_BUDGET_S = 0.005

    #: EWMA smoothing for the per-table bytesFetched estimate
    _BYTES_EWMA_ALPHA = 0.2

    def execute_partial(self, table: str, ctx: Union[str, QueryContext],
                        segment_names: Optional[Sequence[str]] = None,
                        time_filter: Optional[str] = None,
                        sole: bool = False) -> SegmentResult:
        """Run the query over this server's copy of `segment_names`, return the merged
        server-level partial (reference: ServerQueryExecutorV1Impl.processQuery returning
        a DataTable).

        `time_filter` is an optional SQL boolean expression ANDed into the WHERE
        clause — the broker's hybrid-table time-boundary split (reference: the
        brokerRequest's timeBoundary attachment in BaseSingleStageBrokerRequestHandler).
        `sole`: the broker routed the query to this server alone (the request's
        flag over the wire; an in-process broker sets `SOLE_SERVER` on `ctx`).
        """
        schema = self.catalog.schema_for_table(table)
        if isinstance(ctx, str):
            ctx = compile_query(ctx, schema)
        if sole:
            ctx.options[SOLE_SERVER] = True
        if time_filter:
            ctx = _apply_time_filter(ctx, time_filter, schema)
        # graftfault: a crash here dies exactly where a killed process would
        # (the broker's taxonomy sees a transport failure and retries on
        # another replica); slow is the straggler the hedging machinery hunts
        fault_point("server.crash")
        fault_point("server.slow")
        # deadline propagation: the broker stamps deadlineEpochMs from its own
        # timeout budget; a partial that arrives after the caller gave up
        # fails typed NOW instead of burning scheduler and device time on an
        # answer nobody is waiting for
        remaining_s = _deadline_remaining_s(ctx)
        if remaining_s is not None and remaining_s <= self.MIN_DEADLINE_BUDGET_S:
            # admission-time rejection: a query whose budget is already spent
            # (or too thin to survive even the queue hop) fails typed NOW with
            # the stamped deadline attached, so the 408 body tells the caller
            # WHICH deadline was missed instead of burning a scheduler slot
            from ..query.scheduler import QueryTimeoutError
            d_ms = ctx.options.get("deadlineEpochMs") if ctx.options else None
            err = QueryTimeoutError(
                f"query deadline budget exhausted ({remaining_s * 1000:.1f}ms "
                f"remaining, floor {self.MIN_DEADLINE_BUDGET_S * 1000:.0f}ms) "
                f"at {self.instance_id}",
                deadline_epoch_ms=float(d_ms) if d_ms is not None else None)
            raise err
        if self.scheduler is not None:
            timeout_s = None
            t_ms = ctx.options.get("timeoutMs") if ctx.options else None
            if t_ms is not None:
                timeout_s = float(t_ms) / 1000.0
            if remaining_s is not None:
                # the tighter of the per-query budget and the broker deadline
                timeout_s = remaining_s if timeout_s is None \
                    else min(timeout_s, remaining_s)
            # the scheduler's worker thread must see the caller's request trace,
            # seeded at the caller's nesting depth so in-proc spans tree up
            # exactly like HTTP-spliced ones; the submit->run gap is admission
            # queueing — recorded as queue_wait so the hop decomposition never
            # goes queued-blind
            from ..utils.trace import current_depth, current_trace
            tr = current_trace()
            depth = current_depth()
            submit_ms = tr.now_ms() if tr is not None else 0.0

            def run():
                if tr is None:
                    return self._execute_partial(table, ctx, segment_names)
                tr.record("queue_wait", submit_ms, tr.now_ms() - submit_ms,
                          depth=depth)
                with tr.activate(depth=depth):
                    return self._execute_partial(table, ctx, segment_names)
            result = self.scheduler.submit(
                table, run, timeout_s=timeout_s,
                cost_bytes=self._predicted_bytes(table))
            self._observe_bytes(table, result)
            return result
        result = self._execute_partial(table, ctx, segment_names)
        self._observe_bytes(table, result)
        return result

    def _predicted_bytes(self, table: str) -> float:
        """The fair scheduler's per-query byte cost for `table`: the EWMA of
        recent partials' bytesFetched (0.0 until the first completes — an
        unknown tenant is charged the 1.0 base cost only)."""
        with self._lock:
            return self._table_bytes_ewma.get(table, 0.0)

    def _observe_bytes(self, table: str, result) -> None:
        stats = getattr(result, "stats", None)
        if not isinstance(stats, dict):
            return
        try:
            b = float(stats.get(qstats.BYTES_FETCHED, 0.0))
        except (TypeError, ValueError):
            return
        with self._lock:
            prev = self._table_bytes_ewma.get(table)
            # graftcheck: ignore[unbounded-keyed-accumulation] -- one float
            # per table this server hosts (topology-bounded key space)
            self._table_bytes_ewma[table] = b if prev is None else \
                prev + self._BYTES_EWMA_ALPHA * (b - prev)

    def _execute_partial(self, table: str, ctx: QueryContext,
                         segment_names: Optional[Sequence[str]]) -> SegmentResult:
        # per-query telemetry record for this server-level partial: executor /
        # kernel hooks on THIS thread publish into it; pipeline-attributed
        # launch stats arrive attached to the device partial and fold in after
        import time as _t

        from ..utils.trace import span
        t0 = _t.perf_counter()
        with qstats.collect_stats() as st, span("server.execute"):
            merged = self._run_partial(table, ctx, segment_names)
        st.merge(merged.stats)
        st.set_max(qstats.SERVER_TIME_MS, (_t.perf_counter() - t0) * 1000)
        merged.stats = st.to_wire()
        return merged

    def _run_partial(self, table: str, ctx: QueryContext,
                     segment_names: Optional[Sequence[str]]) -> SegmentResult:
        import time as _t

        from ..utils.metrics import get_registry
        from ..utils.trace import span
        reg = get_registry()
        t0 = _t.perf_counter()
        mgr = self._table_manager(table)
        handler = self._realtime_managers.get(table)
        upsert = getattr(handler, "upsert", None) if handler else None
        # acquire, admission and settle: three `server.acquire` spans whose
        # wall is the answer's serverAcquireMs
        with span("server.acquire") as acq:
            segments = mgr.acquire(segment_names)
        acquire_ms = acq.ms
        admitted: List[ImmutableSegment] = []
        settle: List[str] = []
        try:
            # cold tier: requested segments assigned COLD to this server with
            # no local copy lazily download NOW, bounded by the propagated
            # deadline — past-budget loads fail typed instead of stalling
            for seg_name in self._cold_unloaded(table, segment_names, mgr):
                remaining_s = _deadline_remaining_s(ctx)
                if (remaining_s is not None
                        and remaining_s <= self.MIN_DEADLINE_BUDGET_S):
                    from ..query.scheduler import QueryTimeoutError
                    d_ms = ctx.options.get("deadlineEpochMs") \
                        if ctx.options else None
                    raise QueryTimeoutError(
                        f"deadline budget exhausted before cold-tier load of "
                        f"{table}/{seg_name} at {self.instance_id}",
                        deadline_epoch_ms=float(d_ms)
                        if d_ms is not None else None)
                t_load = _t.perf_counter()
                with span(f"coldload:{seg_name}"):
                    self._load_online_segment(table, seg_name, mgr)
                segments.extend(mgr.acquire([seg_name]))
                self.tiering.note_cold_load()
                emit_event("segment.cold.loaded", node=self.instance_id,
                           table=table, segment=seg_name)
                qstats.record(qstats.SEGMENTS_COLD_LOADED, 1)
                qstats.record(qstats.COLD_LOAD_MS,
                              (_t.perf_counter() - t_load) * 1000)

            # HBM admission gate: predict each un-staged block's bytes
            # against the tiering target (evicting colder victims first);
            # rejected segments run the host plan instead of OOMing
            from ..engine.datablock import has_block
            host_tier: List[ImmutableSegment] = []
            with span("server.acquire") as acq:
                for seg in segments:
                    fresh = not has_block(seg)
                    if self.tiering.admit(table, seg, mgr):
                        admitted.append(seg)
                        if fresh:
                            self.tiering.note_promotion()
                            emit_event("tier.promoted", node=self.instance_id,
                                       table=table,
                                       segment=getattr(seg, "name", ""))
                            qstats.record(qstats.TIER_PROMOTIONS, 1)
                    else:
                        host_tier.append(seg)
                # pre-screened on THIS thread: only shapes that CAN plan on
                # device enter the pipeline — everything else goes straight
                # to the host loop instead of waiting out the pipeline's
                # batch-accumulation window for a FALLBACK verdict. DISTINCT
                # rewrites to a group-by, which plans on device; ORDER-BY-
                # limit selections ride the fused top-k kernel when the
                # screen admits them (single-column order, bounded k)
                on_device = (self.device_pipeline is not None and admitted
                             and upsert is None
                             and (ctx.aggregations or ctx.distinct
                                  or device_topk_screen(ctx)))
                if on_device:
                    # what is staged and planned is the table's RESIDENT
                    # set: every immutable segment this server holds of it
                    # that the admission gate lets onto the device, in push
                    # order. The admitted members of the routed set are an
                    # input of the launch, so a pruned query builds no block
                    # of its own
                    routed = {seg.name for seg in admitted}
                    rejected = {seg.name for seg in host_tier}
                    resident = [
                        seg for seg in mgr.resident()
                        if seg.name in routed
                        or (seg.name not in rejected
                            and not getattr(seg, "is_mutable", False)
                            and self.tiering.admit(table, seg, mgr))]
                    settle = [seg.name for seg in resident]
            acquire_ms += acq.ms
            if host_tier:
                qstats.record(qstats.SEGMENTS_SERVED_HOST_TIER,
                              len(host_tier))

            results = []
            device_partial = None
            if on_device and ctx.options.get(SOLE_SERVER) and (
                    host_tier or handler is not None
                    or (segment_names is not None
                        and set(segment_names) - {s.name for s in segments})):
                # other parts join this partial (host-tier or consuming
                # segments), or a routed segment is missing: not the whole
                # answer, so no cut of the ORDER BY ... LIMIT on the device
                ctx = dataclasses.replace(ctx, options={
                    k: v for k, v in ctx.options.items() if k != SOLE_SERVER})
            if on_device:
                # device path: ONE server-level partial for the whole set,
                # executed on the mesh with batched fetches; falls back per
                # segment below when the plan can't ride the device (upsert
                # valid masks always take the host path — per-doc visibility
                # is host state)
                from .device_server import DEVICE_FALLBACK
                with span("device"):
                    try:
                        out = self.device_pipeline.execute_partial(
                            ctx, admitted, resident)
                    except Exception:
                        # fetch/decode raised on the device path: the host
                        # answers, the pipeline logs and counts the error
                        self.device_pipeline.record_error("execute_partial")
                        out = DEVICE_FALLBACK
                if out is not DEVICE_FALLBACK:
                    device_partial = out
                    reg.counter("pinot_server_device_queries",
                                {"table": table}).inc()
            if device_partial is not None:
                results.append(device_partial)
                # the pipeline's threads can't attribute per-query segment
                # counts (they serve many queries per launch) — account the
                # set here, on the query's own thread
                qstats.record(qstats.NUM_SEGMENTS_QUERIED, len(admitted))
                if (device_partial.num_docs_scanned > 0
                        or device_partial.groups or device_partial.rows
                        or device_partial.dense is not None):
                    qstats.record(qstats.NUM_SEGMENTS_MATCHED, len(admitted))
                # unadmitted segments still answer — on the host plan
                for seg in host_tier:
                    with span(f"segment:{seg.name}"):
                        valid = upsert.valid_mask(seg.name, seg.num_docs) \
                            if upsert else None
                        results.append(self.host_executor.execute_segment(
                            ctx, seg, valid))
            else:
                admitted_names = {seg.name for seg in admitted}
                for seg in segments:
                    with span(f"segment:{seg.name}"):
                        valid = upsert.valid_mask(seg.name, seg.num_docs) \
                            if upsert else None
                        ex = self.executor if seg.name in admitted_names \
                            else self.host_executor
                        results.append(ex.execute_segment(ctx, seg, valid))
            # include in-progress realtime docs when a consuming manager exists
            served = [seg.name for seg in segments]
            if handler is not None:
                with span("consuming"):
                    rt_results, rt_served = handler.consuming_results(
                        ctx, segment_names, exclude=set(served))
                results.extend(rt_results)
                served.extend(rt_served)
                if rt_served:
                    # consuming-segment visibility (reference: the broker
                    # response's numConsumingSegmentsQueried +
                    # minConsumingFreshnessTimeMs pair): freshness is the min
                    # across the consuming segments THIS partial touched —
                    # the broker min-merges across servers
                    qstats.record(qstats.NUM_CONSUMING_SEGMENTS_QUERIED,
                                  len(rt_served))
                    fresh = handler.min_freshness_ms(rt_served)
                    if fresh is not None:
                        qstats.record_min(
                            qstats.MIN_CONSUMING_FRESHNESS_TIME_MS, fresh)
        finally:
            # reservations made by THIS query's admissions are settled: a
            # block either staged (the ledger counts it now) or never will
            # until another query re-admits it
            with span("server.acquire") as acq:
                self.tiering.settle(settle or [seg.name for seg in admitted])
                mgr.release(segments)
            qstats.record(qstats.SERVER_ACQUIRE_MS, acquire_ms + acq.ms)
        aggs = [make_agg(f) for f in ctx.aggregations]
        with span("server.merge") as merging:
            merged = merge_segment_results(results, aggs)
        qstats.record(qstats.SERVER_MERGE_MS, merging.ms)
        merged.served = served
        # ServerMeter QUERIES / NUM_DOCS_SCANNED / NUM_SEGMENTS_QUERIED analogs
        reg.counter("pinot_server_queries", {"table": table}).inc()
        reg.counter("pinot_server_docs_scanned").inc(merged.num_docs_scanned)
        reg.counter("pinot_server_segments_queried").inc(len(segments))
        reg.timer("pinot_server_query_latency_ms").update(
            (_t.perf_counter() - t0) * 1000)
        return merged

    def explain_partial(self, table: str, ctx: Union[str, QueryContext],
                        segment_names: Optional[Sequence[str]] = None) -> List[List]:
        """EXPLAIN rows over this server's copy of the segments (reference: v2
        explain asks servers for their operator plans)."""
        from ..query.explain import explain_result
        schema = self.catalog.schema_for_table(table)
        if isinstance(ctx, str):
            ctx = compile_query(ctx, schema)
        mgr = self._table_manager(table)
        segments = mgr.acquire(segment_names)
        try:
            return explain_result(ctx, segments, table=table).rows
        finally:
            mgr.release(segments)

    def segments_served(self, table: str) -> List[str]:
        return self._table_manager(table).segment_names

    @staticmethod
    def apply_time_filter(ctx: QueryContext, time_filter: str, schema) -> QueryContext:
        return _apply_time_filter(ctx, time_filter, schema)


def _deadline_remaining_s(ctx: QueryContext) -> Optional[float]:
    """Seconds left until the broker-stamped absolute deadline
    (`deadlineEpochMs` query option), or None when no deadline rode in.
    Negative means the caller already gave up on this query."""
    d_ms = ctx.options.get("deadlineEpochMs") if ctx.options else None
    if d_ms is None:
        return None
    import time
    return float(d_ms) / 1000.0 - time.time()


def _apply_time_filter(ctx: QueryContext, time_filter: str, schema) -> QueryContext:
    """AND a SQL boolean expression (the broker's hybrid time-boundary predicate)
    into the context's WHERE tree, reusing the normal compile pipeline so the
    predicate is normalized exactly like a user-written one."""
    import dataclasses
    from ..sql.ast import Function
    from ..sql.parser import parse_query
    dummy = parse_query(f"SELECT * FROM t WHERE {time_filter}")
    tf = compile_query(dummy, schema).filter
    new_filter = tf if ctx.filter is None else Function("and", (ctx.filter, tf))
    return dataclasses.replace(ctx, filter=new_filter)
