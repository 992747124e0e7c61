"""Deterministic DeviceQueryPipeline batching tests (no real device work).

A fake mesh executor with controllable latency and full call recording
proves the pipeline's scheduling contract WITHOUT racing on real kernel
times: (a) concurrent submissions coalesce into ONE host fetch, (b) a
timed-out caller's future is never dispatched or fetched, (c) shape-keyed
reuse launches ONE executable for N same-shape queries (stacked) and
collapses byte-identical queries to one dispatch (dedupe). A final smoke
test runs the REAL executor end-to-end on the CPU mesh and asserts more than
one query a batch, so served-path batching can never silently regress to
one-query-per-round-trip.

Reference: QueryScheduler.java:56 bounds per-server concurrency; here the
pipeline converts that concurrency into batched device round trips.
"""

import threading
import time

import numpy as np
import pytest

from pinot_tpu.cluster import QuickCluster, device_server
from pinot_tpu.cluster.device_server import (DEVICE_FALLBACK,
                                             DeviceQueryPipeline, _Item)
from pinot_tpu.table import TableConfig

from conftest import make_ssb_columns


class FakePrepared:
    """Duck-typed PreparedDispatch: only the fields the pipeline reads."""

    def __init__(self, shape, literal, decoded):
        self.kind = "agg"
        self.stackable = True
        self.stack_key = ("shape", shape)
        self.dedupe_key = ("shape", shape, literal)
        self.decode = lambda outs, d=decoded: (d, outs)


class FakeMeshExec:
    """Prepared-API fake: ctx is a dict {shape, literal, fallback?}."""

    def __init__(self, fetch_latency: float = 0.0):
        self.fetch_latency = fetch_latency
        self.prepared = []        # ctxs that reached prepare_partial
        self.launched_keys = []   # one stack_key per kernel launch
        self.fetch_calls = []     # number of trees per fetch() call
        self.fetch_started = threading.Event()
        self.recorded = {}        # stack_key -> what that launch "recorded"

    def prepare_partial(self, ctx, segments, resident=None):
        self.prepared.append(ctx)
        if ctx.get("fallback"):
            return None
        return FakePrepared(ctx["shape"], ctx["literal"],
                            ("res", ctx["shape"], ctx["literal"]))

    def dispatch_prepared(self, reps):
        groups = {}
        order = []
        for i, p in enumerate(reps):
            key = p.stack_key if p.stackable else ("solo", i)
            if key not in groups:
                groups[key] = []
                order.append(key)
            groups[key].append(i)
        launches = []
        for key in order:
            idxs = groups[key]
            self.launched_keys.append(key)
            outs_dev = {"launch": len(self.launched_keys), "n": len(idxs)}
            launches.append((outs_dev,
                             lambda host, n=len(idxs): [host] * n, idxs,
                             dict(self.recorded.get(key, {}))))
        return launches

    def fetch(self, trees):
        self.fetch_started.set()
        if self.fetch_latency:
            time.sleep(self.fetch_latency)
        self.fetch_calls.append(len(trees))
        return trees


def _submit_concurrently(pipeline, ctxs):
    """Queue every ctx from its own thread against a NOT-started pipeline,
    wait until all are queued, then start — one deterministic drain."""
    results = [None] * len(ctxs)

    def run(i):
        results[i] = pipeline.execute_partial(ctxs[i], [])

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(ctxs))]
    for t in threads:
        t.start()
    deadline = time.time() + 5
    while pipeline._q.qsize() < len(ctxs) and time.time() < deadline:
        time.sleep(0.005)
    assert pipeline._q.qsize() == len(ctxs)
    pipeline.start()
    for t in threads:
        t.join(timeout=10)
    return results


def test_concurrent_submissions_coalesce_into_one_fetch():
    fake = FakeMeshExec()
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
    try:
        ctxs = [{"shape": "A", "literal": i} for i in range(6)]
        results = _submit_concurrently(pipeline, ctxs)
        assert results == [(("res", "A", i), {"launch": 1, "n": 6})
                           for i in range(6)]
        # six queries, one drain, ONE host fetch for the whole batch
        assert len(fake.fetch_calls) == 1
        s = pipeline.stats()
        assert (s["batches"], s["dispatched"], s["batchesOfOne"]) == (1, 6, 0)
    finally:
        pipeline.stop()


def test_timed_out_future_not_dispatched_or_fetched():
    fake = FakeMeshExec()
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
    try:
        stale = _Item({"shape": "A", "literal": 0}, [])
        stale.future.cancel()  # caller timed out while still queued
        live = _Item({"shape": "A", "literal": 1}, [])
        pipeline._q.put(stale)
        pipeline._q.put(live)
        pipeline.start()
        assert live.future.result(timeout=10)[0] == ("res", "A", 1)
        # the cancelled item never reached the executor at all
        assert fake.prepared == [{"shape": "A", "literal": 1}]
        assert pipeline.dispatched == 1
    finally:
        pipeline.stop()


def test_timeout_mid_fetch_skips_decode():
    fake = FakeMeshExec(fetch_latency=0.5)
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
    try:
        decoded = []
        a = _Item({"shape": "A", "literal": 0}, [])
        b = _Item({"shape": "B", "literal": 1}, [])
        pipeline._q.put(a)
        pipeline._q.put(b)
        pipeline.start()
        assert fake.fetch_started.wait(timeout=5)
        a.future.cancel()  # times out while the batched fetch is in flight
        got_b = b.future.result(timeout=10)
        assert got_b[0] == ("res", "B", 1)
        assert a.future.cancelled()
    finally:
        pipeline.stop()


def test_all_timed_out_launches_never_fetched():
    fake = FakeMeshExec()
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
    try:
        a = _Item({"shape": "A", "literal": 0}, [])
        b = _Item({"shape": "A", "literal": 1}, [])
        # dispatch on the calling thread (threads not running yet), then
        # cancel BOTH callers before the fetcher ever sees the entry
        entry, n = pipeline._dispatch_grouped([a, b])
        assert n == 2 and entry
        a.future.cancel()
        b.future.cancel()
        pipeline._fetchq.put((entry, time.perf_counter()))
        pipeline.start()
        time.sleep(0.3)
        # the dead batch was dropped WITHOUT paying a host round trip
        assert fake.fetch_calls == []
    finally:
        pipeline.stop()


def test_shape_keyed_reuse_one_executable_for_n_queries():
    fake = FakeMeshExec()
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
    try:
        # five same-shape (different literal), one different shape, one
        # byte-identical duplicate of the first
        ctxs = ([{"shape": "A", "literal": i} for i in range(5)]
                + [{"shape": "B", "literal": 99}]
                + [{"shape": "A", "literal": 0}])
        results = _submit_concurrently(pipeline, ctxs)
        assert all(r is not DEVICE_FALLBACK for r in results)
        # 7 queries -> 6 dedupe groups -> 2 launches (A stacked, B solo)
        assert len(fake.launched_keys) == 2
        assert set(fake.launched_keys) == {("shape", "A"), ("shape", "B")}
        s = pipeline.stats()
        assert s["dispatched"] == 7
        assert s["launches"] == 2
        assert s["dedupeHits"] == 1
        assert s["stackedLaunches"] == 1
        # the duplicate decoded from the SAME launch result as the original
        assert results[6] == results[0]
    finally:
        pipeline.stop()


def test_fallback_and_stage_timings():
    fake = FakeMeshExec()
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
    try:
        results = _submit_concurrently(
            pipeline, [{"shape": "A", "literal": 1},
                       {"shape": "A", "literal": 2, "fallback": True}])
        assert results[0][0] == ("res", "A", 1)
        assert results[1] is DEVICE_FALLBACK
        s = pipeline.stats()
        assert s["fallbacks"] == 1
        # one live query left in the drain
        assert (s["batches"], s["dispatched"], s["batchesOfOne"]) == (1, 1, 1)
        assert "stageMs" not in s and "meanBatch" not in s
    finally:
        pipeline.stop()


class _Boom(RuntimeError):
    pass


class _RaisingPrepare(FakeMeshExec):
    def prepare_partial(self, ctx, segments, resident=None):
        if ctx.get("boom"):
            raise _Boom("prepare")
        return super().prepare_partial(ctx, segments)


class _RaisingDispatch(FakeMeshExec):
    def dispatch_prepared(self, reps):
        raise _Boom("dispatch")


class _RaisingLegacy:
    def dispatch_partial(self, ctx, segments):
        raise _Boom("legacy")


@pytest.mark.parametrize("fake,where", [
    (_RaisingPrepare, "prepare_partial"),
    (_RaisingDispatch, "dispatch_prepared"),
    (_RaisingLegacy, "dispatch_partial"),
])
def test_device_exception_is_logged_and_counted_apart(fake, where, caplog):
    """The device path RAISING is not a plan fallback: the host still answers
    (DEVICE_FALLBACK), but the traceback is logged and `deviceErrors` counts
    it; a None plan increments only `fallbacks`."""
    pipeline = DeviceQueryPipeline(mesh_exec=fake(), start=False)
    try:
        with caplog.at_level("ERROR", logger="pinot_tpu.cluster.device_server"):
            results = _submit_concurrently(
                pipeline, [{"shape": "A", "literal": 1, "boom": True}])
        assert results[0] is DEVICE_FALLBACK
        s = pipeline.stats()
        assert s["deviceErrors"] == 1 and s["fallbacks"] == 0
        rec = [r for r in caplog.records if where in r.getMessage()]
        assert rec and rec[0].exc_info and rec[0].exc_info[0] is _Boom
    finally:
        pipeline.stop()
    if fake is _RaisingLegacy:
        return
    quiet = DeviceQueryPipeline(mesh_exec=FakeMeshExec(), start=False)
    try:
        results = _submit_concurrently(
            quiet, [{"shape": "A", "literal": 1, "fallback": True}])
        assert results[0] is DEVICE_FALLBACK
        s = quiet.stats()
        assert s["deviceErrors"] == 0 and s["fallbacks"] == 1
    finally:
        quiet.stop()


def test_legacy_executor_without_prepared_api():
    class LegacyExec:
        def __init__(self):
            self.calls = 0

        def dispatch_partial(self, ctx, segments):
            self.calls += 1
            if ctx.get("fallback"):
                return None
            return {"x": ctx["literal"]}, (lambda outs: ("legacy",
                                                         outs["x"]))

    legacy = LegacyExec()
    pipeline = DeviceQueryPipeline(mesh_exec=legacy, start=False)
    try:
        results = _submit_concurrently(
            pipeline, [{"literal": 7}, {"literal": 8, "fallback": True}])
        assert results[0] == ("legacy", 7)
        assert results[1] is DEVICE_FALLBACK
        assert legacy.calls == 2
    finally:
        pipeline.stop()


def test_smoke_real_executor_mean_batch_gt_one(tmp_path, ssb_schema):
    """CI smoke (tier-1, CPU mesh): a real QuickCluster + real
    MeshQueryExecutor under a small concurrent workload MUST batch — more
    than one query a batch, or the served path has regressed to one query per
    round trip."""
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline(start=False)
    cluster.servers[0].device_pipeline = pipeline
    rng = np.random.default_rng(11)
    cfg = TableConfig(ssb_schema.name)
    cluster.create_table(ssb_schema, cfg)
    cluster.ingest_columns(cfg, make_ssb_columns(rng, 1500))
    try:
        sqls = [("SELECT COUNT(*), SUM(lo_revenue) FROM lineorder "
                 f"WHERE lo_quantity >= {q}") for q in (5, 15, 25, 35)]
        results = [None] * len(sqls)

        def run(i):
            results[i] = cluster.query(sqls[i])

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(sqls))]
        for t in threads:
            t.start()
        deadline = time.time() + 10
        while pipeline._q.qsize() < len(sqls) and time.time() < deadline:
            time.sleep(0.01)
        pipeline.start()
        for t in threads:
            t.join(timeout=60)
        s = pipeline.stats()
        assert s["dispatched"] == len(sqls)
        assert s["dispatched"] > s["batches"], s
        assert s["batchesOfOne"] < s["batches"], s
        assert all(r is not None and r.rows for r in results)
    finally:
        pipeline.stop()


# -- PR 26: per-query phase fields, drain counters, compile attribution -------

class FakePartial:
    """What a decode returns on the served path: something with `.stats`."""

    def __init__(self, tag):
        self.tag = tag
        self.stats = None


class StatsMeshExec(FakeMeshExec):
    """FakeMeshExec whose phases take a known time and whose results carry
    stats, as a SegmentResult does."""

    def __init__(self, prepare_s=0.0, launch_s=0.0, fetch_latency=0.0):
        super().__init__(fetch_latency=fetch_latency)
        self.prepare_s, self.launch_s = prepare_s, launch_s

    def prepare_partial(self, ctx, segments, resident=None):
        time.sleep(self.prepare_s)
        p = super().prepare_partial(ctx, segments)
        if p is not None:
            p.decode = lambda outs, c=ctx: FakePartial(c["literal"])
        return p

    def dispatch_prepared(self, reps):
        time.sleep(self.launch_s)
        return super().dispatch_prepared(reps)


PHASE_KEYS = ("queueWaitMs", "devicePrepareMs", "deviceLaunchMs",
              "deviceHandoffMs", "deviceFetchMs", "deviceDecodeMs")


def test_every_phase_field_reaches_the_partial_and_fits_the_wall():
    """A query the pipeline answers carries every phase of its way through
    it, and the phases never add up to more than the wall the caller saw."""
    fake = StatsMeshExec(prepare_s=0.01, launch_s=0.01, fetch_latency=0.02)
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
    try:
        t0 = time.perf_counter()
        results = _submit_concurrently(
            pipeline, [{"shape": "A", "literal": 1},
                       {"shape": "B", "literal": 2}])
        wall_ms = (time.perf_counter() - t0) * 1000
        for r in results:
            s = r.stats
            for key in PHASE_KEYS:
                assert key in s, key
            assert s["devicePrepareMs"] >= 9.0      # its own prepare only
            assert s["deviceLaunchMs"] >= 9.0       # the drain's, shared
            assert s["deviceFetchMs"] >= 19.0
            assert s["deviceHandoffMs"] >= 0.0 and s["deviceDecodeMs"] >= 0.0
            assert s["deviceBatchSize"] == 2
            assert sum(s[k] for k in PHASE_KEYS) <= wall_ms
        # the launch and the fetch are the batch's: the same for both
        assert results[0].stats["deviceLaunchMs"] == \
            results[1].stats["deviceLaunchMs"]
        assert results[0].stats["deviceFetchMs"] == \
            results[1].stats["deviceFetchMs"]
    finally:
        pipeline.stop()


def test_execute_partial_rebuilds_every_phase_in_the_request_trace():
    """/debug/traces and OPTION(trace=true) show the whole pipeline: one
    `pipeline:<phase>` span a field, end to end from the submit."""
    from pinot_tpu.utils.trace import Trace
    fake = StatsMeshExec(prepare_s=0.005, fetch_latency=0.005)
    pipeline = DeviceQueryPipeline(mesh_exec=fake)
    tr = Trace("r")
    try:
        with tr.activate():
            r = pipeline.execute_partial({"shape": "A", "literal": 1}, [])
        rows = [s for s in tr.to_rows() if s["name"].startswith("pipeline:")]
        assert [s["name"] for s in rows] == [
            "pipeline:queue_wait", "pipeline:prepare", "pipeline:launch",
            "pipeline:handoff", "pipeline:fetch", "pipeline:decode"]
        for row, key in zip(rows, PHASE_KEYS):
            assert row["durationMs"] == pytest.approx(r.stats[key], abs=2e-3)
        for a, b in zip(rows, rows[1:]):
            assert b["startMs"] == pytest.approx(
                a["startMs"] + a["durationMs"], abs=5e-3)
    finally:
        pipeline.stop()


def _queued(pipeline, n):
    for i in range(n):
        pipeline._q.put(_Item({"shape": "A", "literal": i}, []))


@pytest.mark.parametrize("reason,kwargs,queued,drained", [
    ("drainsClosedIdle", dict(), 2, 2),
    ("drainsClosedFull", dict(max_batch=2), 3, 2),
    ("drainsClosedBurst", dict(burst_window_s=0.03), 1, 1),
])
def test_why_a_drain_closed_is_counted(reason, kwargs, queued, drained):
    """Each reason `_drain` has for closing a batch has its own counter,
    driven here by a constructed queue with no thread running."""
    pipeline = DeviceQueryPipeline(mesh_exec=FakeMeshExec(), start=False,
                                   **kwargs)
    _queued(pipeline, queued)
    t0 = time.perf_counter()
    batch = pipeline._drain()
    assert len(batch) == drained
    if reason == "drainsClosedBurst":
        assert time.perf_counter() - t0 >= 0.03   # the window held it open
    s = pipeline.stats()
    closed = {k: v for k, v in s.items() if k.startswith("drainsClosed")}
    assert closed == {"drainsClosedIdle": 0, "drainsClosedFull": 0,
                      "drainsClosedBurst": 0, reason: 1}
    # an empty queue is no drain at all
    if queued == drained:
        assert pipeline._drain() is None
        assert sum(v for k, v in pipeline.stats().items()
                   if k.startswith("drainsClosed")) == 1


def test_a_fetch_in_flight_keeps_the_drain_open():
    """While the fetcher is busy the drain goes on gathering: what arrives
    meanwhile rides the same batch, and it closes idle once the fetch ends."""
    pipeline = DeviceQueryPipeline(mesh_exec=FakeMeshExec(), start=False)
    _queued(pipeline, 1)
    pipeline._fetch_busy.set()

    def late():
        time.sleep(0.03)
        _queued(pipeline, 1)
        time.sleep(0.03)
        pipeline._fetch_busy.clear()
    t = threading.Thread(target=late)
    t.start()
    batch = pipeline._drain()
    t.join()
    assert len(batch) == 2
    assert pipeline.stats()["drainsClosedIdle"] == 1


@pytest.mark.parametrize("n,ones", [(1, 1), (3, 0)])
def test_batches_of_one_counts_drains_with_one_live_query(n, ones):
    pipeline = DeviceQueryPipeline(mesh_exec=FakeMeshExec(), start=False)
    try:
        _submit_concurrently(
            pipeline, [{"shape": "A", "literal": i} for i in range(n)])
        s = pipeline.stats()
        assert (s["batches"], s["dispatched"], s["batchesOfOne"]) == \
            (1, n, ones)
    finally:
        pipeline.stop()


def test_handoff_blocked_counts_a_put_that_met_a_full_fetch_queue():
    """max_batch=1 closes every drain at once; with one slot in the fetch
    queue and a slow fetch, the third launch finds the slot taken."""
    fake = StatsMeshExec(fetch_latency=0.15)
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False, max_batch=1,
                                   max_inflight=1)
    try:
        results = _submit_concurrently(
            pipeline, [{"shape": "A", "literal": i} for i in range(3)])
        s = pipeline.stats()
        assert s["batches"] == 3 and s["drainsClosedFull"] == 3
        assert s["handoffBlocked"] >= 1
        # the blocked hand-off is time the queries of that batch waited
        assert max(r.stats["deviceHandoffMs"] for r in results) >= 100.0
    finally:
        pipeline.stop()


def test_what_a_launch_recorded_goes_to_the_queries_it_answers():
    """The dispatcher folds what each launch recorded (the kernel cache, the
    compile fence) into the items that launch answers, and no others."""
    fake = StatsMeshExec()
    fake.recorded = {("shape", "cold"): {"compileMs": 12.5,
                                         "compileCacheMisses": 1,
                                         "deviceLaunches": 1},
                     ("shape", "warm"): {"compileCacheHits": 1,
                                         "deviceLaunches": 1}}
    pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
    try:
        cold, warm = _submit_concurrently(
            pipeline, [{"shape": "cold", "literal": 1},
                       {"shape": "warm", "literal": 2}])
        assert cold.stats["compileMs"] == 12.5
        assert cold.stats["compileCacheMisses"] == 1
        assert "compileCacheHits" not in cold.stats
        assert warm.stats.get("compileMs", 0) == 0
        assert warm.stats.get("compileCacheMisses", 0) == 0
        assert warm.stats["compileCacheHits"] == 1
        assert cold.stats["deviceLaunches"] == warm.stats["deviceLaunches"] == 1
    finally:
        pipeline.stop()


def test_cold_kernel_says_so_in_its_own_response_not_its_neighbours(
        tmp_path, ssb_schema):
    """Real executor, CPU mesh: two queries ride one drain, one of a shape
    the process has compiled, one of a shape it has not. The cold one's
    response carries compileCacheMisses >= 1 and compileMs > 0; its
    neighbour's carries 0 of both."""
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cluster.servers[0].device_pipeline = warm_up = DeviceQueryPipeline()
    rng = np.random.default_rng(26)
    cfg = TableConfig(ssb_schema.name)
    cluster.create_table(ssb_schema, cfg)
    cluster.ingest_columns(cfg, make_ssb_columns(rng, 1500))
    warm_sql = ("SELECT COUNT(*), SUM(lo_revenue) FROM lineorder "
                "WHERE lo_quantity >= 7")
    cold_sql = ("SELECT lo_quantity, MAX(lo_revenue), MIN(lo_revenue) "
                "FROM lineorder WHERE lo_quantity < 31 GROUP BY lo_quantity")
    try:
        first = cluster.query(warm_sql)
        assert first.stats["deviceLaunches"] >= 1
    finally:
        warm_up.stop()
    pipeline = DeviceQueryPipeline(start=False)
    cluster.servers[0].device_pipeline = pipeline
    try:
        results = {}

        def run(sql):
            results[sql] = cluster.query(sql)
        threads = [threading.Thread(target=run, args=(sql,))
                   for sql in (warm_sql, cold_sql)]
        for t in threads:
            t.start()
        deadline = time.time() + 10
        while pipeline._q.qsize() < 2 and time.time() < deadline:
            time.sleep(0.01)
        pipeline.start()
        for t in threads:
            t.join(timeout=120)
        assert pipeline.stats()["batches"] == 1
        cold, warm = results[cold_sql].stats, results[warm_sql].stats
        assert cold["compileCacheMisses"] >= 1 and cold["compileMs"] > 0
        assert warm["compileCacheMisses"] == 0 and warm["compileMs"] == 0
        assert warm["compileCacheHits"] >= 1
        for s in (cold, warm):
            assert s["deviceBatchSize"] == 2 and s["deviceLaunches"] >= 1
            for key in PHASE_KEYS + ("serverTimeMs",):
                assert key in s, key
            assert sum(s[k] for k in PHASE_KEYS) <= s["serverTimeMs"]
    finally:
        pipeline.stop()


# -- PR 38: the hop's pieces timed where they run ------------------------------

HOP_KEYS = ("devicePrepareCpuMs", "deviceLaunchCpuMs", "devicePlanMs",
            "deviceInputsMs", "deviceWakeMs", "serverAcquireMs",
            "serverMergeMs")


def test_served_answer_carries_the_hops_pieces(tmp_path, ssb_schema):
    """Real executor, CPU mesh: a served answer carries the pieces that were
    found by subtraction, each timed where it ran. Acquire, merge and the
    handler's wake-up fit in what `server.host_ms` subtracts; the prepare's
    two halves fit in the prepare; CPU never exceeds its wall."""
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline()
    cluster.servers[0].device_pipeline = pipeline
    cfg = TableConfig(ssb_schema.name)
    cluster.create_table(ssb_schema, cfg)
    cluster.ingest_columns(cfg, make_ssb_columns(np.random.default_rng(38),
                                                 1500))
    try:
        for sql in ("SELECT COUNT(*), SUM(lo_revenue) FROM lineorder "
                    "WHERE lo_quantity >= 7",
                    "SELECT lo_quantity, SUM(lo_revenue) FROM lineorder "
                    "GROUP BY lo_quantity"):
            cluster.query(sql)                  # compiled, then served warm
            s = cluster.query(sql).stats
            assert s["deviceLaunches"] >= 1
            for key in HOP_KEYS:
                assert key in s, key
            assert s["deviceWakeMs"] >= 0.0
            host_ms = s["serverTimeMs"] - sum(s[k] for k in PHASE_KEYS)
            assert (s["serverAcquireMs"] + s["serverMergeMs"]
                    + s["deviceWakeMs"]) <= host_ms + 0.01
            assert s["serverAcquireMs"] > 0.0 and s["serverMergeMs"] > 0.0
            assert 0.0 < s["devicePlanMs"] + s["deviceInputsMs"] \
                <= s["devicePrepareMs"] + 0.01
            assert s["devicePrepareCpuMs"] <= s["devicePrepareMs"] + 0.5
            assert s["deviceLaunchCpuMs"] <= s["deviceLaunchMs"] + 0.5
    finally:
        pipeline.stop()


def test_wake_is_measured_from_the_resolve_on_the_handlers_side():
    """deviceWakeMs runs from the fetcher's resolve to the caller back from
    the future: a fake answer resolved at a known time reads the gap."""
    fake = StatsMeshExec(fetch_latency=0.005)
    pipeline = DeviceQueryPipeline(mesh_exec=fake)
    try:
        r = pipeline.execute_partial({"shape": "A", "literal": 1}, [])
        assert 0.0 <= r.stats["deviceWakeMs"] < 1000.0
        assert r.stats["deviceLaunchCpuMs"] <= r.stats["deviceLaunchMs"] + 0.5
        assert r.stats["devicePrepareCpuMs"] <= \
            r.stats["devicePrepareMs"] + 0.5
    finally:
        pipeline.stop()


# -- prepared while the batch before is fetched; woken by its end -----------

class HeldFetchExec(StatsMeshExec):
    """Every fetch waits until `release` is set: what is submitted after the
    first fetch started arrives while a fetch is in flight. Each prepare
    says whether it ran while that fetch was held."""

    def __init__(self):
        super().__init__()
        self.release = threading.Event()
        self.prepared_while_held = []

    def prepare_partial(self, ctx, segments, resident=None):
        self.prepared_while_held.append(self.fetch_started.is_set()
                                        and not self.release.is_set())
        if ctx.get("boom"):
            raise _Boom("prepare")
        return super().prepare_partial(ctx, segments)

    def fetch(self, trees):
        self.fetch_started.set()
        assert self.release.wait(timeout=10)
        return super().fetch(trees)


def _submit(pipeline, ctxs):
    """Each ctx from a thread of its own, at once; (threads, results)."""
    results = [None] * len(ctxs)

    def run(i):
        results[i] = pipeline.execute_partial(ctxs[i], [])
    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(ctxs))]
    for t in threads:
        t.start()
    return threads, results


def _wait_for(cond, timeout=5.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.002)
    return cond()


def _join(threads):
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()


def _hold_a_fetch(pipeline, fake):
    """Launch one query of its own shape and hold its fetch open."""
    head, _ = _submit(pipeline, [{"shape": "head", "literal": 0}])
    assert fake.fetch_started.wait(timeout=5)
    return head


DRAIN = [{"shape": "A", "literal": 0}, {"shape": "A", "literal": 1},
         {"shape": "B", "literal": 9}, {"shape": "A", "literal": 0}]


def _one_drain(ctxs, ahead):
    """The batch of `ctxs` formed while a fetch is in flight (`ahead`) or
    queued before the pipeline starts: what it launched and answered."""
    fake = HeldFetchExec()
    if not ahead:
        fake.release.set()
        pipeline = DeviceQueryPipeline(mesh_exec=fake, start=False)
        try:
            results = _submit_concurrently(pipeline, ctxs)
            return fake, pipeline.stats(), results
        finally:
            pipeline.stop()
    pipeline = DeviceQueryPipeline(mesh_exec=fake)
    try:
        head = _hold_a_fetch(pipeline, fake)
        t0 = time.perf_counter()
        threads, results = _submit(pipeline, ctxs)
        assert _wait_for(lambda: len(fake.prepared) == 1 + len(ctxs))
        time.sleep(0.05)
        # every item that arrived during the fetch is prepared before it ends
        assert fake.prepared_while_held == [False] + [True] * len(ctxs)
        assert fake.launched_keys == [("shape", "head")]
        fake.release.set()
        _join(head + threads)
        wall_ms = (time.perf_counter() - t0) * 1000
        for r in results:
            # the phases still tile: waiting out the held fetch is queue wait
            assert r.stats["queueWaitMs"] >= 40.0
            assert sum(r.stats[k] for k in PHASE_KEYS) <= wall_ms
        # ... and none is prepared again when the drain closes
        assert fake.prepared == [{"shape": "head", "literal": 0}] + ctxs
        del fake.launched_keys[0], fake.fetch_calls[0]
        return fake, pipeline.stats(), results
    finally:
        pipeline.stop()


def test_a_batch_prepared_during_the_fetch_launches_as_one_prepared_at_close():
    """Items submitted while the fetch before is held are prepared before it
    is released; they then launch as ONE batch with the grouping and dedupe
    of the same items queued and prepared when the drain closes. Only the
    early prepares count in `preparedAhead`."""
    late, s_late, r_late = _one_drain(DRAIN, ahead=True)
    close, s_close, r_close = _one_drain(DRAIN, ahead=False)
    assert late.launched_keys == close.launched_keys == [("shape", "A"),
                                                         ("shape", "B")]
    assert late.fetch_calls == close.fetch_calls == [2]
    for key in ("dedupeHits", "stackedLaunches"):
        assert s_late[key] == s_close[key], key
    assert (s_late["batches"], s_late["dispatched"]) == (2, 5)
    assert (s_close["batches"], s_close["dispatched"]) == (1, 4)
    assert [r.tag for r in r_late] == [r.tag for r in r_close] == [0, 1, 9, 0]
    assert all(r.stats["deviceBatchSize"] == 4 for r in r_late + r_close)
    assert r_late[3].stats["dedupedLaunches"] == 1
    assert s_late["preparedAhead"] == 4 and s_close["preparedAhead"] == 0


def test_an_item_cancelled_after_its_early_prepare_is_not_launched():
    fake = HeldFetchExec()
    pipeline = DeviceQueryPipeline(mesh_exec=fake)
    try:
        head = _hold_a_fetch(pipeline, fake)
        stale = _Item({"shape": "stale", "literal": 1}, [])
        pipeline._q.put(stale)
        threads, results = _submit(pipeline, [{"shape": "B", "literal": 2}])
        assert _wait_for(lambda: len(fake.prepared) == 3)
        stale.future.cancel()       # its caller timed out after the prepare
        fake.release.set()
        _join(head + threads)
        assert results[0].tag == 2
        assert fake.launched_keys == [("shape", "head"), ("shape", "B")]
        assert fake.fetch_calls == [1, 1]
        s = pipeline.stats()
        assert (s["dispatched"], s["preparedAhead"]) == (2, 1)
    finally:
        pipeline.stop()


def test_an_early_prepare_that_raises_falls_back_and_the_rest_launch(caplog):
    fake = HeldFetchExec()
    pipeline = DeviceQueryPipeline(mesh_exec=fake)
    try:
        head = _hold_a_fetch(pipeline, fake)
        with caplog.at_level("ERROR",
                             logger="pinot_tpu.cluster.device_server"):
            threads, results = _submit(
                pipeline, [{"shape": "A", "literal": 1, "boom": True},
                           {"shape": "A", "literal": 2}])
            # the host answers the one that raised before the fetch ends
            assert _wait_for(lambda: results[0] is not None)
            assert results[0] is DEVICE_FALLBACK
            assert _wait_for(lambda: len(fake.prepared_while_held) == 3)
            fake.release.set()
            _join(head + threads)
        assert results[1].tag == 2
        s = pipeline.stats()
        assert (s["deviceErrors"], s["fallbacks"]) == (1, 0)
        assert (s["batches"], s["dispatched"], s["preparedAhead"]) == (2, 2, 1)
        assert fake.launched_keys == [("shape", "head"), ("shape", "A")]
        assert any("prepare_partial" in r.getMessage() for r in caplog.records)
    finally:
        pipeline.stop()


def test_the_fetchs_end_wakes_the_gather_not_its_poll(monkeypatch):
    """With the gather's poll at 10 s, a drain held open by a fetch still
    closes as soon as that fetch ends."""
    monkeypatch.setattr(device_server, "_GATHER_POLL_S", 10.0)
    fake = HeldFetchExec()
    pipeline = DeviceQueryPipeline(mesh_exec=fake)
    try:
        head = _hold_a_fetch(pipeline, fake)
        threads, results = _submit(pipeline, [{"shape": "A", "literal": 1}])
        assert _wait_for(lambda: len(fake.prepared) == 2)
        time.sleep(0.05)            # the gather is in its 10 s wait now
        t_release = time.perf_counter()
        fake.release.set()
        _join(threads)
        assert time.perf_counter() - t_release < 0.5
        assert results[0].tag == 1
        _join(head)
    finally:
        pipeline.stop()
