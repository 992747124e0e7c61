"""Device-memory observability plane tests: the HBM ledger, its gauges, the
controller's per-table memory verdicts, launch attribution in query stats,
Chrome-trace memory counters, and a ledger-backed leak regression.

The ledger is the accounting substrate (utils/memledger.py) — these tests pin
its arithmetic exactly (byte-accurate totals, filter semantics, re-registration
replacement), then prove the plane end to end: staging through the engine shows
up in `/debug/memory`, the controller turns server headroom into
HEALTHY/DEGRADED/UNHEALTHY, and unloading a segment returns the ledger to
baseline (the leak gate).
"""

import json
import threading

import numpy as np
import pytest

from pinot_tpu.utils import memledger
from pinot_tpu.utils.memledger import (MemoryLedger, get_ledger, reset_ledger,
                                       staged)
from pinot_tpu.utils.metrics import get_registry

from conftest import make_ssb_columns


@pytest.fixture()
def ledger(monkeypatch):
    """A MemoryLedger with a deterministic 1000-byte capacity (exact headroom
    math) publishing into a freshly reset process registry."""
    monkeypatch.setenv("PINOT_TPU_HBM_CAPACITY_BYTES", "1000")
    get_registry().reset()
    led = MemoryLedger()
    yield led
    get_registry().reset()


def _gauge_value(name, **labels):
    """Find one gauge in the registry snapshot by name + label pairs (label
    render order is an implementation detail; match pairs individually)."""
    for key, v in get_registry().snapshot().items():
        if key == name:
            return v
        if key.startswith(name + "{") and all(
                f"{lk}={lv}" in key for lk, lv in labels.items()):
            return v
    return None


# -- unknown device != default device ------------------------------------------

class _FakeDevice:
    def __init__(self, platform, kind, stats=None):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("dev,gbps", [
    (_FakeDevice("cpu", "cpu"), 819.0),
    (_FakeDevice("tpu", "TPU v5 lite"), 819.0),
    (_FakeDevice("tpu", "TPU v99"), None),
])
def test_device_peak_keyed_by_kind(monkeypatch, dev, gbps):
    import jax
    from pinot_tpu.utils.device_peaks import device_peak
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    if gbps is None:
        with pytest.raises(RuntimeError, match="unknown device kind"):
            device_peak()
    else:
        assert device_peak()["hbm_gbps"] == gbps


@pytest.mark.parametrize("dev,want", [
    (_FakeDevice("cpu", "cpu"), (16 << 30, True)),
    (_FakeDevice("tpu", "TPU v5 lite", {"bytes_limit": 123}), (123, False)),
    (_FakeDevice("tpu", "TPU v5 lite", None), None),
])
def test_capacity_estimate_is_cpu_only(monkeypatch, dev, want):
    import jax
    from pinot_tpu.utils.memledger import device_capacity_bytes
    monkeypatch.delenv("PINOT_TPU_HBM_CAPACITY_BYTES", raising=False)
    monkeypatch.setattr(jax, "local_devices", lambda *a: [dev])
    if want is None:
        with pytest.raises(RuntimeError, match="bytes_limit"):
            device_capacity_bytes()
    else:
        assert device_capacity_bytes() == want


# -- ledger arithmetic --------------------------------------------------------

def test_register_release_and_filters(ledger):
    ledger.register("t1", "seg_a", "raw", "col_x", 100)
    ledger.register("t1", "seg_a", "dict", "col_x", 40)
    ledger.register("t1", "seg_b", "raw", "col_x", 60)
    ledger.register("t2", "seg_c", "raw", "col_y", 9)
    assert ledger.resident_bytes() == 209
    assert ledger.resident_bytes(table="t1") == 200
    assert ledger.resident_bytes(segment="seg_a") == 140
    assert ledger.resident_bytes(kind="raw") == 169
    assert ledger.resident_bytes(table="t1", kind="raw") == 160
    # release by segment returns exactly what that segment held
    assert ledger.release(segment="seg_a") == 140
    assert ledger.resident_bytes() == 69
    # release by table sweeps the remainder of t1
    assert ledger.release(table="t1") == 60
    assert ledger.resident_bytes() == 9
    assert ledger.release() == 9
    assert ledger.resident_bytes() == 0


def test_reregistration_replaces_not_accumulates(ledger):
    """Idempotent re-staging (a cache rebuild) must not double-count."""
    ledger.register("t1", "seg_a", "raw", "col_x", 100)
    ledger.register("t1", "seg_a", "raw", "col_x", 100)
    assert ledger.resident_bytes() == 100
    # a rebuild at a different size replaces the old accounting
    ledger.register("t1", "seg_a", "raw", "col_x", 250)
    assert ledger.resident_bytes() == 250
    assert ledger.release(segment="seg_a") == 250


def test_table_attribution_binding_and_llc_fallback(ledger):
    # explicit binding wins (offline segment names carry no table prefix)
    ledger.bind_segment("trips_OFFLINE", "trips_0")
    ledger.register(None, "trips_0", "raw", "fare", 10)
    assert ledger.resident_bytes(table="trips_OFFLINE") == 10
    # LLC names embed the table: {table}__{partition}__{seq}__{creation}
    ledger.register(None, "lineorder__0__3__20240101", "consuming", "rows", 7)
    assert ledger.resident_bytes(table="lineorder") == 7
    # neither binding nor LLC shape: attributed to the "-" bucket, not lost
    ledger.register(None, "orphan_seg", "raw", "c", 5)
    assert ledger.resident_bytes(table="-") == 5
    assert ledger.resident_bytes() == 22
    # releasing a segment also drops its binding; re-registering the same
    # segment name falls back to the LLC/"-" resolution
    ledger.release(segment="trips_0")
    ledger.register(None, "trips_0", "raw", "fare", 10)
    assert ledger.resident_bytes(table="trips_OFFLINE") == 0
    assert ledger.resident_bytes(table="-") == 15


def test_snapshot_shape_and_headroom(ledger):
    ledger.register("t1", "seg_a", "raw", "col_x", 300)
    ledger.register("t1", "seg_b", "dict", "col_x", 100)
    ledger.register("t2", "seg_c", "raw", "col_y", 200)
    ledger.note_transient(50)
    snap = ledger.snapshot()
    assert snap["totalBytes"] == 600
    assert snap["entries"] == 3
    assert snap["capacityBytes"] == 1000
    assert snap["capacityEstimated"] is False   # env override is exact
    assert snap["headroomPct"] == 40.0
    assert snap["transientPeakBytes"] == 50
    # watermark tracks resident + transient peak, with a timestamped history
    assert snap["watermarkBytes"] == 650
    assert snap["watermarkHistory"]
    ts, bytes_ = snap["watermarkHistory"][-1]
    assert bytes_ == 650 and ts > 0
    assert snap["kinds"] == {"raw": 500, "dict": 100}
    assert snap["tables"] == {"t1": 400, "t2": 200}
    # topSegments sorted by bytes descending
    top = snap["topSegments"]
    assert [e["segment"] for e in top] == ["seg_a", "seg_c", "seg_b"]
    assert top[0] == {"table": "t1", "segment": "seg_a", "bytes": 300}
    # snapshot must be JSON-serializable as-is (it IS the /debug/memory body)
    json.dumps(snap)


def test_note_transient_tracks_peak_only(ledger):
    ledger.note_transient(100)
    ledger.note_transient(40)    # below peak: ignored
    ledger.note_transient(120)
    assert ledger.snapshot()["transientPeakBytes"] == 120
    assert _gauge_value("pinot_server_hbm_transient_peak_bytes") == 120


def test_reconcile_drift_math(ledger, monkeypatch):
    ledger.register("t1", "seg_a", "raw", "c", 800)
    # device view = baseline (untracked compile constants) + tracked staging
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: 1000)
    rec = ledger.reconcile(baseline_bytes=200)
    assert rec["ledgerBytes"] == 800
    assert rec["deviceBytes"] == 1000
    assert rec["driftBytes"] == 0 and rec["driftPct"] == 0.0
    # a leak on the device side shows as positive drift
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: 1200)
    rec = ledger.reconcile(baseline_bytes=200)
    assert rec["driftBytes"] == 200
    assert rec["driftPct"] == pytest.approx(20.0)
    # runtime can't enumerate live arrays: drift is None, not a fake zero
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: None)
    rec = ledger.reconcile()
    assert rec["driftBytes"] is None and rec["driftPct"] is None


def test_concurrent_registration_is_exact(ledger):
    """N threads staging disjoint entries: the total must be byte-exact —
    the ledger is the reconciliation source of truth, so a lost update would
    masquerade as device-side drift."""
    threads_n, per_thread, nbytes = 8, 200, 10

    def work(tid):
        for i in range(per_thread):
            ledger.register("t", f"seg_{tid}", "raw", f"col_{i}", nbytes)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(threads_n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ledger.resident_bytes() == threads_n * per_thread * nbytes
    freed = sum(ledger.release(segment=f"seg_{t}") for t in range(threads_n))
    assert freed == threads_n * per_thread * nbytes
    assert ledger.resident_bytes() == 0


# -- gauge exposition ---------------------------------------------------------

def test_gauges_flush_after_register_burst(ledger):
    """The register hot path throttles gauge publishing; internal accounting
    is always exact and flush()/snapshot()/release() force the gauges
    current."""
    ledger.register("t1", "seg_a", "raw", "c1", 100)   # first publish is free
    ledger.register("t1", "seg_a", "dict", "c1", 40)   # within throttle window
    assert ledger.resident_bytes() == 140               # accounting: exact now
    ledger.flush()
    assert _gauge_value("pinot_server_hbm_resident_bytes",
                        table="t1", kind="raw") == 100
    assert _gauge_value("pinot_server_hbm_resident_bytes",
                        table="t1", kind="dict") == 40
    assert _gauge_value("pinot_server_hbm_resident_total_bytes") == 140
    assert _gauge_value("pinot_server_hbm_capacity_bytes") == 1000
    assert _gauge_value("pinot_server_hbm_headroom_pct") == 86.0


def test_stale_series_removed_on_release(ledger):
    """A dropped table/kind must not keep exporting a zero series forever —
    the same stale-gauge hygiene the controller checkers follow."""
    ledger.register("t1", "seg_a", "raw", "c1", 100)
    ledger.flush()
    assert _gauge_value("pinot_server_hbm_resident_bytes",
                        table="t1", kind="raw") == 100
    ledger.release(table="t1")
    assert _gauge_value("pinot_server_hbm_resident_bytes",
                        table="t1", kind="raw") is None
    assert _gauge_value("pinot_server_hbm_resident_total_bytes") == 0


def test_staged_wrapper_registers_and_passes_through(monkeypatch):
    """staged() is THE sanctioned staging wrapper (the graftcheck rule
    enforces it): registers nbytes in the process ledger, returns the array
    unchanged."""
    get_registry().reset()
    reset_ledger()
    try:
        arr = np.zeros(256, dtype=np.float64)
        out = staged(arr, "seg_w", "raw", name="col", table="tw")
        assert out is arr
        assert get_ledger().resident_bytes(table="tw", kind="raw") == arr.nbytes
        # objects without nbytes register 0 rather than raising mid-staging
        token = staged(object(), "seg_w", "dict", table="tw")
        assert token is not None
        assert get_ledger().resident_bytes(table="tw") == arr.nbytes
    finally:
        reset_ledger()
        get_registry().reset()


# -- controller memory verdicts ----------------------------------------------

@pytest.fixture()
def verdict_cluster(tmp_path, ssb_schema):
    from pinot_tpu.cluster import QuickCluster
    from pinot_tpu.table import TableConfig
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cfg = TableConfig(ssb_schema.name, replication=1,
                      time_column="lo_orderdate")
    cluster.create_table(ssb_schema, cfg)
    return cluster, cfg.table_name_with_type


def _poller(headroom, tables=None, total=None):
    snap = {"headroomPct": headroom, "tables": tables or {},
            "totalBytes": total if total is not None
            else sum((tables or {}).values())}
    return lambda: snap


def _raising_poller():
    def poll():
        raise ConnectionError("server down")
    return poll


def test_memory_verdict_matrix(verdict_cluster):
    """The full HEALTHY/DEGRADED/UNHEALTHY decision table off the
    `controller.memory.headroom.pct` threshold (default 20%)."""
    cluster, table = verdict_cluster
    ctl = cluster.controller

    # comfortable headroom -> HEALTHY, bytes attributed per server
    ctl.memory_pollers = {"server_0": _poller(80.0, {table: 4096})}
    assert ctl.run_memory_check() == {table: "HEALTHY"}
    st = ctl.memory_status(table)
    assert st["memoryState"] == "HEALTHY" and st["reasons"] == []
    assert st["residentBytes"] == 4096
    assert st["servers"] == {"server_0": 4096}
    assert st["minServerHeadroomPct"] == 80.0

    # below threshold -> DEGRADED, reason names the server and the threshold
    ctl.memory_pollers = {"server_0": _poller(10.0, {table: 4096})}
    assert ctl.run_memory_check() == {table: "DEGRADED"}
    st = ctl.memory_status(table)
    assert any("server_0" in r and "20" in r for r in st["reasons"])

    # at/below a quarter of the threshold -> UNHEALTHY (critically low)
    ctl.memory_pollers = {"server_0": _poller(4.0, {table: 4096})}
    assert ctl.run_memory_check() == {table: "UNHEALTHY"}
    assert any("critically" in r
               for r in ctl.memory_status(table)["reasons"])

    # fully out of HBM -> UNHEALTHY even when the threshold is tiny
    cluster.catalog.put_property(
        "clusterConfig/controller.memory.headroom.pct", "1")
    ctl.memory_pollers = {"server_0": _poller(0.0, {table: 4096})}
    assert ctl.run_memory_check() == {table: "UNHEALTHY"}

    # threshold override: 40% headroom breaches a raised 50% bar
    cluster.catalog.put_property(
        "clusterConfig/controller.memory.headroom.pct", "50")
    ctl.memory_pollers = {"server_0": _poller(40.0, {table: 4096})}
    assert ctl.run_memory_check() == {table: "DEGRADED"}
    assert ctl.memory_status(table)["headroomThresholdPct"] == 50.0


def test_memory_verdict_unreachable_servers(verdict_cluster):
    cluster, table = verdict_cluster
    ctl = cluster.controller

    # every poller raising: no data at all -> UNHEALTHY, not silently healthy
    ctl.memory_pollers = {"server_0": _raising_poller()}
    assert ctl.run_memory_check() == {table: "UNHEALTHY"}
    st = ctl.memory_status(table)
    assert any("no server reported" in r for r in st["reasons"])
    assert st["unreachableServers"] == ["server_0"]

    # one healthy + one unreachable -> DEGRADED (partial visibility)
    ctl.memory_pollers = {"server_0": _poller(90.0, {table: 1024}),
                          "server_1": _raising_poller()}
    assert ctl.run_memory_check() == {table: "DEGRADED"}
    st = ctl.memory_status(table)
    assert any("poll failed" in r for r in st["reasons"])
    # residency still sums over the servers that did report
    assert st["residentBytes"] == 1024

    # resident bytes sum ACROSS servers when several report the same table
    ctl.memory_pollers = {"server_0": _poller(90.0, {table: 1024}),
                          "server_1": _poller(70.0, {table: 512})}
    assert ctl.run_memory_check() == {table: "HEALTHY"}
    st = ctl.memory_status(table)
    assert st["residentBytes"] == 1536
    assert st["minServerHeadroomPct"] == 70.0


def test_memory_status_unknown_and_prejudgment(verdict_cluster):
    cluster, table = verdict_cluster
    ctl = cluster.controller
    # before the first check: UNKNOWN, never a fabricated verdict
    ctl._memory_status = {}
    st = ctl.memory_status(table)
    assert st["memoryState"] == "UNKNOWN"
    ctl.memory_pollers = {"server_0": _poller(80.0, {table: 10})}
    ctl.run_memory_check()
    assert ctl.memory_status(table)["memoryState"] == "HEALTHY"
    # verdicts key on nameWithType here; the bare logical name is still a
    # known table, so it answers UNKNOWN rather than 404ing
    assert ctl.memory_status("lineorder")["memoryState"] in (
        "UNKNOWN", "HEALTHY")
    with pytest.raises(ValueError):
        ctl.memory_status("no_such_table")


def test_memory_check_publishes_and_removes_gauges(verdict_cluster):
    cluster, table = verdict_cluster
    ctl = cluster.controller
    ctl.memory_pollers = {"server_0": _poller(35.5, {table: 2048})}
    ctl.run_memory_check()
    assert _gauge_value("pinot_controller_hbm_headroom_pct",
                        instance="server_0") == 35.5
    assert _gauge_value("pinot_controller_hbm_healthy", table=table) == 1
    assert _gauge_value("pinot_controller_hbm_resident_bytes",
                        table=table) == 2048
    # server departs: its instance series must disappear, not freeze
    ctl.memory_pollers = {"server_1": _poller(60.0, {table: 2048})}
    ctl.run_memory_check()
    assert _gauge_value("pinot_controller_hbm_headroom_pct",
                        instance="server_0") is None
    assert _gauge_value("pinot_controller_hbm_headroom_pct",
                        instance="server_1") == 60.0


# -- launch attribution + end-to-end ledger (in-proc) ---------------------------

@pytest.fixture()
def lineorder_cluster(tmp_path, ssb_schema):
    from pinot_tpu.cluster import QuickCluster
    from pinot_tpu.table import TableConfig
    rng = np.random.default_rng(11)
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cfg = TableConfig(ssb_schema.name, replication=1,
                      time_column="lo_orderdate")
    cluster.create_table(ssb_schema, cfg)
    cluster.ingest_columns(cfg, make_ssb_columns(rng, 2000))
    return cluster, cfg


REMOVED_COST_KEYS = ("deviceFlops", "deviceBytesAccessed", "rooflinePct")


def test_query_stats_carry_launch_and_compile_attribution(lineorder_cluster):
    """What a query's launches cost rides every response as counts and wall
    times taken where the work happens (launches, the kernel cache, the
    first-call compile fence, the server's execute wall); the modeled
    cost_analysis() figures and the host-clock roofline are gone (PR 26)."""
    cluster, cfg = lineorder_cluster
    # a literal nothing else in the suite uses: the shape is cold here
    res = cluster.query("SELECT SUM(lo_revenue), COUNT(*) FROM lineorder "
                        "WHERE lo_quantity BETWEEN 7 AND 23")
    stats = res.stats
    assert stats["deviceLaunches"] >= 1
    assert stats["compileCacheMisses"] + stats["compileCacheHits"] >= 1
    assert stats["serverTimeMs"] > 0
    assert stats["serverTimeMs"] <= stats["timeUsedMs"]
    for key in REMOVED_COST_KEYS:
        assert key not in stats
    # serverTimeMs is max-merged: over a multi-segment scatter it stays one
    # server's wall, inside the broker's
    res2 = cluster.query(
        "SELECT lo_region, SUM(lo_revenue) FROM lineorder "
        "GROUP BY lo_region LIMIT 10")
    assert 0 < res2.stats["serverTimeMs"] <= res2.stats["timeUsedMs"]


def test_query_staging_lands_in_ledger_and_verdict(lineorder_cluster):
    """End to end in-proc: running a query stages columns, the ledger
    attributes them to the table, and the controller verdict sees the bytes."""
    cluster, cfg = lineorder_cluster
    table = cfg.table_name_with_type
    ledger = get_ledger()
    before = ledger.resident_bytes(table=table)
    cluster.query("SELECT SUM(lo_extendedprice) FROM lineorder")
    assert ledger.resident_bytes(table=table) > before
    snap = cluster.servers[0].memory_snapshot()
    assert snap["instanceId"] == "server_0"
    assert snap["tables"].get(table, 0) > 0
    verdicts = cluster.controller.run_memory_check()
    assert verdicts[table] in ("HEALTHY", "DEGRADED", "UNHEALTHY")
    st = cluster.controller.memory_status(table)
    assert st["residentBytes"] >= snap["tables"][table]


def test_segment_unload_returns_ledger_to_baseline(lineorder_cluster):
    """The leak regression: block_for/release_block cycles and a table-manager
    remove_segment must return the ledger exactly to baseline."""
    from pinot_tpu.engine import datablock
    cluster, cfg = lineorder_cluster
    table = cfg.table_name_with_type
    mgr = cluster.servers[0].tables[table]
    segments = mgr.acquire()
    assert segments
    seg = segments[0]
    try:
        ledger = get_ledger()
        datablock.release_block(seg)
        baseline = ledger.resident_bytes(segment=seg.name)
        staged_bytes = None
        for _ in range(5):
            blk = datablock.block_for(seg)
            blk.valid
            blk.ids("lo_region")
            blk.values("lo_quantity")
            now = ledger.resident_bytes(segment=seg.name)
            assert now > baseline
            if staged_bytes is None:
                staged_bytes = now
            # idempotent re-staging must not grow the ledger
            assert now == staged_bytes
            datablock.release_block(seg)
            assert ledger.resident_bytes(segment=seg.name) == baseline
        # unload path: remove_segment DEFERS the block drop while this test
        # still holds an acquired ref (the unload-vs-in-flight-query fix) —
        # the device block stays alive until the last release()
        datablock.block_for(seg).ids("lo_region")
        assert ledger.resident_bytes(segment=seg.name) > baseline
        mgr.remove_segment(seg.name)
        assert ledger.resident_bytes(segment=seg.name) > baseline
    finally:
        mgr.release(segments)
    # the release that drained the refcount freed block + ledger entries
    assert ledger.resident_bytes(segment=seg.name) == 0


# -- HTTP transport: /debug/memory, memoryStatus, cost fields -----------------

@pytest.fixture()
def http_cluster(tmp_path):
    """Controller + 1 server + 1 broker over real HTTP (test_mux idiom) with
    a loaded two-segment trips table."""
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.catalog import Catalog
    from pinot_tpu.cluster.controller import Controller
    from pinot_tpu.cluster.deepstore import LocalDeepStore
    from pinot_tpu.cluster.process import ControllerClient
    from pinot_tpu.cluster.remote import ControllerDeepStore, RemoteCatalog
    from pinot_tpu.cluster.server import ServerNode
    from pinot_tpu.cluster.services import (BrokerService, ControllerService,
                                            ServerService)
    from pinot_tpu.schema import DataType, FieldSpec, Schema
    from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig
    from pinot_tpu.table import TableConfig
    from conftest import wait_until

    schema = Schema("trips", [FieldSpec("city", DataType.STRING),
                              FieldSpec("fare", DataType.DOUBLE),
                              FieldSpec("n", DataType.INT)])
    catalog = Catalog()
    deepstore = LocalDeepStore(str(tmp_path / "deepstore"))
    controller = Controller("controller_0", catalog, deepstore,
                            str(tmp_path / "ctrl"))
    csvc = ControllerService(controller)
    services = [csvc]
    catalogs = []
    try:
        src = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
        catalogs.append(src)
        node = ServerNode("server_0", src, ControllerDeepStore(csvc.url),
                          str(tmp_path / "server_0"))
        ssvc = ServerService(node)
        services.append(ssvc)
        brc = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
        catalogs.append(brc)
        bsvc = BrokerService(Broker("broker_0", brc))
        services.append(bsvc)

        c = ControllerClient(csvc.url)
        c.add_schema(schema)
        cfg = TableConfig("trips", replication=1)
        c.add_table(cfg)
        builder = SegmentBuilder(schema, SegmentGeneratorConfig())
        for i, (cities, fares, ns) in enumerate((
                (["nyc", "sf", "nyc", "la"], [10.0, 20.0, 30.0, 7.5],
                 [1, 2, 3, 4]),
                (["sf", "la", "nyc"], [5.0, 7.0, 2.5], [5, 6, 7]))):
            seg = builder.build(
                {"city": np.array(cities, dtype=object),
                 "fare": np.array(fares, dtype=np.float64),
                 "n": np.array(ns, dtype=np.int32)},
                str(tmp_path / f"b{i}"), f"trips_{i}")
            c.upload_segment(cfg.table_name_with_type, seg)
        assert wait_until(
            lambda: len(node.segments_served(cfg.table_name_with_type)) == 2,
            timeout=15.0, interval=0.05, swallow=())
        yield {"csvc": csvc, "ssvc": ssvc, "bsvc": bsvc,
               "controller": controller, "table": cfg.table_name_with_type}
    finally:
        for rc in catalogs:
            rc.close()
        for s in services:
            s.stop()


def test_memory_plane_over_http(http_cluster):
    """The whole plane through real sockets: launch stats in broker responses,
    the server's /debug/memory ledger panel, and the controller's
    memoryStatus verdict fed by its HTTP /debug/memory poller."""
    from pinot_tpu.cluster.http_service import get_json
    from pinot_tpu.cluster.process import BrokerClient
    from conftest import wait_until

    bc = BrokerClient(http_cluster["bsvc"].url)
    assert wait_until(
        lambda: bc.query("SELECT COUNT(*) FROM trips"
                         )["resultTable"]["rows"][0][0] == 7,
        timeout=15.0, interval=0.1)
    # stats keys ride at the top level of the broker response (Pinot style)
    resp = bc.query("SELECT SUM(fare) FROM trips")
    assert resp["deviceLaunches"] >= 1
    assert 0 < resp["serverTimeMs"] <= resp["timeUsedMs"]
    for key in REMOVED_COST_KEYS:
        assert key not in resp

    # the server's ledger panel shows the staged columns, attributed
    snap = get_json(f"{http_cluster['ssvc'].url}/debug/memory")
    assert snap["instanceId"] == "server_0"
    assert snap["totalBytes"] > 0
    assert snap["tables"].get(http_cluster["table"], 0) > 0
    assert 0.0 <= snap["headroomPct"] <= 100.0
    assert snap["capacityBytes"] > 0

    # controller polls the HTTP route (no in-proc poller registered here)
    verdicts = http_cluster["controller"].run_memory_check()
    assert http_cluster["table"] in verdicts
    st = get_json(f"{http_cluster['csvc'].url}"
                  f"/tables/{http_cluster['table']}/memoryStatus")
    assert st["memoryState"] in ("HEALTHY", "DEGRADED", "UNHEALTHY")
    assert st["residentBytes"] >= snap["tables"][http_cluster["table"]]
    assert "server_0" in st["servers"]


# -- Chrome-trace memory counters ---------------------------------------------

def test_chrome_trace_memory_counter_events():
    """HBM residency rides the trace timeline as Chrome counter events
    (`ph: "C"`, cat "memory") so chrome://tracing renders a filled residency
    track under the query spans."""
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.utils.trace import to_chrome_trace

    samples = Broker._memory_samples(5.0)
    assert samples and samples[0]["tsMs"] == 5.0
    series = samples[0]["series"]
    assert set(series) == {"hbm_resident_bytes", "hbm_transient_peak_bytes"}
    entry = {"traceId": "t-mem", "sql": "SELECT 1", "timeUsedMs": 5.0,
             "spans": [{"name": "broker", "startMs": 0.0, "durationMs": 5.0,
                        "depth": 0}],
             "memory": samples}
    doc = to_chrome_trace(entry)
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert {e["name"] for e in counters} == set(series)
    for ev in counters:
        assert ev["cat"] == "memory"
        assert ev["ts"] == 5000.0          # ms -> µs on the span timebase
        assert "bytes" in ev["args"]
        assert ev["args"]["bytes"] == series[ev["name"]]
    # span events are untouched by the counter track
    assert any(e.get("ph") == "X" and e["name"] == "broker"
               for e in doc["traceEvents"])


def test_trace_without_memory_samples_has_no_counters():
    from pinot_tpu.utils.trace import to_chrome_trace
    doc = to_chrome_trace({"traceId": "t0", "spans": [
        {"name": "broker", "startMs": 0.0, "durationMs": 1.0, "depth": 0}]})
    assert not [e for e in doc["traceEvents"] if e.get("ph") == "C"]
