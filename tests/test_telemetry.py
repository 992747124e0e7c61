"""End-to-end query telemetry: ExecutionStats threading, device kernel timing,
EXPLAIN ANALYZE, the slow-query log, and the /debug endpoint.

Reference coverage pattern: BrokerResponseNative metadata assertions in the
reference's integration tests, plus its slow-query WARN log — here the record
is typed (`pinot_tpu.query.stats.ExecutionStats`) and must survive BOTH the
in-proc and the HTTP transport unchanged.
"""

import json
import logging
import re
import threading

import numpy as np
import pytest

from pinot_tpu.cluster import QuickCluster
from pinot_tpu.query import stats as qstats
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.table import TableConfig
from pinot_tpu.utils.metrics import Histogram, MetricsRegistry, get_registry
from pinot_tpu.utils.trace import Trace, current_depth, span

# the keys the acceptance criteria name: every query response must carry them
ACCEPTANCE_KEYS = (
    "numSegmentsQueried", "numSegmentsPruned", "numSegmentsMatched",
    "numDocsScanned", "deviceLaunches", "compileCacheHits",
    "compileCacheMisses", "deviceExecMs", "phaseTimesMs", "timeUsedMs",
)


@pytest.fixture
def tel_cluster(tmp_path):
    schema = Schema("ev", [dimension("site", DataType.STRING),
                           metric("v", DataType.LONG)])
    cluster = QuickCluster(num_servers=2, work_dir=str(tmp_path))
    cfg = TableConfig("ev", replication=1)
    cluster.create_table(schema, cfg)
    rng = np.random.default_rng(0)
    for _ in range(3):
        cluster.ingest_columns(cfg, {
            "site": np.array(["a", "b", "c", "d"] * 25),
            "v": rng.integers(0, 100, 100),
        })
    return cluster


class _CaptureHandler(logging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


@pytest.fixture
def slow_log_capture():
    logger = logging.getLogger("pinot_tpu.broker.slow_query")
    h = _CaptureHandler()
    logger.addHandler(h)
    try:
        yield h
    finally:
        logger.removeHandler(h)


# -- tentpole: stats through the in-proc broker ------------------------------

def test_groupby_stats_through_inproc_broker(tel_cluster):
    res = tel_cluster.query(
        "SELECT site, SUM(v) FROM ev GROUP BY site ORDER BY site")
    for key in ACCEPTANCE_KEYS:
        assert key in res.stats, f"missing {key}: {sorted(res.stats)}"
    assert res.stats["numSegmentsQueried"] == 3
    assert res.stats["numSegmentsPruned"] == 0
    assert res.stats["numSegmentsMatched"] == 3
    assert res.stats["numDocsScanned"] == 300
    # broker phase wall times keep their exact shape
    assert set(res.stats["phaseTimesMs"]) == {"compile", "scatter", "reduce"}
    # the op:* EXPLAIN ANALYZE breakdown never leaks into the public response
    assert not any(k.startswith("op:") for k in res.stats)


def test_segment_pruning_counted(tel_cluster):
    res = tel_cluster.query("SELECT COUNT(*) FROM ev WHERE site = 'nope'")
    # the constant-false fold happens per segment: all pruned, none matched
    assert res.stats["numSegmentsPruned"] + res.stats["numSegmentsQueried"] == 3
    assert res.stats["numSegmentsMatched"] <= res.stats["numSegmentsQueried"]


def test_compile_cache_hits_on_repeat_query(tel_cluster):
    sql = "SELECT site, SUM(v), MAX(v) FROM ev GROUP BY site"
    tel_cluster.query(sql)      # warm: builds whatever executables are needed
    res = tel_cluster.query(sql)
    assert res.stats["compileCacheMisses"] == 0, res.stats
    if res.stats["deviceLaunches"]:     # device path: cache must have served it
        assert res.stats["compileCacheHits"] >= 1


# -- tentpole: EXPLAIN ANALYZE -----------------------------------------------

def test_explain_analyze_renders_rows_and_ms(tel_cluster):
    res = tel_cluster.query(
        "EXPLAIN ANALYZE SELECT site, SUM(v) FROM ev GROUP BY site")
    assert res.columns == ["Operator", "Operator_Id", "Parent_Id", "Rows", "Ms"]
    assert res.stats.get("analyze") is True and res.stats.get("explain") is True
    # root row: result row count + total wall time
    root = res.rows[0]
    assert root[1] == 0 and root[2] == -1
    assert root[3] == 4 and root[4] > 0
    # per-node annotation: at least combine + segment plan carry rows/ms
    annotated = {r[0].split("(")[0] for r in res.rows if r[4] is not None}
    assert "COMBINE_GROUP_BY" in annotated
    assert "SEGMENT_PLAN" in annotated
    seg_rows = [r[3] for r in res.rows
                if r[0].startswith("SEGMENT_PLAN") and r[3] is not None]
    assert seg_rows and seg_rows[0] == 300      # docs actually scanned
    # the full stats record rides along
    assert res.stats["numSegmentsQueried"] == 3


def test_plain_explain_stays_three_columns(tel_cluster):
    res = tel_cluster.query(
        "EXPLAIN PLAN FOR SELECT site, SUM(v) FROM ev GROUP BY site")
    assert res.columns == ["Operator", "Operator_Id", "Parent_Id"]
    assert all(len(r) == 3 for r in res.rows)


def test_explain_analyze_single_node_executor(tmp_path):
    from pinot_tpu.query.executor import execute_query
    from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig
    schema = Schema("t", [dimension("k", DataType.STRING),
                          metric("x", DataType.LONG)])
    seg = SegmentBuilder(schema, SegmentGeneratorConfig()).build(
        {"k": np.array(["p", "q", "p"], dtype=object),
         "x": np.array([1, 2, 3], dtype=np.int64)}, str(tmp_path), "t_0")
    from pinot_tpu.segment.reader import load_segment
    res = execute_query([load_segment(seg)],
                        "EXPLAIN ANALYZE SELECT k, SUM(x) FROM t GROUP BY k")
    assert res.columns == ["Operator", "Operator_Id", "Parent_Id", "Rows", "Ms"]
    assert res.rows[0][3] == 2 and res.rows[0][4] > 0
    assert res.stats["numSegmentsQueried"] == 1


# -- tentpole: slow-query log + /debug ---------------------------------------

def test_slow_query_emits_exactly_one_log_line(tel_cluster, slow_log_capture):
    cat = tel_cluster.broker.catalog
    counter = get_registry().counter("pinot_broker_slow_queries")
    before = counter.value
    cat.put_property("clusterConfig/broker.slow.query.ms", "0")
    try:
        tel_cluster.query("SELECT COUNT(*) FROM ev")
    finally:
        cat.put_property("clusterConfig/broker.slow.query.ms", None)
    assert len(slow_log_capture.records) == 1
    entry = json.loads(slow_log_capture.records[0].getMessage())
    assert entry["sql"] == "SELECT COUNT(*) FROM ev"
    assert entry["timeUsedMs"] > 0
    assert entry["thresholdMs"] == 0.0
    assert entry["stats"]["numServersResponded"] >= 1
    assert counter.value == before + 1
    # below threshold: silent
    tel_cluster.query("SELECT COUNT(*) FROM ev")
    assert len(slow_log_capture.records) == 1


def test_slow_query_log_carries_trace_spans(tel_cluster, slow_log_capture):
    cat = tel_cluster.broker.catalog
    cat.put_property("clusterConfig/broker.slow.query.ms", "0")
    try:
        tel_cluster.query("SELECT COUNT(*) FROM ev OPTION(trace=true)")
    finally:
        cat.put_property("clusterConfig/broker.slow.query.ms", None)
    entry = json.loads(slow_log_capture.records[-1].getMessage())
    assert entry["traceSpans"], entry
    assert any(s["name"] == "broker.compile" for s in entry["traceSpans"])


def test_debug_stats_rollup(tel_cluster, slow_log_capture):
    cat = tel_cluster.broker.catalog
    cat.put_property("clusterConfig/broker.slow.query.ms", "0")
    try:
        tel_cluster.query("SELECT COUNT(*) FROM ev")
    finally:
        cat.put_property("clusterConfig/broker.slow.query.ms", None)
    dbg = tel_cluster.broker.debug_stats()
    qs = dbg["queryStats"]
    assert qs["numQueries"] >= 1
    assert qs["numSlowQueries"] >= 1
    assert qs["maxTimeMs"] >= qs["avgTimeMs"] > 0
    assert dbg["recentSlowQueries"][-1]["sql"] == "SELECT COUNT(*) FROM ev"
    assert "pinot_broker_queries" in dbg["brokerMetrics"]


# -- satellite 3: device pipeline counters surface per query -----------------

def test_device_pipeline_counters_in_query_stats(tmp_path, tel_cluster):
    from pinot_tpu.cluster.device_server import DeviceQueryPipeline
    pipeline = DeviceQueryPipeline()
    for server in tel_cluster.servers:
        server.device_pipeline = pipeline
    try:
        res = tel_cluster.query("SELECT COUNT(*), SUM(v) FROM ev WHERE v >= 0")
        assert res.rows[0][0] == 300
        if res.stats["deviceLaunches"]:     # served through the pipeline
            assert "queueWaitMs" in res.stats
            assert "dedupedLaunches" in res.stats
            assert "stackedLaunches" in res.stats
            assert res.stats["queueWaitMs"] >= 0
    finally:
        for server in tel_cluster.servers:
            server.device_pipeline = None
        pipeline.stop()


# -- satellite 1: Histogram.observe is atomic under concurrency --------------

def test_histogram_observe_concurrent():
    h = Histogram(buckets=(1.0, 10.0, 100.0))
    n_threads, per_thread = 8, 4000
    values = [0.5, 5.0, 50.0, 500.0]
    stop = threading.Event()
    torn = []

    def reader():
        # percentile() reads count + bucket rows together; a torn observe
        # would let the cumulative walk run past count and fall off the end
        while not stop.is_set():
            p = h.percentile(0.99)
            if p < 0:
                torn.append(p)

    def writer(i):
        for j in range(per_thread):
            h.observe(values[(i + j) % len(values)])

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    r = threading.Thread(target=reader)
    r.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    r.join()
    assert not torn
    total = n_threads * per_thread
    assert h.count == total
    # the atomic observe keeps the cumulative-bucket invariant exact: every
    # observation landed in exactly one bucket row
    assert sum(h.bucket_counts) == total
    assert h.bucket_counts == [total // 4] * 4


# -- satellite 2: spliced trace spans nest under the dispatch span -----------

def test_splice_applies_depth_offset():
    tr = Trace("q1")
    tr.record("server:s1", 0.0, 9.0, depth=1)
    remote = [{"name": "query", "startMs": 0.0, "durationMs": 5.0, "depth": 0},
              {"name": "segment:a", "startMs": 1.0, "durationMs": 2.0,
               "depth": 1}]
    tr.splice(remote, prefix="server:s1", offset_ms=3.0, depth_offset=2)
    by_name = {s["name"]: s for s in tr.to_rows()}
    assert by_name["server:s1/query"]["depth"] == 2
    assert by_name["server:s1/segment:a"]["depth"] == 3
    assert by_name["server:s1/query"]["startMs"] == 3.0


def test_current_depth_tracks_open_spans():
    tr = Trace("q2")
    with tr.activate():
        assert current_depth() == 0
        with span("outer"):
            assert current_depth() == 1
            with span("inner"):
                assert current_depth() == 2
        assert current_depth() == 0


# -- satellite 4: Prometheus exposition with multiple label sets -------------

def test_prometheus_histogram_multiple_labelsets():
    reg = MetricsRegistry()
    reg.histogram("lat_ms", {"table": "trips"}, buckets=(1.0, 10.0)).observe(0.5)
    reg.histogram("lat_ms", {"table": 'we"ird\nname'},
                  buckets=(1.0, 10.0)).observe(5.0)
    text = reg.render_prometheus()
    # exactly ONE # TYPE line for the family, both series grouped under it
    assert text.count("# TYPE lat_ms histogram") == 1
    assert 'lat_ms_bucket{table="trips",le="1"} 1' in text
    # label escaping: literal quote -> \" and newline -> \n, series intact
    assert 'table="we\\"ird\\nname"' in text
    for line in text.splitlines():
        assert "\n" not in line        # escaping kept the exposition line-safe
    assert 'lat_ms_count{table="trips"} 1' in text


def test_snapshot_reports_histogram_p50():
    reg = MetricsRegistry()
    h = reg.histogram("scan_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 5.0, 50.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["scan_ms_count"] == 4
    assert snap["scan_ms_sum"] == pytest.approx(60.5)
    # p50 reads back as the upper bound of the bucket holding the median
    assert snap["scan_ms_p50"] == 10.0


# -- HTTP transport: same stats over the wire --------------------------------

def test_stats_and_debug_over_http(tmp_path):
    from conftest import wait_until
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.catalog import Catalog
    from pinot_tpu.cluster.controller import Controller
    from pinot_tpu.cluster.deepstore import LocalDeepStore
    from pinot_tpu.cluster.http_service import http_call
    from pinot_tpu.cluster.process import BrokerClient, ControllerClient
    from pinot_tpu.cluster.remote import ControllerDeepStore, RemoteCatalog
    from pinot_tpu.cluster.server import ServerNode
    from pinot_tpu.cluster.services import (BrokerService, ControllerService,
                                            ServerService)
    from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig

    schema = Schema("ev", [dimension("site", DataType.STRING),
                           metric("v", DataType.LONG)])
    catalog = Catalog()
    controller = Controller("controller_0", catalog,
                            LocalDeepStore(str(tmp_path / "ds")),
                            str(tmp_path / "ctrl"))
    csvc = ControllerService(controller)
    services, catalogs = [csvc], []
    try:
        rc = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
        catalogs.append(rc)
        node = ServerNode("server_0", rc, ControllerDeepStore(csvc.url),
                          str(tmp_path / "server_0"))
        services.append(ServerService(node))
        brc = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
        catalogs.append(brc)
        broker = Broker("broker_http", brc)
        bsvc = BrokerService(broker)
        services.append(bsvc)

        cc = ControllerClient(csvc.url)
        cc.add_schema(schema)
        cfg = TableConfig("ev", replication=1)
        cc.add_table(cfg)
        seg = SegmentBuilder(schema, SegmentGeneratorConfig()).build(
            {"site": np.array(["a", "b", "a", "c"], dtype=object),
             "v": np.array([1, 2, 3, 4], dtype=np.int64)},
            str(tmp_path / "b"), "ev_0")
        cc.upload_segment(cfg.table_name_with_type, seg)
        assert wait_until(
            lambda: len(node.segments_served(cfg.table_name_with_type)) == 1,
            timeout=15.0, interval=0.05, swallow=())

        bc = BrokerClient(bsvc.url)

        def grouped():
            try:
                return bc.query("SELECT site, SUM(v) FROM ev GROUP BY site "
                                "ORDER BY site")
            except Exception:
                return None     # broker catalog mirror still converging

        assert wait_until(lambda: grouped() is not None, timeout=15.0,
                          interval=0.1, swallow=())
        resp = grouped()
        assert resp["resultTable"]["rows"] == [["a", 4], ["b", 2], ["c", 4]]
        # the full merged record survives the HTTP hop, spread at top level
        for key in ACCEPTANCE_KEYS:
            assert key in resp, f"missing {key}: {sorted(resp)}"
        assert resp["numSegmentsQueried"] == 1
        assert resp["numDocsScanned"] == 4
        assert set(resp["phaseTimesMs"]) == {"compile", "scatter", "reduce"}

        # EXPLAIN ANALYZE over HTTP: annotated 5-column plan
        an = bc.query("EXPLAIN ANALYZE SELECT site, SUM(v) FROM ev GROUP BY site")
        cols = an["resultTable"]["dataSchema"]["columnNames"]
        assert cols == ["Operator", "Operator_Id", "Parent_Id", "Rows", "Ms"]
        assert an["resultTable"]["rows"][0][3] == 3     # result groups
        assert an["analyze"] is True

        # satellite 2: remote server spans splice in NESTED under the
        # broker's server:<id> dispatch span (depth_offset=current_depth())
        traced = bc.query("SELECT COUNT(*) FROM ev OPTION(trace=true)")
        spans = traced["traceInfo"]
        remote = [s for s in spans
                  if re.match(r"server:server_\d+/", s["name"])]
        assert remote, [s["name"] for s in spans]
        dispatch_depth = {s["name"]: s["depth"] for s in spans
                          if re.fullmatch(r"server:server_\d+", s["name"])}
        assert dispatch_depth
        for s in remote:
            root = s["name"].split("/", 1)[0]
            assert s["depth"] > dispatch_depth[root], s

        # GET /debug: rollups + slow ring as JSON
        catalog.put_property("clusterConfig/broker.slow.query.ms", "0")
        try:
            # the broker reads the key from its mirror of the catalog, which
            # a long poll brings up to date: wait for it, or the query below
            # races the poll and is not slow
            assert wait_until(lambda: broker._slow_threshold_ms() == 0.0,
                              timeout=15.0, interval=0.02, swallow=())
            bc.query("SELECT COUNT(*) FROM ev")
        finally:
            catalog.put_property("clusterConfig/broker.slow.query.ms", None)
        dbg = json.loads(http_call("GET", f"{bsvc.url}/debug").decode())
        assert dbg["queryStats"]["numQueries"] >= 2
        assert dbg["queryStats"]["numSlowQueries"] >= 1
        assert dbg["recentSlowQueries"][-1]["sql"] == "SELECT COUNT(*) FROM ev"
    finally:
        for c in catalogs:
            c.close()
        for s in services:
            s.stop()


# -- glossary drift guard + report tool --------------------------------------

def _readme_documented_keys():
    import os
    readme = os.path.join(os.path.dirname(__file__), "..", "README.md")
    with open(readme) as f:
        text = f.read()
    obs = text.split("## Observability", 1)[1].split("## Layout", 1)[0]
    return set(re.findall(r"`([A-Za-z][A-Za-z.]*)`", obs))


def test_every_stats_constant_documented_in_readme():
    documented = _readme_documented_keys()
    for key in qstats.COUNTER_KEYS + qstats.BROKER_KEYS:
        assert key in documented, f"{key} missing from README glossary"
    assert "broker.slow.query.ms" in documented


def test_emitted_stats_keys_documented(tel_cluster):
    """Drift guard: every key a real query emits is in the README glossary."""
    documented = _readme_documented_keys()
    res = tel_cluster.query("SELECT site, SUM(v) FROM ev GROUP BY site")
    undocumented = set(res.stats) - documented
    assert not undocumented, (
        f"stats keys {sorted(undocumented)} are emitted but not documented "
        "in README.md's Observability glossary — add them there AND to "
        "pinot_tpu/query/stats.py's key tables")


def test_every_registered_metric_documented_in_readme():
    """Drift guard, now delegated to graftcheck's drift-metric-glossary rule:
    the static form covers EVERY registry call site in the package — not just
    the ones a query in this test run happens to execute."""
    from pinot_tpu.analysis import run_project
    from pinot_tpu.analysis.drift_guards import MetricGlossaryRule
    findings, _suppressed, _ctx = run_project(rules=[MetricGlossaryRule()])
    assert not findings, "\n".join(f.render() for f in findings)


def test_query_report_renders_waterfall(tel_cluster, capsys):
    from pinot_tpu.tools.query_report import _extract_stats, render_report
    res = tel_cluster.query("SELECT site, SUM(v) FROM ev GROUP BY site")
    body = render_report(_extract_stats(dict(res.stats)))
    assert "phase waterfall" in body
    assert "compile" in body and "scatter" in body and "reduce" in body
    assert "numDocsScanned" in body and "300" in body
    # also accepts a full response body and a slow-log entry
    body2 = render_report(_extract_stats({"sql": "SELECT 1",
                                          "stats": dict(res.stats)}))
    assert body2.startswith("query: SELECT 1")
