"""Observability tests: metrics registry, per-phase timers, request tracing.

Reference pattern: the metrics stack (`pinot-common/.../metrics/`, AbstractMetrics +
meter catalogs), per-phase timings (`ServerQueryPhase`/`BrokerQueryPhase`) and the
trace SPI (`pinot-spi/.../trace/Tracing.java`) exercised via OPTION(trace=true).
"""

import numpy as np
import pytest

from pinot_tpu.cluster import QuickCluster
from pinot_tpu.query.executor import execute_query
from pinot_tpu.schema import DataType, FieldSpec, Schema
from pinot_tpu.segment.reader import load_segment
from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig
from pinot_tpu.table import TableConfig
from pinot_tpu.utils.metrics import MetricsRegistry, get_registry
from pinot_tpu.utils.trace import Trace, request_trace, span

from conftest import make_ssb_columns


# -- registry primitives -----------------------------------------------------

def test_counter_gauge_timer():
    reg = MetricsRegistry()
    reg.counter("q").inc()
    reg.counter("q").inc(2)
    assert reg.counter_value("q") == 3
    # labels split the series
    reg.counter("q", {"table": "a"}).inc()
    assert reg.counter_value("q", {"table": "a"}) == 1
    assert reg.counter_value("q") == 3
    reg.gauge("g").set(7.5)
    t = reg.timer("lat")
    with t.time():
        pass
    t.update(10.0)
    assert t.count == 2 and t.max_ms >= 10.0
    snap = reg.snapshot()
    assert snap["q"] == 3 and snap["q{table=a}"] == 1 and snap["g"] == 7.5
    assert snap["lat_count"] == 2


def test_prometheus_render():
    reg = MetricsRegistry()
    reg.counter("pinot_server_queries", {"table": "t1"}).inc(5)
    reg.counter("pinot_server_queries", {"table": "t2"}).inc(1)
    reg.gauge("pinot_up").set(1)
    reg.timer("lat").update(3.0)
    text = reg.render_prometheus()
    assert 'pinot_server_queries{table="t1"} 5.0' in text
    assert 'pinot_server_queries{table="t2"} 1.0' in text
    # exactly ONE TYPE line per family even with multiple labeled series —
    # Prometheus rejects an exposition with duplicate TYPE lines
    assert text.count("# TYPE pinot_server_queries counter") == 1
    assert "pinot_up 1.0" in text
    assert "lat_count 1" in text and "lat_sum 3.0" in text
    # label values escape quotes/backslashes/newlines
    reg.counter("esc", {"q": 'a"b\\c\nd'}).inc()
    assert 'esc{q="a\\"b\\\\c\\nd"} 1.0' in reg.render_prometheus()


# -- trace primitives ---------------------------------------------------------

def test_trace_spans_nest_and_cross_threads():
    import threading
    with request_trace(True) as tr:
        with span("outer"):
            with span("inner"):
                pass

        def worker():
            with tr.activate(), span("thread-side"):
                pass
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    rows = tr.to_rows()
    names = {s["name"]: s for s in rows}
    assert set(names) == {"outer", "inner", "thread-side"}
    assert names["outer"]["depth"] == 0
    assert names["inner"]["depth"] == 1
    assert names["thread-side"]["depth"] == 0


def test_disabled_trace_is_noop():
    with request_trace(False) as tr:
        assert tr is None
        with span("ignored"):
            pass


# -- executor phase timers -----------------------------------------------------

SCHEMA = Schema("obs", [
    FieldSpec("k", DataType.STRING),
    FieldSpec("v", DataType.DOUBLE),
])


@pytest.fixture(scope="module")
def seg(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("obs")
    builder = SegmentBuilder(SCHEMA, SegmentGeneratorConfig())
    d = builder.build({"k": np.array(["a", "b", "a", "c"], dtype=object),
                       "v": np.array([1.0, 2.0, 3.0, 4.0])}, str(tmp), "obs_0")
    return load_segment(d)


def test_executor_phase_times(seg):
    res = execute_query([seg], "SELECT k, SUM(v) FROM obs GROUP BY k")
    pt = res.stats["phaseTimesMs"]
    assert set(pt) == {"compile", "scan", "reduce"}
    assert all(v >= 0 for v in pt.values())


# -- cluster wiring -------------------------------------------------------------

@pytest.fixture()
def lineorder_cluster(tmp_path, ssb_schema):
    cluster = QuickCluster(num_servers=2, work_dir=str(tmp_path))
    rng = np.random.default_rng(11)
    cfg = TableConfig(ssb_schema.name, replication=1, time_column="lo_orderdate")
    cluster.create_table(ssb_schema, cfg)
    for _ in range(2):
        cluster.ingest_columns(cfg, make_ssb_columns(rng, 500))
    return cluster, cfg


def test_broker_and_server_meters(lineorder_cluster):
    cluster, cfg = lineorder_cluster
    reg = get_registry()
    q0 = reg.counter_value("pinot_broker_queries")
    d0 = reg.counter_value("pinot_server_docs_scanned")
    e0 = reg.counter_value("pinot_broker_query_exceptions")

    # group-by: a bare COUNT(*) (even with a foldable filter) answers from
    # metadata and scans 0 docs, which would not move the docs-scanned meter
    res = cluster.query("SELECT lo_region, COUNT(*) FROM lineorder "
                        "GROUP BY lo_region")
    assert sum(r[1] for r in res.rows) == 1000
    assert reg.counter_value("pinot_broker_queries") == q0 + 1
    assert reg.counter_value("pinot_server_docs_scanned") >= d0 + 1000
    assert reg.counter_value(
        "pinot_server_queries", {"table": cfg.table_name_with_type}) >= 1
    assert "phaseTimesMs" in res.stats
    assert set(res.stats["phaseTimesMs"]) == {"compile", "scatter", "reduce"}

    with pytest.raises(Exception):
        cluster.query("SELECT COUNT(*) FROM no_such_table")
    assert reg.counter_value("pinot_broker_query_exceptions") == e0 + 1
    # latency timer observed every successful query
    assert reg.timer("pinot_broker_query_latency_ms").count >= 1


def test_trace_through_broker(lineorder_cluster):
    cluster, cfg = lineorder_cluster
    res = cluster.query("SELECT lo_region, COUNT(*) FROM lineorder "
                        "GROUP BY lo_region OPTION(trace=true)")
    spans = res.stats["traceInfo"]
    names = [s["name"] for s in spans]
    assert {"broker.compile", "broker.scatter", "broker.reduce"} <= set(names)
    assert "server.execute" in names
    assert any(n.startswith("server:") for n in names)
    assert any(n.startswith("segment:") for n in names)
    # untraced query carries no traceInfo
    res2 = cluster.query("SELECT COUNT(*) FROM lineorder")
    assert "traceInfo" not in res2.stats


def test_segment_status_checker_and_task_metrics(tmp_path):
    """Reference: SegmentStatusChecker / TaskMetricsEmitter /
    MinionInstancesCleanupTask periodic controller tasks."""
    import numpy as np
    from pinot_tpu.cluster import QuickCluster
    from pinot_tpu.cluster.catalog import InstanceInfo
    from pinot_tpu.schema import DataType, Schema, dimension, metric
    from pinot_tpu.table import TableConfig
    from pinot_tpu.utils.metrics import get_registry

    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    schema = Schema("m1", [dimension("k"), metric("v", DataType.DOUBLE)])
    cfg = TableConfig("m1")
    cluster.create_table(schema, cfg)
    cluster.ingest_columns(cfg, {"k": ["a", "b"], "v": np.array([1.0, 2.0])})

    st = cluster.controller.run_segment_status_check()
    assert st["m1_OFFLINE"]["segments"] == 1
    assert st["m1_OFFLINE"]["online"] == 1
    reg = get_registry()
    assert reg.gauge("pinot_controller_segments_total",
                     {"table": "m1_OFFLINE"}).value == 1
    assert reg.gauge("pinot_controller_table_converged",
                     {"table": "m1_OFFLINE"}).value == 1

    # dead minion cleanup
    cluster.catalog.register_instance(InstanceInfo("minion_9", "minion"))
    cluster.catalog.set_instance_alive("minion_9", False)
    assert cluster.controller.cleanup_dead_minions() == ["minion_9"]
    assert "minion_9" not in cluster.catalog.instances
    assert cluster.controller.cleanup_dead_minions() == []

    # task metrics over the queue (generate_all may enqueue nothing here;
    # emit must not fail on an empty queue either way)
    counts = cluster.controller.emit_task_metrics()
    assert isinstance(counts, dict)
