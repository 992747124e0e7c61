"""Device-backed serving: broker-routed queries execute through the mesh
executor inside the server role (VERDICT r4 #1).

In-proc tests run the DeviceQueryPipeline against the conftest 8-device CPU
mesh — the same MeshQueryExecutor/shard_map path the TPU server runs — and
prove (a) served results match the host engine, (b) the device pipeline
actually executed them (pipeline stats + metrics counter), (c) concurrent
queries batch into shared fetches, (d) host fallback still answers shapes the
device can't plan. A ProcessCluster test proves the config wiring boots a
REAL server OS process in device mode and serves through a real broker.
Reference: ServerInstance.java:55,120-186 (engine inside the serving role),
BaseServerStarter.java:467-560 (readiness gating).
"""

import threading

import numpy as np
import pytest

from pinot_tpu.cluster import QuickCluster
from pinot_tpu.cluster.device_server import DEVICE_FALLBACK, DeviceQueryPipeline
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.table import StreamConfig, TableConfig, TableType

from conftest import make_ssb_columns


@pytest.fixture()
def device_cluster(tmp_path, ssb_schema):
    """QuickCluster whose single server routes partials through a device
    pipeline over the virtual CPU mesh."""
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline()
    cluster.servers[0].device_pipeline = pipeline
    rng = np.random.default_rng(9)
    cfg = TableConfig(ssb_schema.name)
    cluster.create_table(ssb_schema, cfg)
    for i in range(3):
        cluster.ingest_columns(cfg, make_ssb_columns(rng, 2000))
    yield cluster, pipeline
    pipeline.stop()


DEVICE_QUERIES = [
    # NOTE: COUNT(*) with no WHERE (or with a predicate the planner folds
    # to match-all via column min/max metadata) answers from metadata — no
    # scan, no device. Every query here forces a real scan.
    "SELECT COUNT(*) FROM lineorder WHERE lo_quantity >= 2",
    "SELECT lo_region, SUM(lo_revenue), COUNT(*) FROM lineorder "
    "GROUP BY lo_region ORDER BY lo_region LIMIT 10",
    "SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder "
    "WHERE lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25 LIMIT 5",
    "SELECT lo_brand, SUM(lo_revenue) FROM lineorder GROUP BY lo_brand "
    "ORDER BY SUM(lo_revenue) DESC LIMIT 9",
]


@pytest.mark.parametrize("sql", DEVICE_QUERIES)
def test_served_query_executes_on_device(device_cluster, sql):
    cluster, pipeline = device_cluster
    before = pipeline.dispatched
    res = cluster.query(sql)
    assert pipeline.dispatched == before + 1, \
        "query did not execute through the device pipeline"
    # differential: host-engine cluster answer over the same segments
    host = cluster.servers[0]
    saved, host.device_pipeline = host.device_pipeline, None
    try:
        want = cluster.query(sql)
    finally:
        host.device_pipeline = saved
    assert len(res.rows) == len(want.rows)
    for dr, hr in zip(res.rows, want.rows):
        for dv, hv in zip(dr, hr):
            if isinstance(dv, float):
                assert abs(dv - hv) <= 2e-3 * max(1.0, abs(hv))
            else:
                assert dv == hv


def test_device_metrics_counter(device_cluster):
    cluster, pipeline = device_cluster
    from pinot_tpu.utils.metrics import get_registry
    cluster.query("SELECT COUNT(*) FROM lineorder WHERE lo_quantity >= 2")
    snap = get_registry().snapshot()
    assert any(k.startswith("pinot_server_device_queries") for k in snap), \
        f"no device counter in {list(snap)[:10]}"


def test_concurrent_queries_batch(device_cluster):
    """Concurrent clients drain into shared device fetches: mean batch > 1."""
    cluster, pipeline = device_cluster
    warm = "SELECT COUNT(*) FROM lineorder WHERE lo_quantity >= 2"
    expect = cluster.query(warm).rows[0][0]  # also warms the kernel cache
    b0, d0 = pipeline.batches, pipeline.dispatched
    n_threads, per = 8, 4
    errs = []

    def client():
        try:
            for _ in range(per):
                r = cluster.query(warm)
                assert r.rows[0][0] == expect
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    ts = [threading.Thread(target=client) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    dispatched = pipeline.dispatched - d0
    batches = pipeline.batches - b0
    assert dispatched == n_threads * per
    assert batches < dispatched, \
        f"no batching: {batches} batches for {dispatched} queries"


def test_host_fallback_for_selection(device_cluster):
    """Selection queries are pre-screened on the handler thread: they never
    enter the device pipeline (no batch-window wait) and the host path
    answers."""
    cluster, pipeline = device_cluster
    f0, d0 = pipeline.fallbacks, pipeline.dispatched
    res = cluster.query("SELECT lo_region, lo_revenue FROM lineorder "
                        "WHERE lo_quantity > 48 LIMIT 5")
    assert pipeline.fallbacks == f0 and pipeline.dispatched == d0, \
        "selection should bypass the pipeline entirely"
    assert len(res.rows) <= 5


def test_fallback_sentinel_direct():
    pipeline = DeviceQueryPipeline()
    try:
        from pinot_tpu.query.context import compile_query
        schema = Schema("t", [dimension("a", DataType.STRING),
                              metric("b", DataType.DOUBLE)])
        # no segments -> planning raises inside the loop -> DEVICE_FALLBACK
        ctx = compile_query("SELECT COUNT(*) FROM t", schema)
        assert pipeline.execute_partial(ctx, []) is DEVICE_FALLBACK
    finally:
        pipeline.stop()


def test_realtime_consuming_rides_host_alongside_device(tmp_path):
    """A hybrid moment: committed segments answer on the device path while
    the in-progress consuming rows merge in from the host manager."""
    from pinot_tpu.ingest.stream import MemoryStream
    schema = Schema("ev", [dimension("site", DataType.STRING),
                           metric("clicks", DataType.LONG)])
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline()
    cluster.servers[0].device_pipeline = pipeline
    cfg = TableConfig("ev", table_type=TableType.REALTIME,
                      stream=StreamConfig(stream_type="memory", topic="ev_dev",
                                          flush_threshold_rows=40))
    cluster.create_realtime_table(schema, cfg, num_partitions=1)
    import json as _json
    stream = MemoryStream.get("ev_dev")
    for i in range(100):
        stream.produce(_json.dumps({"site": f"s{i % 4}", "clicks": 1}),
                       partition=0)
    table = cfg.table_name_with_type
    for _ in range(12):
        cluster.pump_realtime(table)
    res = cluster.query("SELECT COUNT(*) FROM ev")
    assert res.rows[0][0] == 100
    pipeline.stop()


def test_process_cluster_device_mode(tmp_path, ssb_schema):
    """REAL OS-process server in device mode behind a real broker: the
    /health endpoint's device stats prove the served path dispatched on the
    mesh executor inside the server process."""
    import json as _json
    import os
    import urllib.request

    from pinot_tpu.cluster.process import ProcessCluster
    from pinot_tpu.segment.writer import SegmentBuilder

    rng = np.random.default_rng(3)
    cols = make_ssb_columns(rng, 4000)
    with ProcessCluster(
            num_servers=1, work_dir=str(tmp_path),
            server_env={"PINOT_TPU_SERVER_DEVICE_ENABLED": "true"}) as cluster:
        cluster.controller.add_schema(ssb_schema)
        cfg = TableConfig(ssb_schema.name)
        cluster.controller.add_table(cfg)
        b = SegmentBuilder(ssb_schema)
        seg = b.build(cols, os.path.join(str(tmp_path), "b"), "lineorder_0")
        cluster.controller.upload_segment(cfg.table_name_with_type, seg)
        import time
        deadline = time.time() + 30
        while time.time() < deadline:
            r = cluster.query("SELECT COUNT(*) FROM lineorder")[
                "resultTable"]["rows"]
            if r and r[0][0] == 4000:
                break
            time.sleep(0.2)
        res = cluster.query("SELECT lo_region, COUNT(*) FROM lineorder "
                            "GROUP BY lo_region ORDER BY lo_region LIMIT 10")
        assert sum(r[1] for r in res["resultTable"]["rows"]) == 4000
        # the server process's health endpoint carries the pipeline stats
        with open(os.path.join(cluster.run_dir, "server_0.ready")) as f:
            url = _json.load(f)["url"]
        st = _json.loads(urllib.request.urlopen(f"{url}/health").read())
        # the group-by dispatched on device (the bare COUNT(*) probe answers
        # from metadata and counts as a fallback)
        assert st["device"]["dispatched"] >= 1, st
        assert st["device"]["batches"] >= 1, st


@pytest.mark.parametrize("device_enabled", [False, True])
def test_process_cluster_role_env(monkeypatch, device_enabled):
    """One process for each chip: only a device server inherits the platform;
    every other role (and a host-engine server) is held to CPU jax."""
    from pinot_tpu.cluster.process import ProcessCluster
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "--some_flag")
    monkeypatch.setattr(ProcessCluster, "_spawn", lambda *a, **k: None)
    monkeypatch.setattr(ProcessCluster, "_await_ready",
                        lambda *a, **k: "http://127.0.0.1:1")
    server_env = ({"PINOT_TPU_SERVER_DEVICE_ENABLED": "true"}
                  if device_enabled else None)
    cluster = ProcessCluster(num_servers=1, server_env=server_env)
    for role in ("controller", "broker", "minion"):
        env = cluster._role_env(role)
        assert env["JAX_PLATFORMS"] == "cpu" and "XLA_FLAGS" not in env
    env = cluster._role_env("server")
    if device_enabled:
        assert env["JAX_PLATFORMS"] == "tpu"
        assert env["XLA_FLAGS"] == "--some_flag"
    else:
        assert env["JAX_PLATFORMS"] == "cpu" and "XLA_FLAGS" not in env


def test_served_high_card_groupby_differential(tmp_path, ssb_schema):
    """High-cardinality GROUP BY through the SERVED device path (the
    chunked kernel feeding an UNTRIMMED server partial that the broker
    reduces) must match numpy exactly."""
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline()
    cluster.servers[0].device_pipeline = pipeline
    rng = np.random.default_rng(21)
    cfg = TableConfig(ssb_schema.name)
    cluster.create_table(ssb_schema, cfg)
    all_cols = {k: [] for k in make_ssb_columns(rng, 1)}
    for i in range(2):
        cols = make_ssb_columns(rng, 30_000)
        for k, v in cols.items():
            all_cols[k].extend(list(v))
        cluster.ingest_columns(cfg, cols)
    d0 = pipeline.dispatched
    res = cluster.query("SELECT lo_custkey, SUM(lo_revenue), COUNT(*) "
                        "FROM lineorder GROUP BY lo_custkey "
                        "ORDER BY SUM(lo_revenue) DESC LIMIT 50")
    assert pipeline.dispatched == d0 + 1, "did not run on the device path"
    keys = np.asarray(all_cols["lo_custkey"])
    revs = np.asarray(all_cols["lo_revenue"], dtype=np.float64)
    sums = {}
    cnts = {}
    for k, v in zip(keys.tolist(), revs.tolist()):
        sums[k] = sums.get(k, 0.0) + v
        cnts[k] = cnts.get(k, 0) + 1
    want = sorted(sums.items(), key=lambda kv: -kv[1])[:50]
    assert len(res.rows) == 50
    for (gk, gs, gc), (wk, ws) in zip(res.rows, want):
        assert gk == wk and gc == cnts[wk]
        assert abs(gs - ws) <= 2e-3 * max(1.0, abs(ws)), (gk, gs, ws)
    pipeline.stop()


def test_upsert_table_bypasses_device(tmp_path):
    """Upsert tables need per-doc validity masks (host state): on a
    device-enabled server they must take the host path and stay correct."""
    import json as _json

    from pinot_tpu.ingest.stream import MemoryStream
    from pinot_tpu.table import UpsertConfig

    schema = Schema("ups", [dimension("pk", DataType.STRING),
                            metric("v", DataType.LONG),
                            metric("ts", DataType.LONG)])
    schema.primary_key_columns = ["pk"]
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline()
    cluster.servers[0].device_pipeline = pipeline
    cfg = TableConfig("ups", table_type=TableType.REALTIME,
                      upsert=UpsertConfig(mode="FULL"),
                      stream=StreamConfig(stream_type="memory",
                                          topic="ups_dev",
                                          flush_threshold_rows=1000))
    cluster.create_realtime_table(schema, cfg, num_partitions=1)
    stream = MemoryStream.get("ups_dev")
    for i in range(60):
        stream.produce(_json.dumps(
            {"pk": f"k{i % 20}", "v": i, "ts": i}), partition=0)
    for _ in range(8):
        cluster.pump_realtime(cfg.table_name_with_type)
    d0 = pipeline.dispatched
    res = cluster.query("SELECT COUNT(*), SUM(v) FROM ups WHERE ts >= 0")
    # 20 live rows (latest per pk: i in 40..59)
    assert res.rows[0][0] == 20
    assert res.rows[0][1] == sum(range(40, 60))
    assert pipeline.dispatched == d0, "upsert query must not ride the device"
    pipeline.stop()


# -- served ORDER-BY-limit via the fused device top-k -----------------------

TOPK_QUERIES = [
    "SELECT lo_orderkey, lo_revenue FROM lineorder "
    "WHERE lo_quantity >= 10 ORDER BY lo_revenue DESC LIMIT 7",
    "SELECT lo_orderkey, lo_extendedprice FROM lineorder "
    "ORDER BY lo_extendedprice LIMIT 12",
    # NOTE: ordering by lo_orderdate would fall back by design — yyyymmdd
    # ints exceed 2^24, past f32's exact-integer range for the score pass
    "SELECT lo_orderkey, lo_orderdate, lo_revenue FROM lineorder "
    "WHERE lo_discount BETWEEN 1 AND 3 ORDER BY lo_orderkey LIMIT 9",
]


def _host_answer(cluster, sql):
    host = cluster.servers[0]
    saved, host.device_pipeline = host.device_pipeline, None
    try:
        return cluster.query(sql)
    finally:
        host.device_pipeline = saved


@pytest.mark.parametrize("sql", TOPK_QUERIES)
def test_served_orderby_limit_executes_topk_on_device(device_cluster, sql):
    """ORDER-BY-limit selections ride the fused filter+top_k kernel through
    the REAL ServerNode path: dispatched (not fallback) and row-for-row
    equal to the host reducer (unique random doubles -> no tie ambiguity)."""
    cluster, pipeline = device_cluster
    d0, f0 = pipeline.dispatched, pipeline.fallbacks
    res = cluster.query(sql)
    assert pipeline.dispatched == d0 + 1, \
        "ORDER-BY-limit selection did not execute through the device pipeline"
    assert pipeline.fallbacks == f0, "device top-k fell back to host"
    want = _host_answer(cluster, sql)
    assert res.rows == want.rows


def test_served_orderby_tie_keys_match_host(tmp_path):
    """Heavy ties: device and host may break ties differently (both are
    valid per SQL), but the ordered KEY multiset and row count must agree,
    and every device row must exist in the table."""
    schema = Schema("tt", [dimension("id", DataType.LONG),
                           metric("grade", DataType.INT),
                           metric("score", DataType.DOUBLE)])
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline()
    cluster.servers[0].device_pipeline = pipeline
    cfg = TableConfig("tt")
    cluster.create_table(schema, cfg)
    rng = np.random.default_rng(5)
    n = 3000
    rows = {"id": np.arange(n, dtype=np.int64),
            "grade": rng.integers(0, 4, n).astype(np.int32),  # 4 values: ties
            "score": np.round(rng.uniform(0, 100, n), 2)}
    cluster.ingest_columns(cfg, rows)
    try:
        sql = "SELECT id, grade FROM tt ORDER BY grade DESC LIMIT 40"
        d0 = pipeline.dispatched
        res = cluster.query(sql)
        assert pipeline.dispatched == d0 + 1
        want = _host_answer(cluster, sql)
        assert len(res.rows) == len(want.rows) == 40
        assert [r[1] for r in res.rows] == [r[1] for r in want.rows]
        by_id = dict(zip(rows["id"].tolist(), rows["grade"].tolist()))
        for rid, rgrade in res.rows:
            assert by_id[rid] == rgrade
    finally:
        pipeline.stop()


def test_served_orderby_nan_falls_back_to_host(tmp_path):
    """NaN order keys poison lax.top_k comparisons: the kernel reports
    nanMatches and the pipeline resolves DEVICE_FALLBACK — the host reducer
    (NaN-as-null ordering) answers, and device/host agree by construction."""
    schema = Schema("nt", [dimension("id", DataType.LONG),
                           metric("score", DataType.DOUBLE)])
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline()
    cluster.servers[0].device_pipeline = pipeline
    cfg = TableConfig("nt")
    cluster.create_table(schema, cfg)
    rng = np.random.default_rng(6)
    n = 2000
    score = np.round(rng.uniform(0, 100, n), 2)
    score[rng.choice(n, 25, replace=False)] = np.nan
    cluster.ingest_columns(cfg, {"id": np.arange(n, dtype=np.int64),
                                 "score": score})
    try:
        sql = "SELECT id, score FROM nt ORDER BY score DESC LIMIT 10"
        f0 = pipeline.fallbacks
        res = cluster.query(sql)
        assert pipeline.fallbacks == f0 + 1, \
            "NaN order keys must force the host fallback"
        want = _host_answer(cluster, sql)
        assert res.rows == want.rows
    finally:
        pipeline.stop()


def test_served_orderby_nulls_parity(tmp_path):
    """Null cells reach BOTH reducers as the column's null fill (the stored
    sentinel), so device top-k and host sort place them identically —
    including under NULLS LAST, which only reorders genuine None keys that
    the selection path never produces."""
    schema = Schema("nl", [dimension("id", DataType.LONG),
                           metric("score", DataType.DOUBLE)])
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline()
    cluster.servers[0].device_pipeline = pipeline
    cfg = TableConfig("nl")
    cluster.create_table(schema, cfg)
    rng = np.random.default_rng(7)
    n = 1500
    vals = list(np.round(rng.uniform(1, 100, n), 2))
    for i in rng.choice(n, 30, replace=False):
        vals[int(i)] = None  # stored as the DOUBLE metric null fill (0.0)
    cluster.ingest_columns(cfg, {"id": np.arange(n, dtype=np.int64),
                                 "score": vals})
    try:
        for sql in (
                "SELECT id, score FROM nl ORDER BY score LIMIT 35",
                "SELECT id, score FROM nl ORDER BY score ASC NULLS LAST "
                "LIMIT 35",
                "SELECT id, score FROM nl ORDER BY score DESC NULLS LAST "
                "LIMIT 8"):
            d0, f0 = pipeline.dispatched, pipeline.fallbacks
            res = cluster.query(sql)
            assert pipeline.dispatched == d0 + 1, sql
            assert pipeline.fallbacks == f0, sql
            want = _host_answer(cluster, sql)
            assert [r[1] for r in res.rows] == [r[1] for r in want.rows], sql
    finally:
        pipeline.stop()


def test_served_stacked_same_shape_queries_one_launch(tmp_path, ssb_schema):
    """N concurrent same-plan-shape aggregations (different literals) share
    ONE traced executable and ONE stacked kernel launch, with differential
    correctness per query."""
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    pipeline = DeviceQueryPipeline(start=False)
    cluster.servers[0].device_pipeline = pipeline
    rng = np.random.default_rng(13)
    cfg = TableConfig(ssb_schema.name)
    cluster.create_table(ssb_schema, cfg)
    for _ in range(2):
        cluster.ingest_columns(cfg, make_ssb_columns(rng, 2500))
    try:
        thresholds = [5, 12, 24, 36, 12]  # duplicate 12 -> dedupe hit
        sqls = [("SELECT COUNT(*), SUM(lo_revenue) FROM lineorder "
                 f"WHERE lo_quantity >= {q}") for q in thresholds]
        results = [None] * len(sqls)

        def run(i):
            results[i] = cluster.query(sqls[i])

        ts = [threading.Thread(target=run, args=(i,)) for i in range(len(sqls))]
        for t in ts:
            t.start()
        import time
        deadline = time.time() + 10
        while pipeline._q.qsize() < len(sqls) and time.time() < deadline:
            time.sleep(0.01)
        pipeline.start()
        for t in ts:
            t.join(timeout=120)
        s = pipeline.stats()
        assert s["dispatched"] == len(sqls)
        assert s["launches"] == 1, s
        assert s["stackedLaunches"] == 1, s
        assert s["dedupeHits"] == 1, s
        host = cluster.servers[0]
        saved, host.device_pipeline = host.device_pipeline, None
        try:
            for i, sql in enumerate(sqls):
                want = cluster.query(sql)
                for dr, hr in zip(results[i].rows, want.rows):
                    for dv, hv in zip(dr, hr):
                        if isinstance(dv, float):
                            assert abs(dv - hv) <= 2e-3 * max(1.0, abs(hv))
                        else:
                            assert dv == hv
        finally:
            host.device_pipeline = saved
    finally:
        pipeline.stop()


def test_pipeline_stage_histograms_exported(device_cluster):
    """The stage timings ride the process metrics registry as Prometheus
    histograms — the /metrics body a scraper sees."""
    from pinot_tpu.utils.metrics import get_registry
    cluster, pipeline = device_cluster
    cluster.query("SELECT COUNT(*) FROM lineorder WHERE lo_quantity >= 2")
    text = get_registry().render_prometheus()
    for stage in ("queue_wait", "dispatch", "prepare", "launch", "handoff",
                  "fetch", "decode"):
        name = f"pinot_server_device_pipeline_{stage}_ms"
        assert f"# TYPE {name} histogram" in text, name
        assert f'{name}_bucket{{le="+Inf"}}' in text, name
        assert get_registry().histogram(name).count >= 1, name
    st = pipeline.stats()
    assert "stageMs" not in st and "meanBatch" not in st
    assert st["batches"] >= 1 and st["drainsClosedIdle"] >= 1
