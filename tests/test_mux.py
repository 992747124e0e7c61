"""Mux transport tests: differential vs legacy, concurrency fuzz, zero-copy.

Three proofs the multiplexed data plane (`cluster/mux.py`) must carry:

* the mux and legacy transports are OBSERVABLY IDENTICAL — result bytes,
  stats key sets, EXPLAIN ANALYZE plans, and server span trees all match
  (reference analog: QueryRoutingTest asserting Netty and in-proc dispatch
  agree on DataTable contents);
* tagged responses on one shared connection always land on the right
  request under heavy interleaving, and a mid-stream disconnect fails ONLY
  the in-flight tags before the pool recovers on the next submit;
* a 1M-element array payload is decoded with zero copies
  (`np.shares_memory` against the receive buffer).
"""

import json
import socket
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from pinot_tpu.cluster.broker import Broker
from pinot_tpu.cluster.catalog import Catalog
from pinot_tpu.cluster.controller import Controller
from pinot_tpu.cluster.deepstore import LocalDeepStore
from pinot_tpu.cluster.http_service import HttpService
from pinot_tpu.cluster.mux import MuxClient, serve_mux_stream
from pinot_tpu.cluster.process import BrokerClient, ControllerClient
from pinot_tpu.cluster.remote import ControllerDeepStore, RemoteCatalog
from pinot_tpu.cluster.server import ServerNode
from pinot_tpu.cluster.services import (BrokerService, ControllerService,
                                        ServerService)
from pinot_tpu.cluster.wire import (decode_segment_result, decode_value,
                                    encode_segment_result_parts, encode_value)
from pinot_tpu.query.reduce import DensePartial, SegmentResult
from pinot_tpu.schema import DataType, FieldSpec, Schema
from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig
from pinot_tpu.table import TableConfig


def _wait_until(fn, timeout=15.0):
    from conftest import wait_until
    return wait_until(fn, timeout=timeout, interval=0.05, swallow=())


# -- differential: mux vs legacy over a real HTTP cluster --------------------

SCHEMA = Schema("trips", [
    FieldSpec("city", DataType.STRING),
    FieldSpec("fare", DataType.DOUBLE),
    FieldSpec("n", DataType.INT),
])

#: transport-mechanics spans excluded when diffing server execution trees —
#: the wire decomposition differs BY DESIGN between the two transports
#: (matches the exclusion set in test_tracing's dual-transport differential)
WIRE_SPANS = frozenset(("broker.serialize", "broker.send",
                        "broker.deserialize", "queue_wait",
                        "mux:frame_queue", "mux:flow_control"))


@pytest.fixture
def dual_broker_cluster(tmp_path):
    """Controller + 2 servers + TWO brokers over HTTP: one pinned to the mux
    transport, one pinned to legacy one-exchange-per-query POST /query."""
    catalog = Catalog()
    deepstore = LocalDeepStore(str(tmp_path / "deepstore"))
    controller = Controller("controller_0", catalog, deepstore,
                            str(tmp_path / "ctrl"))
    csvc = ControllerService(controller)
    services = [csvc]
    catalogs = []
    servers = []
    try:
        for i in range(2):
            rc = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
            catalogs.append(rc)
            node = ServerNode(f"server_{i}", rc, ControllerDeepStore(csvc.url),
                              str(tmp_path / f"server_{i}"))
            ssvc = ServerService(node)
            services.append(ssvc)
            servers.append((node, rc, ssvc))
        bsvcs = {}
        for name, mux in (("mux", True), ("legacy", False)):
            rc = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
            catalogs.append(rc)
            bsvc = BrokerService(Broker(f"broker_{name}", rc), mux=mux)
            services.append(bsvc)
            bsvcs[name] = bsvc
        yield {"csvc": csvc, "servers": servers, "bsvcs": bsvcs,
               "tmp": tmp_path}
    finally:
        for rc in catalogs:
            rc.close()
        for s in services:
            s.stop()


def _load_trips(cluster):
    c = ControllerClient(cluster["csvc"].url)
    c.add_schema(SCHEMA)
    cfg = TableConfig("trips", replication=2)
    c.add_table(cfg)
    builder = SegmentBuilder(SCHEMA, SegmentGeneratorConfig())
    seg1 = builder.build(
        {"city": np.array(["nyc", "sf", "nyc", "la"], dtype=object),
         "fare": np.array([10.0, 20.0, 30.0, 7.5], dtype=np.float64),
         "n": np.array([1, 2, 3, 4], dtype=np.int32)},
        str(cluster["tmp"] / "b1"), "trips_0")
    seg2 = builder.build(
        {"city": np.array(["sf", "la", "nyc"], dtype=object),
         "fare": np.array([5.0, 7.0, 2.5], dtype=np.float64),
         "n": np.array([5, 6, 7], dtype=np.int32)},
        str(cluster["tmp"] / "b2"), "trips_1")
    c.upload_segment(cfg.table_name_with_type, seg1)
    c.upload_segment(cfg.table_name_with_type, seg2)
    assert _wait_until(lambda: all(
        len(node.segments_served(cfg.table_name_with_type)) == 2
        for node, _, _ in cluster["servers"]))


def _converged_clients(cluster):
    """Both broker mirrors answering the full-table count: ready to diff."""
    clients = {name: BrokerClient(svc.url)
               for name, svc in cluster["bsvcs"].items()}

    def ready(bc):
        try:
            return bc.query("SELECT COUNT(*) FROM trips"
                            )["resultTable"]["rows"][0][0] == 7
        except Exception:
            return None
    for bc in clients.values():
        assert _wait_until(lambda: ready(bc))
    return clients


def test_mux_vs_legacy_differential(dual_broker_cluster):
    """The two transports return byte-identical result tables, identical
    stats key sets, and matching deterministic counters."""
    _load_trips(dual_broker_cluster)
    clients = _converged_clients(dual_broker_cluster)

    queries = [
        "SELECT city, SUM(fare) AS total FROM trips "
        "GROUP BY city ORDER BY total DESC",
        "SELECT COUNT(*), MIN(n), MAX(fare) FROM trips WHERE fare > 6",
        "SELECT city, fare, n FROM trips WHERE n >= 2 ORDER BY n LIMIT 10",
        "SELECT DISTINCT city FROM trips ORDER BY city",
    ]
    deterministic = ("numDocsScanned", "numSegmentsQueried",
                     "numSegmentsProcessed", "numServersQueried",
                     "numServersResponded", "partialResult",
                     "numEntriesScannedInFilter")
    for sql in queries:
        resp_m = clients["mux"].query(sql)
        resp_l = clients["legacy"].query(sql)
        # byte-identical results
        assert (json.dumps(resp_m["resultTable"], sort_keys=True) ==
                json.dumps(resp_l["resultTable"], sort_keys=True)), sql
        # identical stats surfaces: COUNTER_KEYS zero-fill means the mux-only
        # counters (muxFrameQueueMs/muxFlowControlMs) exist on BOTH sides
        assert set(resp_m) == set(resp_l), sql
        assert "muxFrameQueueMs" in resp_m and "muxFlowControlMs" in resp_m
        for k in deterministic:
            if k in resp_m:
                assert resp_m[k] == resp_l[k], (sql, k)


def test_both_transports_time_the_wire_where_it_runs(dual_broker_cluster):
    """The broker's request encode and answer decode, and the server's request
    decode, ride every answer over both transports; the server's answer
    encode, which cannot ride its own payload, is counted on /health's
    `device` block beside the process's collections."""
    from pinot_tpu.cluster.device_server import DeviceQueryPipeline
    from pinot_tpu.cluster.http_service import get_json
    _load_trips(dual_broker_cluster)
    clients = _converged_clients(dual_broker_cluster)
    for name, bc in clients.items():
        resp = bc.query("SELECT city, SUM(fare) FROM trips GROUP BY city")
        for key in ("scatterSerializeMs", "scatterDeserializeMs",
                    "serverDecodeMs", "serverAcquireMs", "serverMergeMs"):
            assert resp[key] > 0.0, (name, key)
    node, _, ssvc = dual_broker_cluster["servers"][0]
    node.device_pipeline = DeviceQueryPipeline(mesh_exec=object(),
                                               start=False)
    try:
        device = get_json(f"{ssvc.url}/health")["device"]
    finally:
        node.device_pipeline = None
    assert device["encodes"] >= 2 and device["encodeMs"] > 0.0
    assert device["gcCollections"] >= 0 and device["gcPauseMs"] >= 0.0


def test_mux_vs_legacy_explain_analyze(dual_broker_cluster):
    """EXPLAIN ANALYZE through both transports: identical operator trees and
    row counts (the Ms column is wall clock and excluded by design)."""
    _load_trips(dual_broker_cluster)
    clients = _converged_clients(dual_broker_cluster)
    sql = ("EXPLAIN ANALYZE SELECT city, SUM(fare) AS total FROM trips "
           "GROUP BY city ORDER BY total DESC")
    resp_m = clients["mux"].query(sql)
    resp_l = clients["legacy"].query(sql)
    assert (resp_m["resultTable"]["dataSchema"] ==
            resp_l["resultTable"]["dataSchema"])

    def shape(resp):   # [label, id, parent, rows] — drop the Ms column
        return [row[:4] for row in resp["resultTable"]["rows"]]
    assert shape(resp_m) == shape(resp_l)
    assert set(resp_m) == set(resp_l)
    assert resp_m["analyze"] is True


def test_mux_vs_legacy_trace_span_tree(dual_broker_cluster):
    """OPTION(trace=true): the server execution span tree (everything that is
    not wire mechanics) is identical across transports, and each transport
    exposes exactly its own wire spans."""
    _load_trips(dual_broker_cluster)
    clients = _converged_clients(dual_broker_cluster)
    sql = ("SELECT city, SUM(fare) AS total FROM trips GROUP BY city "
           "ORDER BY total DESC OPTION(trace=true)")
    names_m = [s["name"] for s in clients["mux"].query(sql)["traceInfo"]]
    names_l = [s["name"] for s in clients["legacy"].query(sql)["traceInfo"]]

    def exec_tree(names):
        return set(n for n in names
                   if n.rsplit("/", 1)[-1] not in WIRE_SPANS)
    assert exec_tree(names_m) == exec_tree(names_l)
    # both carry the spliced per-server segment spans
    for names in (names_m, names_l):
        assert any(n.startswith("server:server_") and "/segment:" in n
                   for n in names)
    # the mux wire decomposition only appears on the mux transport
    assert "mux:frame_queue" in names_m
    assert "mux:frame_queue" not in names_l


# -- concurrency fuzz against a raw mux stream -------------------------------

@pytest.fixture
def echo_mux():
    """A bare /mux endpoint whose execute echoes the request's value back as
    `num_docs_scanned` — any tag mismatch becomes a visible wrong answer.
    Requests with `hold` block until the gate opens (in-flight on the wire)."""
    pool = ThreadPoolExecutor(max_workers=8, thread_name_prefix="mux-echo")
    gate = threading.Event()
    gate.set()

    def execute(payload, flow_wait_ms):
        d = json.loads(bytes(payload).decode())
        if d.get("hold"):
            gate.wait(timeout=30.0)
        r = SegmentResult("groups")
        r.num_docs_scanned = d["v"]
        return 200, encode_segment_result_parts(r)

    svc = HttpService()
    svc.route("POST", "mux", lambda parts, params, body:
              (200, "application/octet-stream",
               serve_mux_stream(body, execute, executor=pool,
                                max_inflight=32)),
              duplex=True)
    svc.start()
    try:
        yield {"svc": svc, "gate": gate}
    finally:
        gate.set()
        svc.stop()
        pool.shutdown(wait=False)


def _payload(v, hold=False):
    return json.dumps({"v": v, **({"hold": True} if hold else {})}).encode()


def test_mux_concurrent_tag_matching(echo_mux):
    """8 threads x 25 interleaved queries over ONE connection: every response
    lands on the future whose tag requested it."""
    mc = MuxClient(echo_mux["svc"].url, streams=1, timeout_s=30.0)
    try:
        mismatches = []

        def worker(t):
            futs = [(t * 1000 + j, mc.submit(_payload(t * 1000 + j)))
                    for j in range(25)]
            for want, fut in futs:
                got = fut.result(timeout=30.0).num_docs_scanned
                if got != want:
                    mismatches.append((want, got))
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60.0)
        assert not any(th.is_alive() for th in threads)
        assert mismatches == []
    finally:
        mc.close()


def test_mux_out_of_order_completion(echo_mux):
    """Responses are matched by tag, not arrival order: fast queries complete
    while earlier held queries are still in flight on the same stream."""
    gate = echo_mux["gate"]
    mc = MuxClient(echo_mux["svc"].url, streams=1, timeout_s=30.0)
    try:
        gate.clear()
        held = [mc.submit(_payload(100 + i, hold=True)) for i in range(3)]
        fast = [mc.submit(_payload(200 + i)) for i in range(3)]
        for i, fut in enumerate(fast):
            assert fut.result(timeout=15.0).num_docs_scanned == 200 + i
        assert not any(f.done() for f in held)
        gate.set()
        for i, fut in enumerate(held):
            assert fut.result(timeout=15.0).num_docs_scanned == 100 + i
    finally:
        gate.set()
        mc.close()


def test_mux_disconnect_fails_inflight_then_recovers(echo_mux):
    """A mid-stream disconnect fails exactly the in-flight tags with
    ConnectionError (what `_is_transport_failure` expects of a dead server);
    the next submit reconnects and the stream works again."""
    from pinot_tpu.utils.metrics import get_registry
    gate = echo_mux["gate"]
    mc = MuxClient(echo_mux["svc"].url, streams=1, timeout_s=30.0)
    try:
        # a completed exchange on the same stream first
        assert mc.submit(_payload(7)).result(timeout=15.0) \
            .num_docs_scanned == 7

        gate.clear()
        held = [mc.submit(_payload(100 + i, hold=True)) for i in range(4)]
        conn = mc._slots[0]
        assert _wait_until(lambda: len(conn._pending) == 4)

        reconnects = get_registry().counter_value(
            "pinot_broker_mux_reconnects")
        conn._conn.sock.shutdown(socket.SHUT_RDWR)  # sever mid-stream
        for fut in held:
            with pytest.raises(ConnectionError):
                fut.result(timeout=15.0)
        assert _wait_until(lambda: conn.closed)
        gate.set()  # release the server-side executions into the dead stream

        # the pool recovers: the next submit opens a fresh stream
        assert mc.submit(_payload(42)).result(timeout=15.0) \
            .num_docs_scanned == 42
        assert get_registry().counter_value(
            "pinot_broker_mux_reconnects") == reconnects + 1
    finally:
        gate.set()
        mc.close()


# -- zero-copy decode ---------------------------------------------------------

def test_zero_copy_decode_1m_elements():
    """A 1M-element float64 payload decodes as a VIEW over the receive
    buffer — no copy anywhere between the socket read and the ndarray."""
    arr = np.arange(1_000_000, dtype=np.float64)
    buf = encode_value(arr)
    out = decode_value(memoryview(buf))
    assert isinstance(out, np.ndarray)
    assert out.dtype == np.float64 and out.shape == (1_000_000,)
    assert np.array_equal(out, arr)
    assert np.shares_memory(out, np.frombuffer(buf, dtype=np.uint8))


def test_zero_copy_dense_partial_response():
    """The full response path a mux frame carries: a dense group-by partial
    is encoded as gathered parts and decoded as views over the joined frame
    body — counts and every aggregate column share the frame's memory."""
    keys = 1_000_000
    dp = DensePartial(token=("k", (keys,), ("h",), keys), cards=(keys,),
                      strides=(1,), num_keys_real=keys,
                      counts=np.ones(keys, dtype=np.int64),
                      outs={"0.sum": np.arange(keys, dtype=np.float64)},
                      group_values=[np.arange(keys, dtype=np.int64)])
    r = SegmentResult("groups", dense=dp)
    frame = b"".join(bytes(p) for p in encode_segment_result_parts(r))
    decoded = decode_segment_result(memoryview(frame))
    base = np.frombuffer(frame, dtype=np.uint8)
    got = decoded.dense
    assert got is not None and got.num_keys_real == keys
    assert np.array_equal(got.outs["0.sum"], dp.outs["0.sum"])
    for payload in (got.counts, got.outs["0.sum"]):
        assert np.shares_memory(payload, base)
