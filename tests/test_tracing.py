"""Always-on sampled tracing: the sampler/ring primitives, the Chrome
trace-event export, and the distributed span tree over BOTH transports.

Acceptance shape (ISSUE 6): every slow-query log line carries a trace id that
resolves at GET /debug/traces, whose spans decompose the broker<->server HTTP
hop (broker.serialize / broker.send / queue_wait / broker.deserialize /
device exec); the Chrome
export of a sampled multi-server query loads as a valid timeline; the in-proc
transport produces the SAME server-execution span tree as HTTP.
"""

import json
import random
import re
import threading
import time

import numpy as np
import pytest

from pinot_tpu.cluster import QuickCluster
from pinot_tpu.query.scheduler import QueryScheduler
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.table import TableConfig
from pinot_tpu.utils import trace as tracing
from pinot_tpu.utils.trace import (Trace, TraceRing, TraceSampler,
                                   request_trace, span, to_chrome_trace)

# broker-side wire spans + scheduler admission: transport mechanics, not
# server execution — excluded from the dual-transport differential (the mux
# transport adds frame-queue / flow-control decomposition to the same hop)
WIRE_SPANS = frozenset(("broker.serialize", "broker.send",
                        "broker.deserialize", "queue_wait",
                        "mux:frame_queue", "mux:flow_control"))


# -- satellite: sampler determinism ------------------------------------------

def test_sampler_seeded_rng_is_deterministic():
    a = TraceSampler(rng=random.Random(42))
    b = TraceSampler(rng=random.Random(42))
    decisions_a = [a.sample(0.3) for _ in range(200)]
    decisions_b = [b.sample(0.3) for _ in range(200)]
    assert decisions_a == decisions_b
    assert any(decisions_a) and not all(decisions_a)


def test_sampler_rate_edges_never_consult_rng():
    class Boom:
        def random(self):
            raise AssertionError("rng consulted for a 0/1 rate")

    s = TraceSampler(rng=Boom())
    assert s.sample(0.0) is False
    assert s.sample(-1.0) is False
    assert s.sample(1.0) is True
    assert s.sample(2.0) is True


# -- satellite: ring bounds under concurrency --------------------------------

def test_trace_ring_bounded_under_concurrent_admits():
    ring = TraceRing(capacity=8)
    per_thread = 100
    admitted = [[] for _ in range(4)]

    def admit(i):
        for j in range(per_thread):
            tr = Trace(f"req-{i}-{j}")
            tr.sampled = True
            ring.admit(tr, sql=f"SELECT {i * per_thread + j}")
            admitted[i].append(tr.trace_id)

    threads = [threading.Thread(target=admit, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(ring) == 8
    entries = ring.entries()
    assert len(entries) == 8
    # every retained entry resolves by id; evicted ids return None
    for e in entries:
        assert ring.get(e["traceId"]) is e
    retained = {e["traceId"] for e in entries}
    for ids in admitted:
        for tid in ids:
            if tid not in retained:
                assert ring.get(tid) is None
    # the globally newest admit survived (eviction is strictly oldest-first),
    # and it was some thread's final admit
    assert any(entries[0]["traceId"] == ids[-1] for ids in admitted)


def test_trace_ring_entries_newest_first_with_limit():
    ring = TraceRing(capacity=4)
    ids = []
    for i in range(6):
        tr = Trace(f"r{i}")
        ring.admit(tr, seq=i)
        ids.append(tr.trace_id)
    assert [e["seq"] for e in ring.entries()] == [5, 4, 3, 2]
    assert [e["seq"] for e in ring.entries(limit=2)] == [5, 4]
    assert ring.get(ids[0]) is None     # evicted
    assert ring.get(ids[-1])["seq"] == 5


# -- satellite: error spans ---------------------------------------------------

def test_span_marks_error_and_reraises():
    with request_trace(True) as tr:
        with pytest.raises(ValueError):
            with span("explode"):
                raise ValueError("boom")
        with span("fine"):
            pass
    rows = {s["name"]: s for s in tr.to_rows()}
    assert rows["explode"]["error"] is True
    assert "error" not in rows["fine"]


# -- tentpole: Chrome trace-event export --------------------------------------

def _assert_valid_chrome_doc(doc):
    """Schema-check a Chrome trace-event document (the subset Perfetto and
    chrome://tracing require of the JSON object format)."""
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    json.loads(json.dumps(doc))        # round-trips as pure JSON
    for ev in events:
        assert ev["ph"] in ("M", "X", "C")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
            assert isinstance(ev["args"]["name"], str)
        elif ev["ph"] == "C":
            # HBM residency counter track (the device-memory plane)
            assert ev["cat"] == "memory"
            assert ev["ts"] >= 0
            assert isinstance(ev["args"]["bytes"], (int, float))
        else:
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert isinstance(ev["args"]["depth"], int)


def test_chrome_export_splits_tracks_per_server_hop():
    tr = Trace("q1")
    tr.sampled = True
    tr.record("compile", 0.0, 1.0)
    tr.record("server:server_0", 1.0, 5.0, depth=1)
    tr.record("server:server_0/segment:ev_0", 2.0, 3.0, depth=2)
    tr.record("server:server_1/segment:ev_1", 2.0, 3.0, depth=2,
              error=True)
    # a clock-skewed negative start must clamp, not corrupt the timeline
    tr.record("server:server_0/deserialize", -0.4, 0.4, depth=2)
    ring = TraceRing()
    ring.admit(tr, sql="SELECT 1")
    doc = to_chrome_trace(ring.entries())
    _assert_valid_chrome_doc(doc)
    events = doc["traceEvents"]
    names = {ev["args"]["name"] for ev in events if ev["ph"] == "M"}
    assert {"broker", "server:server_0", "server:server_1"} <= names
    proc = next(ev for ev in events
                if ev["ph"] == "M" and ev["name"] == "process_name")
    assert tr.trace_id in proc["args"]["name"]
    assert "SELECT 1" in proc["args"]["name"]
    # per-hop tracks: broker spans and each server's spans get distinct tids
    tid_of = {ev["args"]["name"]: ev["tid"] for ev in events
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    x_events = {ev["name"]: ev for ev in events if ev["ph"] == "X"}
    assert x_events["compile"]["tid"] == tid_of["broker"]
    assert x_events["server:server_0"]["tid"] == tid_of["broker"]
    assert x_events["server:server_0/segment:ev_0"]["tid"] == \
        tid_of["server:server_0"]
    assert x_events["server:server_1/segment:ev_1"]["tid"] == \
        tid_of["server:server_1"]
    assert x_events["server:server_1/segment:ev_1"]["args"]["error"] is True
    assert x_events["server:server_0/deserialize"]["ts"] == 0.0


# -- tentpole: dual-transport span-tree differential + HTTP acceptance -------

@pytest.fixture
def inproc_traced(tmp_path):
    cluster = QuickCluster(num_servers=2, work_dir=str(tmp_path))
    # same admission control as the HTTP fixture so queue_wait appears on
    # both transports
    for s in cluster.servers:
        s.scheduler = QueryScheduler(max_concurrent=2)
    schema = Schema("ev", [dimension("site", DataType.STRING),
                           metric("v", DataType.LONG)])
    cfg = TableConfig("ev", replication=1)
    cluster.create_table(schema, cfg)
    for i in range(2):
        cluster.ingest_columns(cfg, {
            "site": np.array(["a", "b"] * 10),
            "v": np.arange(20, dtype=np.int64) + i,
        })
    return cluster


@pytest.fixture
def http_traced(tmp_path):
    """A real HTTP cluster (controller + 2 scheduled servers + broker), torn
    down after the test. Yields (broker_service_url, broker, controller
    catalog, query client)."""
    from conftest import wait_until
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.catalog import Catalog
    from pinot_tpu.cluster.controller import Controller
    from pinot_tpu.cluster.deepstore import LocalDeepStore
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
    services, catalogs, nodes = [csvc], [], []
    try:
        for i in range(2):
            rc = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
            catalogs.append(rc)
            node = ServerNode(f"server_{i}", rc, ControllerDeepStore(csvc.url),
                              str(tmp_path / f"server_{i}"),
                              scheduler=QueryScheduler(max_concurrent=2))
            nodes.append(node)
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
        b = SegmentBuilder(schema, SegmentGeneratorConfig())
        for i in range(2):
            seg = b.build({"site": np.array(["a", "b"] * 10, dtype=object),
                           "v": np.arange(20, dtype=np.int64) + i},
                          str(tmp_path / "b"), f"ev_{i}")
            cc.upload_segment(cfg.table_name_with_type, seg)
        assert wait_until(
            lambda: sum(len(n.segments_served(cfg.table_name_with_type))
                        for n in nodes) == 2,
            timeout=15.0, interval=0.05, swallow=())
        bc = BrokerClient(bsvc.url)

        def query(sql):
            return bc.query(sql)

        assert wait_until(
            lambda: _try(lambda: query("SELECT COUNT(*) FROM ev")) is not None,
            timeout=15.0, interval=0.1, swallow=())
        yield bsvc.url, broker, catalog, query
    finally:
        for c in catalogs:
            c.close()
        for s in services:
            s.stop()


def _try(fn):
    try:
        return fn()
    except Exception:
        return None


def _server_exec_shape(spans):
    """Normalize one transport's server-execution spans to a comparable
    shape: {(basename, depth relative to its dispatch span)}. HTTP spans are
    spliced in as `server:<id>/<name>`; in-proc spans run under the dispatch
    span directly."""
    dispatch_depth = {s["name"]: s["depth"] for s in spans
                      if re.fullmatch(r"server:server_\d+", s["name"])}
    shape = set()
    for s in spans:
        name, depth = s["name"], s["depth"]
        m = re.match(r"(server:server_\d+)/(.+)", name)
        if m:                                   # HTTP: spliced + prefixed
            base, rel = m.group(2), depth - dispatch_depth[m.group(1)]
        elif name in dispatch_depth or name.startswith("broker."):
            continue                            # broker-side spans
        else:                                   # in-proc: shared trace
            base, rel = name, depth - min(dispatch_depth.values())
        base = re.sub(r"^segment:ev_\d+$", "segment:*", base)
        if base in WIRE_SPANS or base.startswith("pipeline:"):
            continue
        shape.add((base, rel))
    return shape


def test_dual_transport_span_tree_differential(inproc_traced, http_traced):
    sql = "SELECT site, SUM(v) FROM ev GROUP BY site OPTION(trace=true)"
    inproc_spans = inproc_traced.query(sql).stats["traceInfo"]
    _url, _broker, _catalog, query = http_traced
    http_spans = query(sql)["traceInfo"]
    # both transports dispatched to real servers under a dispatch span
    for spans in (inproc_spans, http_spans):
        assert any(re.fullmatch(r"server:server_\d+", s["name"])
                   for s in spans), [s["name"] for s in spans]
    # HTTP decomposes the hop with wire spans the in-proc transport never pays
    http_names = {s["name"] for s in http_spans}
    assert {"broker.serialize", "broker.send",
            "broker.deserialize"} <= http_names
    assert any(n.endswith("/queue_wait") for n in http_names)
    # ... but the server-execution tree (what ran, nested where) is IDENTICAL
    assert _server_exec_shape(inproc_spans) == _server_exec_shape(http_spans)


def test_http_slow_query_resolves_at_debug_traces(http_traced):
    """The acceptance path: slow log line -> traceId -> GET /debug/traces?id=
    -> spans decomposing the broker<->server hop; plus the Chrome export."""
    import logging

    from conftest import wait_until
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.http_service import HttpError, get_json

    url, broker, catalog, query = http_traced
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    h = Capture()
    logger = logging.getLogger(Broker.SLOW_QUERY_LOGGER)
    logger.addHandler(h)
    catalog.put_property("clusterConfig/broker.slow.query.ms", "0")
    try:
        # the broker reads its RemoteCatalog MIRROR; wait for the watch loop
        assert wait_until(
            lambda: broker.catalog.get_property(
                "clusterConfig/broker.slow.query.ms") == "0",
            timeout=10.0, interval=0.05, swallow=())
        query("SELECT COUNT(*) FROM ev")
    finally:
        catalog.put_property("clusterConfig/broker.slow.query.ms", None)
        logger.removeHandler(h)
    entry = json.loads(records[-1].getMessage())
    trace_id = entry["stats"]["traceId"]
    assert re.fullmatch(r"[0-9a-f]{16}", trace_id)

    got = get_json(f"{url}/debug/traces?id={trace_id}")
    assert got["traceId"] == trace_id
    assert got["slow"] is True
    names = {s["name"] for s in got["spans"]}
    # the 110ms-floor decomposition: wire + admission + server execution
    assert {"broker.serialize", "broker.send", "broker.deserialize"} <= names
    assert any(n.endswith("/server.merge") for n in names)
    assert any(n.endswith("/queue_wait") for n in names)
    assert any(re.match(r"server:server_\d+/(segment:|device)", n)
               for n in names), sorted(names)

    # the listing carries it too, and unknown ids 404
    listing = get_json(f"{url}/debug/traces")
    assert any(e["traceId"] == trace_id for e in listing["traces"])
    assert listing["capacity"] >= listing["retained"] >= 1
    with pytest.raises(HttpError):
        get_json(f"{url}/debug/traces?id=deadbeefdeadbeef")

    # Chrome export of the retained trace is a loadable timeline
    doc = get_json(f"{url}/debug/traces?id={trace_id}&format=chrome")
    _assert_valid_chrome_doc(doc)


def test_http_sampled_multi_server_chrome_export(http_traced):
    """sample.rate=1 through clusterConfig: a multi-server query lands in the
    ring WITHOUT OPTION(trace=true), and its Chrome export carries one track
    per server hop."""
    from conftest import wait_until
    from pinot_tpu.cluster.http_service import get_json

    url, broker, catalog, query = http_traced
    catalog.put_property("clusterConfig/broker.trace.sample.rate", "1")
    try:
        assert wait_until(
            lambda: broker.catalog.get_property(
                "clusterConfig/broker.trace.sample.rate") == "1",
            timeout=10.0, interval=0.05, swallow=())
        resp = query("SELECT site, SUM(v) FROM ev GROUP BY site")
    finally:
        catalog.put_property("clusterConfig/broker.trace.sample.rate", None)
    assert "traceInfo" not in resp          # sampling retains, never inlines
    trace_id = resp["traceId"]
    entry = get_json(f"{url}/debug/traces?id={trace_id}")
    assert entry["sampled"] is True
    doc = get_json(f"{url}/debug/traces?id={trace_id}&format=chrome")
    _assert_valid_chrome_doc(doc)
    tracks = {ev["args"]["name"] for ev in doc["traceEvents"]
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    # both servers held a segment, so both hops get their own track
    assert {"broker", "server:server_0", "server:server_1"} <= tracks


def test_query_report_renders_exported_traces(http_traced, capsys):
    """Satellite: saved /debug/traces output analyzes offline."""
    from pinot_tpu.cluster.http_service import get_json
    from pinot_tpu.tools.query_report import _trace_entries, render_trace

    url, _broker, _catalog, query = http_traced
    query("SELECT COUNT(*) FROM ev OPTION(trace=true)")
    listing = get_json(f"{url}/debug/traces")
    entries = _trace_entries(listing)
    assert entries
    body = render_trace(entries[0])
    assert body.startswith("trace: ")
    assert "broker.serialize" in body
    # the chrome form folds back into the same waterfall
    chrome = _trace_entries(get_json(f"{url}/debug/traces?format=chrome"))
    assert chrome and any("broker.serialize" in s["name"]
                          for e in chrome for s in e["spans"])


# -- the second sink: the profiler's clock (PR 26) ----------------------------

def _profiled(tmp_path, body):
    """Run `body()` under a profiler session; the planes of its .xplane.pb as
    benchmark/harness/program_trace.py reads them."""
    import jax
    from benchmark.harness import program_trace, trace_reduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return program_trace.load(trace_reduce.newest_xplane(str(tmp_path)))


def _host_events(planes, prefix="pinot:"):
    return {e[0]: e for p in planes if p["name"].startswith("/host:CPU")
            for ln in p["lines"] for e in ln["events"]
            if e[0].startswith(prefix)}


def test_stage_times_its_body_and_annotates_the_profile(tmp_path):
    """`stage()` is the span of a thread with no request Trace: the caller
    reads its milliseconds, and under a profiler session it is an event
    `pinot:<name>` carrying its attributes, those noted afterwards too."""
    def body():
        with tracing.stage("pipeline.fetch", batch=3) as st:
            time.sleep(0.02)
            st.note(launches=2)
        assert 15.0 < st.ms < 2000.0
    events = _host_events(_profiled(tmp_path, body))
    name, _start, dur_ns, stats = events["pinot:pipeline.fetch"]
    assert dur_ns >= 15e6
    assert stats == {"batch": 3, "launches": 2}


def test_stage_without_a_profiler_session_only_times():
    with tracing.stage("pipeline.wait") as st:
        time.sleep(0.005)
    assert st.ms >= 4.0
    assert tracing.current_trace() is None      # and it opened no Trace


def test_stage_records_cpu_beside_wall():
    """With `cpu=True`, `cpu_ms` is the thread's CPU over the body: never
    above the wall, near 0 for a body that sleeps, near the wall for one that
    computes. Without, the CPU clock (a system call) is not read."""
    with tracing.stage("pipeline.wait") as unread:
        pass
    assert unread.cpu_ms is None
    with tracing.stage("pipeline.wait", cpu=True) as asleep:
        time.sleep(0.03)
    assert asleep.cpu_ms <= asleep.ms
    assert asleep.cpu_ms < 5.0 and asleep.ms >= 25.0
    with tracing.stage("pipeline.prepare", cpu=True) as busy:
        t_end = time.perf_counter() + 0.03
        while time.perf_counter() < t_end:
            pass
    assert busy.cpu_ms <= busy.ms + 0.5      # the two clocks' granularity
    assert busy.cpu_ms > 0.3 * busy.ms


def test_span_yields_its_timings_with_and_without_a_trace():
    tr = tracing.Trace("r")
    with tr.activate():
        with tracing.span("server.merge") as sp:
            time.sleep(0.01)
    assert sp.ms >= 9.0 and sp.cpu_ms is None
    (row,) = tr.to_rows()
    assert row["name"] == "server.merge"
    assert row["durationMs"] == pytest.approx(sp.ms, abs=1e-3)
    with tracing.span("server.acquire") as orphan:      # no Trace
        time.sleep(0.005)
    assert orphan.ms >= 4.0 and len(tr.to_rows()) == 1


def test_gc_hook_counts_a_full_collection_and_spans_it(tmp_path, monkeypatch):
    """One hook a process: a `gc.collect(2)` counts as a collection with its
    pause, and opens and closes `pinot:gc` (generation 2) on the thread that
    collected; a young collection is counted and not spanned."""
    import gc

    tracing.install_gc_hook()
    tracing.install_gc_hook()                   # idempotent
    assert gc.callbacks.count(tracing._on_gc) == 1
    before = tracing.gc_stats()
    gc.collect(2)
    after = tracing.gc_stats()
    assert after["gcCollections"] >= before["gcCollections"] + 1
    assert after["gcPauseMs"] > before["gcPauseMs"]

    seen = []

    class Recorder(tracing.stage):
        __slots__ = ()

        def __enter__(self):
            seen.append(("enter", threading.get_ident()))
            return super().__enter__()

        def __exit__(self, *exc):
            seen.append(("exit", threading.get_ident()))
            return super().__exit__(*exc)
    monkeypatch.setattr(tracing, "stage", Recorder)
    gc.collect(0)
    assert seen == []
    worker = threading.Thread(target=gc.collect, args=(2,))
    worker.start()
    worker.join()
    assert [k for k, _ in seen] == ["enter", "exit"]
    assert seen[0][1] == seen[1][1] == worker.ident
    monkeypatch.undo()
    events = _host_events(_profiled(tmp_path, lambda: gc.collect(2)))
    assert events["pinot:gc"][3] == {"generation": 2}


def test_span_lands_in_both_sinks_with_the_trace_id(tmp_path):
    """One name, one identifier, two sinks: the request Trace's row and the
    profiler's `pinot:<name>` event with the request's trace_id."""
    tr = tracing.Trace("r1")

    def body():
        with tr.activate():
            with tracing.span("broker.compile"):
                with tracing.span("inner"):
                    time.sleep(0.002)
        with tracing.span("orphan"):        # no Trace: annotation only
            pass
    events = _host_events(_profiled(tmp_path, body))
    assert [(s["name"], s["depth"]) for s in tr.to_rows()] == [
        ("broker.compile", 0), ("inner", 1)]
    assert events["pinot:broker.compile"][3] == {"trace_id": tr.trace_id}
    assert events["pinot:inner"][3] == {"trace_id": tr.trace_id}
    assert events["pinot:orphan"][3] == {}
    outer, inner = events["pinot:broker.compile"], events["pinot:inner"]
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def test_served_query_spans_share_the_trace_id_in_the_profile(
        inproc_traced, tmp_path):
    """A served query under a profiler session: broker.compile / scatter /
    reduce and server.execute land on the profiler's clock with the trace id
    the response carries."""
    out = {}

    def body():
        out["res"] = inproc_traced.query(
            "SELECT site, SUM(v) FROM ev GROUP BY site")
    planes = _profiled(tmp_path, body)
    trace_id = out["res"].stats["traceId"]
    events = [e for p in planes if p["name"].startswith("/host:CPU")
              for ln in p["lines"] for e in ln["events"]
              if e[3].get("trace_id") == trace_id]
    names = {e[0] for e in events}
    assert {"pinot:broker.compile", "pinot:broker.scatter",
            "pinot:broker.reduce", "pinot:server.execute"} <= names, names


# -- names on the device: scopes in the lowered program (PR 26) ----------------

@pytest.fixture(scope="module")
def scope_segment(tmp_path_factory):
    """6000 keys (padded 8192): every GROUP BY regime is a choice of caps. `d`
    is a dictionary-encoded metric (the fused in-register decode), `w` a raw
    one (a compare leaf for the filter)."""
    import numpy as np

    from pinot_tpu.schema import DataType, Schema, dimension, metric
    from pinot_tpu.segment.reader import load_segment
    from pinot_tpu.segment.writer import (SegmentBuilder,
                                          SegmentGeneratorConfig)
    rng = np.random.default_rng(26)
    rows = 8000
    schema = Schema("scopes", [dimension("k", DataType.INT),
                               dimension("d", DataType.INT),
                               metric("w", DataType.INT)])
    cols = {"k": rng.integers(0, 6000, rows).astype(np.int32),
            "d": rng.integers(0, 40, rows).astype(np.int32) * 7,
            "w": rng.integers(-1000, 1000, rows).astype(np.int32)}
    out = tmp_path_factory.mktemp("scopes")
    builder = SegmentBuilder(schema, SegmentGeneratorConfig(
        no_dictionary_columns=["w"]))
    return load_segment(builder.build(cols, str(out), "scopes_0"))


def _lowered_text(seg, sql):
    """The served path's program for `sql`, lowered with debug info: the
    name stack of every operation (what becomes `tf_op` in a device trace)."""
    from pinot_tpu.parallel.combine import MeshQueryExecutor
    from pinot_tpu.query.context import compile_query
    mex = MeshQueryExecutor()
    p = mex.prepare_partial(compile_query(sql, seg.schema), [seg])
    assert p is not None
    kern = mex._get_shard_kernel(p.spec, p.s_pad, p.rows)
    jitted = kern.__wrapped__.jitted_for(p.inputs)
    return p, jitted.lower(p.inputs).as_text(debug_info=True)


@pytest.mark.parametrize("regime,caps", [
    ("onehot", dict(matmul_cap=16384)),
    ("chunk64", dict()),
    ("partitioned", dict(chunk_cap=4096)),
])
def test_groupby_regime_scope_in_lowered_program(scope_segment, regime, caps):
    """Each GROUP BY regime's program names its stages: its own
    `pinot.groupby.<regime>` scope, the decode and the filter; and the jitted
    function is named for what it is, not `shard_body`."""
    from pinot_tpu.engine.caps import KernelCaps, get_caps, set_caps
    prev = get_caps()
    set_caps(KernelCaps(**caps))
    try:
        p, text = _lowered_text(
            scope_segment,
            "SELECT k, COUNT(*), SUM(d) FROM scopes WHERE w > 0 GROUP BY k")
    finally:
        set_caps(prev)
    assert p.spec.fused_cols, "SUM(d) should decode the dictionary in-register"
    for scope in (f"pinot.groupby.{regime}", "pinot.groupby.key",
                  "pinot.decode", "pinot.filter"):
        assert scope in text, scope
    others = {"onehot", "chunk64", "partitioned"}
    for other in others - {regime}:
        assert f"pinot.groupby.{other}" not in text, other
    if regime == "partitioned":
        for part in ("sort", "scan", "trim"):
            assert f"pinot.groupby.partitioned.{part}" in text, part
    assert "module @jit_pinot_groupby_fused " in text
    assert "jit(pinot_groupby_fused)/" in text     # the head of every tf_op


@pytest.mark.parametrize("sql,name,scopes", [
    ("SELECT COUNT(*), SUM(d) FROM scopes WHERE w > 0",
     "pinot_agg_fused", ("pinot.agg", "pinot.decode", "pinot.filter")),
    ("SELECT DISTINCTCOUNT(d) FROM scopes WHERE w > 0",
     "pinot_distinct", ("pinot.distinct", "pinot.filter")),
])
def test_scalar_kernels_named_and_scoped(scope_segment, sql, name, scopes):
    p, text = _lowered_text(scope_segment, sql)
    from pinot_tpu.engine.kernels import kernel_name
    assert kernel_name(p.spec) == name
    assert f"module @jit_{name} " in text
    for scope in scopes:
        assert scope in text, scope


def test_kernel_names_do_not_change_cache_keys(scope_segment):
    """A name only: two specs that differ in nothing but what the name is made
    from still differ in `signature()`, and the name is not part of it."""
    from pinot_tpu.engine.kernels import kernel_name
    p, _ = _lowered_text(scope_segment,
                         "SELECT k, COUNT(*) FROM scopes GROUP BY k")
    assert kernel_name(p.spec) == "pinot_groupby"
    assert kernel_name(p.spec, batch=4) == "pinot_groupby_b4"
    assert not any("pinot" in str(part) for part in p.spec.signature())


@pytest.mark.parametrize("sql,present,absent", [
    # d: 40 values, a 64-entry table -> decoded by selects
    ("SELECT COUNT(*), SUM(d) FROM scopes WHERE w > 0",
     ("pinot.decode.select",), ("pinot.decode.gather", "gather")),
    # k: 6000 values, an 8192-entry table -> the gather stays
    ("SELECT COUNT(*), SUM(k) FROM scopes WHERE w > 0",
     ("pinot.decode.gather", "gather"), ("pinot.decode.select",)),
    ("SELECT COUNT(*), SUM(d), SUM(k) FROM scopes WHERE w > 0",
     ("pinot.decode.select", "pinot.decode.gather"), ()),
])
def test_decode_scope_names_the_decode_it_chose(scope_segment, sql, present,
                                                absent):
    """The dictionary decode is scoped by its form, both under the
    `pinot.decode` prefix `kernels.decode_share` matches; a program whose
    tables are all small holds no gather operation at all."""
    p, text = _lowered_text(scope_segment, sql)
    assert p.spec.fused_cols
    for s in present:
        assert s in text, s
    for s in absent:
        assert s not in text, s
    from benchmark.harness.program_trace import scope_of
    assert scope_of("jit(pinot_agg_fused)/jit(shmap_body)/"
                    "pinot.decode.select/select_n:") == "pinot.decode.select"
    assert scope_of("x/pinot.decode.gather/gather:").startswith("pinot.decode")


def test_gather_free_launches_reaches_the_served_response(tmp_path):
    """Through the device pipeline of a served cluster: the query whose fused
    column has a small table answers with `gatherFreeLaunches` 1, the one
    whose table is over the cap with 0 (both `fusedLaunches` 1), and a query
    that decodes nothing in-kernel with neither."""
    from pinot_tpu.cluster.device_server import DeviceQueryPipeline
    from pinot_tpu.engine.kernels import SELECT_DECODE_CAP
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cluster.servers[0].device_pipeline = pipeline = DeviceQueryPipeline()
    rng = np.random.default_rng(27)
    rows = 4000
    schema = Schema("dec", [dimension("small", DataType.INT),
                            dimension("wide", DataType.INT),
                            metric("w", DataType.INT)])
    from pinot_tpu.table import IndexingConfig
    cfg = TableConfig("dec", indexing=IndexingConfig(
        no_dictionary_columns=["w"]))
    cluster.create_table(schema, cfg)
    cluster.ingest_columns(cfg, {
        "small": rng.integers(0, 11, rows).astype(np.int32),
        "wide": rng.integers(0, 4 * SELECT_DECODE_CAP, rows).astype(np.int32),
        "w": rng.integers(-1000, 1000, rows).astype(np.int32)})
    try:
        got = {name: cluster.query(
            f"SELECT COUNT(*), SUM({expr}) FROM dec WHERE w > 0").stats
            for name, expr in (("small", "small"), ("wide", "wide"),
                               ("raw", "w"))}
    finally:
        pipeline.stop()
    for name, fused, free in (("small", 1, 1), ("wide", 1, 0), ("raw", 0, 0)):
        s = got[name]
        assert s["deviceLaunches"] >= 1, (name, s)
        assert s["fusedLaunches"] == fused, (name, s)
        assert s["gatherFreeLaunches"] == free, (name, s)


# -- PR 29: the sort regime's two decodes, their scopes and their counters ----

def test_sort_regime_program_holds_both_sorts_and_both_decodes(scope_segment):
    """One conditional on what the tiles' counts say (PR 33): `.presort` (the
    count outside it; inside it the move and the short sort, one branch a
    step of `PRESORT_SLOTS`, then one `.compact` ladder over the compacted
    rows) beside `.sort`, the full sort, whose branch
    holds PR 29's conditional on the count of rows that passed:
    the answer from the sorted prefix under `.compact`, the per-key decode
    (its `.trim` and `.scan` names unchanged) under `.dense`."""
    from pinot_tpu.engine.caps import KernelCaps, get_caps, set_caps
    regime = "partitioned"
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096))
    try:
        _, text = _lowered_text(
            scope_segment,
            "SELECT k, COUNT(*), SUM(d) FROM scopes WHERE w > 0 GROUP BY k")
    finally:
        set_caps(prev)
    assert "stablehlo.case" in text
    top = f"pinot.groupby.{regime}/"
    full, short = top + "cond/branch_0_fun/", top + "cond/branch_1_fun/"
    assert f"{top}pinot.groupby.{regime}.presort/" in text      # the count
    assert f"{full}pinot.groupby.{regime}.sort/" in text
    assert f"{top}pinot.groupby.{regime}.sort/" not in text
    assert f"{short}pinot.groupby.{regime}.sort/" not in text
    for step in (0, 1):                     # 16 slots a tile, 64: the move
        assert f"{short}pinot.groupby.{regime}.presort/cond/" \
            f"branch_{step}_fun/" in text
    assert re.search(rf"{re.escape(short)}(cond/branch_\d_fun/)?"
                     rf"pinot\.groupby\.{regime}\.compact/", text)
    assert f"pinot.groupby.{regime}.presort/cond/branch_0_fun/" \
        f"pinot.groupby.{regime}.compact" not in text   # one ladder, after
    assert f"{short}pinot.groupby.{regime}.dense/" not in text
    assert not re.search(rf"{re.escape(short)}cond/branch_\d_fun/"
                         rf"pinot\.groupby\.{regime}\.dense/", text)
    for branch in ("compact", "dense"):     # each inside the inner conditional
        assert re.search(rf"{re.escape(full)}cond/branch_\d_fun/"
                         rf"pinot\.groupby\.{regime}\.{branch}/", text), branch
    for part in ("trim", "scan"):       # inside the dense branch, as before
        assert f"pinot.groupby.{regime}.dense/pinot.groupby.{regime}.{part}" \
            in text, part
        assert f"pinot.groupby.{regime}.compact/pinot.groupby.{regime}." \
            f"{part}" not in text, part
    from benchmark.harness.program_trace import scope_of
    for op in (f"{full}cond/branch_1_fun/pinot.groupby.{regime}.compact/"
               "scatter-add:",
               f"{short}pinot.groupby.{regime}.presort/cond/branch_0_fun/"
               "reduce_sum:"):
        assert scope_of("jit(pinot_groupby)/" + op) \
            == f"pinot.groupby.{regime}"    # still one family for the share


def test_small_key_program_past_2_24_rows_holds_no_decode_branch():
    """Where today's decode costs less than a `cap`-row pass no branch is
    built: a sort over 32Mi or 64Mi rows for 8,193 keys (a shape the ladder no
    longer sends here: since PR 31 those take the chunked matmul slab by slab)
    holds one branch, the full-size cell's wide-key ones get n / 64 rows."""
    import jax
    import jax.numpy as jnp
    from pinot_tpu.engine import kernels
    for n in (1 << 25, 1 << 26):
        assert kernels.compact_cap(n, 8193, 4096) == 0
        assert kernels.compact_cap(n, 438_273, 4096) == n // 64
        assert kernels.compact_cap(n, 1_753_089, 4096) == n // 64
    assert kernels.compact_cap(1 << 24, 438_273, 4096) == 262_144
    n = 1 << 25
    took = []
    text = jax.jit(lambda k, v: kernels._grouped_partitioned(
        k, 8193, [v], 4096, took)).lower(
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32)).as_text(debug_info=True)
    assert not took                     # no scalar: the host counts neither
    for part in ("sort", "trim", "scan"):
        assert f"pinot.groupby.partitioned.{part}" in text, part
    for absent in ("pinot.groupby.partitioned.compact", "stablehlo.case",
                   "pinot.groupby.partitioned.dense", "stablehlo.scatter"):
        assert absent not in text, absent


def test_decode_counters_reach_response_explain_and_health(tmp_path,
                                                           monkeypatch):
    """Through the device pipeline of a served cluster whose mesh is four
    devices, a segment each: a query that passes 40 rows answers with
    `compactDecodeLaunches` 1; one that passes every row of ONE chip's segment
    (and 10 rows of each other chip's) with `denseDecodeLaunches` 1, since a
    mesh launch is compact only if every chip took it; the same GROUP BY under
    the default caps, where it does not take the sort regime, with neither.
    Which SORT ran rides beside (PR 33; a tile keeps 16 slots, then 32 here):
    the 40 rows sit in one tile of one
    chip, which falls back, so that launch reads `fullSortLaunches` 1 (only
    if every chip compacted does it read `presortCompactLaunches`), as the
    dense one does; about 16 rows scattered over each of three chips read
    `presortCompactLaunches` 1 and a compact decode with it.
    EXPLAIN ANALYZE carries the same fields and
    `/health`'s device block (the pipeline's `stats()`) sums the launches."""
    from tests.test_dense_groupby import one_full_quarter
    from pinot_tpu.cluster.device_server import DeviceQueryPipeline
    from pinot_tpu.engine.caps import KernelCaps, get_caps, set_caps
    from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
    from pinot_tpu.table import IndexingConfig
    schema, cols = one_full_quarter("dc")
    # every key in every segment: four equal dictionaries, the aligned path
    # (5,000 keys of 8,000 rows: few enough to stay dictionary-encoded)
    per = len(cols["k"]) // 4
    cols["k"] %= 5000
    for s in range(4):
        cols["k"][s * per + 100:s * per + 5100] = np.arange(5000)
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cluster.servers[0].device_pipeline = pipeline = DeviceQueryPipeline(
        mesh_exec=MeshQueryExecutor(default_mesh(4)))
    cfg = TableConfig("dc", indexing=IndexingConfig(
        no_dictionary_columns=["w", "v"]))
    cluster.create_table(schema, cfg)
    for s in range(4):
        cluster.ingest_columns(cfg, {c: v[s * per:(s + 1) * per]
                                     for c, v in cols.items()})
    sql = "SELECT k, COUNT(*), SUM(v) FROM dc WHERE w < {} GROUP BY k " \
          "ORDER BY k LIMIT 10000"
    prev = get_caps()
    try:
        # 8,192 padded keys: the chunked matmul by default, the sort regime
        # once the chunk cap is forced under them
        got = {"no sort regime": cluster.query(sql.format(100))}
        set_caps(KernelCaps(chunk_cap=4096))
        from pinot_tpu.engine import kernels
        monkeypatch.setattr(kernels, "PRESORT_SLOTS", (16, 32))
        got.update({
            "compact": cluster.query(sql.format(10)),
            "dense": cluster.query(sql.format(100)),
            "presorted": cluster.query(sql.replace("w < {}", "w = 500")),
            "explain": cluster.query("EXPLAIN ANALYZE " + sql.format(11))})
        health = pipeline.stats()
    finally:
        set_caps(prev)
        pipeline.stop()
    assert sum(r[1] for r in got["compact"].rows) == 40
    assert sum(r[1] for r in got["dense"].rows) == per + 30
    assert sum(r[1] for r in got["presorted"].rows) \
        == (cols["w"] == 500).sum() > 0
    for name, compact, dense, presorted in (
            ("compact", 1, 0, 0), ("dense", 0, 1, 0), ("presorted", 1, 0, 1),
            ("no sort regime", 0, 0, None), ("explain", 1, 0, 0)):
        s = got[name].stats
        assert s["deviceLaunches"] >= 1 and s["meshLaunches"] >= 1, (name, s)
        assert (s["compactDecodeLaunches"], s["denseDecodeLaunches"]) \
            == (compact, dense), (name, s)
        assert (s["presortCompactLaunches"], s["fullSortLaunches"]) == (
            (0, 0) if presorted is None else (presorted, 1 - presorted)), \
            (name, s)
    assert got["explain"].stats["analyze"] is True
    assert (health["compactDecodeLaunches"], health["denseDecodeLaunches"]) \
        == (3, 1)
    assert (health["presortCompactLaunches"], health["fullSortLaunches"]) \
        == (1, 3)
    assert health["deviceErrors"] == 0 and health["fallbacks"] == 0


# -- PR 31: the matmul GROUP BY regimes slab by slab, and their counter -------

def test_slabbed_launches_reach_response_explain_and_health(tmp_path,
                                                            monkeypatch):
    """Through the device pipeline of a served cluster whose mesh is four
    devices, two segments each (2 x 4,096 padded rows a device), with the slab
    patched to 4,096 rows: a GROUP BY of 200 keys (one-hot) and one of 2,000
    (chunk64) answer with `slabbedLaunches` 1 and the rows the host computes;
    a scalar aggregation, which has no GROUP BY, with 0. EXPLAIN ANALYZE
    carries the field and `/health`'s device block sums the launches."""
    from tests.test_dense_groupby import _patch_slab_rows
    from pinot_tpu.cluster.device_server import DeviceQueryPipeline
    from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
    from pinot_tpu.table import IndexingConfig
    rng = np.random.default_rng(31)
    segs, per = 8, 4000
    schema = Schema("sl", [dimension("g", DataType.INT),
                           dimension("k", DataType.INT),
                           metric("w", DataType.INT)])
    cols = {"g": rng.integers(0, 200, segs * per).astype(np.int32),
            "k": rng.integers(0, 2000, segs * per).astype(np.int32),
            "w": rng.integers(1, 1000, segs * per).astype(np.int32)}
    for s in range(segs):       # every key in every segment: aligned
        cols["g"][s * per:s * per + 200] = np.arange(200)
        cols["k"][s * per + 200:s * per + 2200] = np.arange(2000)
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cluster.servers[0].device_pipeline = pipeline = DeviceQueryPipeline(
        mesh_exec=MeshQueryExecutor(default_mesh(4)))
    cfg = TableConfig("sl", indexing=IndexingConfig(
        no_dictionary_columns=["w"]))
    cluster.create_table(schema, cfg)
    for s in range(segs):
        cluster.ingest_columns(cfg, {c: v[s * per:(s + 1) * per]
                                     for c, v in cols.items()})
    by = "SELECT {0}, COUNT(*), SUM(w) FROM sl WHERE w > 500 GROUP BY {0} " \
         "ORDER BY {0} LIMIT 10000"
    sqls = {"onehot": by.format("g"), "chunk64": by.format("k"),
            "scalar": "SELECT COUNT(*), SUM(w) FROM sl WHERE w > 500",
            "explain": "EXPLAIN ANALYZE " + by.format("g")}
    _patch_slab_rows(monkeypatch, 4096)
    try:
        got = {name: cluster.query(sql) for name, sql in sqls.items()}
        health = pipeline.stats()
    finally:
        pipeline.stop()
    for name, slabbed in (("onehot", 1), ("chunk64", 1), ("scalar", 0),
                          ("explain", 1)):
        s = got[name].stats
        assert s["deviceLaunches"] >= 1 and s["meshLaunches"] >= 1, (name, s)
        assert s["slabbedLaunches"] == slabbed, (name, s)
    assert got["explain"].stats["analyze"] is True
    assert health["slabbedLaunches"] == 3
    assert health["deviceErrors"] == 0 and health["fallbacks"] == 0
    live = cols["w"] > 500
    for name, col in (("onehot", "g"), ("chunk64", "k")):
        keys = np.unique(cols[col][live])
        assert [r[0] for r in got[name].rows] == keys.tolist()
        assert [r[1] for r in got[name].rows] == \
            np.bincount(cols[col][live])[keys].tolist()
        np.testing.assert_allclose(
            [r[2] for r in got[name].rows],
            np.bincount(cols[col][live], weights=cols["w"][live])[keys],
            rtol=1e-6)


# -- the rows that passed, compacted in front of the matmul rungs ------------

def test_matmul_compaction_counters_reach_response_explain_and_health(
        tmp_path):
    """Through the device pipeline of a served cluster whose mesh is four
    devices, two segments each (8 tiles of 1,024 rows a device): a selective
    GROUP BY of 200 keys (one-hot) and one of 2,000 (chunk64) answer with
    `compactMatmulLaunches` 1, an unselective one with `fullMatmulLaunches`
    1, a scalar aggregation with neither; EXPLAIN ANALYZE carries the fields
    and `/health`'s device block sums the launches. Every answer is numpy's."""
    from pinot_tpu.cluster.device_server import DeviceQueryPipeline
    from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
    from pinot_tpu.table import IndexingConfig
    rng = np.random.default_rng(41)
    segs, per = 8, 4000
    schema = Schema("cm", [dimension("g", DataType.INT),
                           dimension("k", DataType.INT),
                           metric("w", DataType.INT)])
    cols = {"g": rng.integers(0, 200, segs * per).astype(np.int32),
            "k": rng.integers(0, 2000, segs * per).astype(np.int32),
            "w": rng.integers(1, 1000, segs * per).astype(np.int32)}
    for s in range(segs):       # every key in every segment: aligned
        cols["g"][s * per:s * per + 200] = np.arange(200)
        cols["k"][s * per + 200:s * per + 2200] = np.arange(2000)
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cluster.servers[0].device_pipeline = pipeline = DeviceQueryPipeline(
        mesh_exec=MeshQueryExecutor(default_mesh(4)))
    cfg = TableConfig("cm", indexing=IndexingConfig(
        no_dictionary_columns=["w"]))
    cluster.create_table(schema, cfg)
    for s in range(segs):
        cluster.ingest_columns(cfg, {c: v[s * per:(s + 1) * per]
                                     for c, v in cols.items()})
    by = "SELECT {0}, COUNT(*), SUM(w) FROM cm WHERE w > {1} GROUP BY {0} " \
         "ORDER BY {0} LIMIT 10000"
    sqls = {"onehot": by.format("g", 990), "chunk64": by.format("k", 990),
            "unselective": by.format("g", 500),
            "scalar": "SELECT COUNT(*), SUM(w) FROM cm WHERE w > 990",
            "explain": "EXPLAIN ANALYZE " + by.format("g", 990)}
    try:
        got = {name: cluster.query(sql) for name, sql in sqls.items()}
        health = pipeline.stats()
    finally:
        pipeline.stop()
    for name, compact, full in (("onehot", 1, 0), ("chunk64", 1, 0),
                                ("unselective", 0, 1), ("scalar", 0, 0),
                                ("explain", 1, 0)):
        s = got[name].stats
        assert s["deviceLaunches"] >= 1 and s["meshLaunches"] >= 1, (name, s)
        assert (s["compactMatmulLaunches"], s["fullMatmulLaunches"]) \
            == (compact, full), (name, s)
    assert got["explain"].stats["analyze"] is True
    assert (health["compactMatmulLaunches"], health["fullMatmulLaunches"]) \
        == (3, 1)
    assert health["deviceErrors"] == 0 and health["fallbacks"] == 0
    for name, col, bound in (("onehot", "g", 990), ("chunk64", "k", 990),
                             ("unselective", "g", 500)):
        live = cols["w"] > bound
        keys = np.unique(cols[col][live])
        assert [r[0] for r in got[name].rows] == keys.tolist()
        assert [r[1] for r in got[name].rows] == \
            np.bincount(cols[col][live])[keys].tolist()
        np.testing.assert_allclose(
            [r[2] for r in got[name].rows],
            np.bincount(cols[col][live], weights=cols["w"][live])[keys],
            rtol=1e-6)


# -- PR 37: the masked reduce at the bottom of the ladder, and its counter ----

def test_masked_groupby_launches_reach_response_explain_and_health(tmp_path):
    """Through the device pipeline of a served cluster whose mesh is four
    devices: a GROUP BY of 5 keys (8 padded, 9 cells: the masked reduce)
    answers with `maskedGroupByLaunches` 1 and the rows numpy computes, one of
    200 keys (the one-hot regime) and a scalar aggregation with 0. EXPLAIN
    ANALYZE carries the field and `/health`'s device block sums the launches;
    none of them is slabbed."""
    from pinot_tpu.cluster.device_server import DeviceQueryPipeline
    from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
    from pinot_tpu.table import IndexingConfig
    rng = np.random.default_rng(37)
    segs, per = 4, 4000
    schema = Schema("mk", [dimension("m", DataType.INT),
                           dimension("g", DataType.INT),
                           metric("w", DataType.INT)])
    cols = {"m": rng.integers(0, 5, segs * per).astype(np.int32),
            "g": rng.integers(0, 200, segs * per).astype(np.int32),
            "w": rng.integers(1, 1000, segs * per).astype(np.int32)}
    for s in range(segs):       # every key in every segment: aligned
        cols["m"][s * per:s * per + 5] = np.arange(5)
        cols["g"][s * per + 5:s * per + 205] = np.arange(200)
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cluster.servers[0].device_pipeline = pipeline = DeviceQueryPipeline(
        mesh_exec=MeshQueryExecutor(default_mesh(4)))
    cfg = TableConfig("mk", indexing=IndexingConfig(
        no_dictionary_columns=["w"]))
    cluster.create_table(schema, cfg)
    for s in range(segs):
        cluster.ingest_columns(cfg, {c: v[s * per:(s + 1) * per]
                                     for c, v in cols.items()})
    by = "SELECT {0}, COUNT(*), SUM(w) FROM mk WHERE w > 500 GROUP BY {0} " \
         "ORDER BY {0} LIMIT 10000"
    sqls = {"masked": by.format("m"), "onehot": by.format("g"),
            "scalar": "SELECT COUNT(*), SUM(w) FROM mk WHERE w > 500",
            "explain": "EXPLAIN ANALYZE " + by.format("m")}
    try:
        got = {name: cluster.query(sql) for name, sql in sqls.items()}
        health = pipeline.stats()
    finally:
        pipeline.stop()
    for name, masked in (("masked", 1), ("onehot", 0), ("scalar", 0),
                         ("explain", 1)):
        s = got[name].stats
        assert s["deviceLaunches"] >= 1 and s["meshLaunches"] >= 1, (name, s)
        assert s["maskedGroupByLaunches"] == masked, (name, s)
        assert s["slabbedLaunches"] == 0, (name, s)
    assert got["explain"].stats["analyze"] is True
    assert health["maskedGroupByLaunches"] == 2
    assert health["deviceErrors"] == 0 and health["fallbacks"] == 0
    live = cols["w"] > 500
    for name, col in (("masked", "m"), ("onehot", "g")):
        keys = np.unique(cols[col][live])
        assert [r[0] for r in got[name].rows] == keys.tolist()
        assert [r[1] for r in got[name].rows] == \
            np.bincount(cols[col][live])[keys].tolist()
        np.testing.assert_allclose(
            [r[2] for r in got[name].rows],
            np.bincount(cols[col][live], weights=cols["w"][live])[keys],
            rtol=1e-6)
