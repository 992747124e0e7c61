"""HTTP services for each cluster role: controller, server, broker.

These wrap the in-proc role objects (controller.py / server.py / broker.py) with the
HTTP endpoints the reference exposes:

* ControllerService — table/schema CRUD + segment upload/download
  (`controller/api/resources/PinotSegmentUploadDownloadRestletResource.java`),
  segment completion protocol (`LLCSegmentCompletionHandlers.java`), and the
  catalog API standing in for ZooKeeper (snapshot + long-poll watch).
* ServerService — the query endpoint (`core/transport/InstanceRequestHandler.java:96`
  over Netty in the reference; HTTP/binary wire here).
* BrokerService — SQL entry (`pinot-broker/api/resources/PinotClientRequest.java`
  POST /query/sql).
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

from ..query import stats as qstats
from ..schema import Schema
from ..table import TableConfig
from ..utils.trace import gc_stats, stage
from .broker import Broker
from .catalog import Catalog, InstanceInfo
from .controller import Controller
from .http_service import (HttpService, binary_response, error_response,
                           json_response)
from .deepstore import untar_segment
from .remote import RemoteServerHandle
from .server import ServerNode
from .wire import decode_query_request, encode_segment_result


def _metrics_route(parts, params, body):
    """GET /metrics — Prometheus text exposition of the process registry
    (reference: the JMX->Prometheus exporter over the yammer metrics registry)."""
    from ..utils.metrics import get_registry
    return 200, "text/plain; version=0.0.4", get_registry().render_prometheus().encode()


def _events_route(params):
    """GET /debug/events?since=<gseq> — the process journal's incremental
    pull (shared by every role service): events past the cursor plus the
    cursor to pass next time. The controller's timeline collector polls
    this exactly like the memory checker polls /debug/memory."""
    from ..utils.events import get_journal
    try:
        since = int(params.get("since", 0))
    except (TypeError, ValueError):
        since = 0
    try:
        limit = int(params["limit"]) if "limit" in params else None
    except (TypeError, ValueError):
        limit = None
    return json_response(get_journal().events_since(since, limit))


def _configure_journal(catalog, instance_id: str) -> None:
    """Role-startup journal config: stamp the process journal's default node
    label and apply the `events.ring.size` knob. One journal per process —
    in OS-process deployments each role owns it; in-proc test clusters the
    last-constructed service wins the default label (emit sites pass their
    node explicitly, so only unlabeled emits are affected)."""
    from ..utils.events import get_journal
    cap = None
    try:
        raw = catalog.get_property("clusterConfig/events.ring.size", None)
        if raw is not None:
            cap = int(raw)
    except (TypeError, ValueError):
        cap = None   # malformed knob: keep the current capacity
    get_journal().configure(node=instance_id, capacity=cap)


def _untar_body(body: bytes, name: str, dest: str) -> str:
    """Write an uploaded segment tar to disk and unpack it; returns the segment dir."""
    tar_path = os.path.join(dest, f"{name}.tar.gz")
    with open(tar_path, "wb") as f:
        f.write(body)
    return untar_segment(tar_path, dest)


class ControllerService:
    """Controller role process: owns the authoritative catalog + deep store."""

    def __init__(self, controller: Controller, host: str = "127.0.0.1",
                 port: int = 0, access_control=None, ssl_context=None):
        self.controller = controller
        self.catalog = controller.catalog
        _configure_journal(self.catalog, controller.instance_id)
        self.http = HttpService(host, port, access_control=access_control,
                                ssl_context=ssl_context)
        self._version = 0
        self._version_cv = threading.Condition()
        self.catalog.subscribe(self._bump_version)
        s = self.http
        s.route("GET", "health", lambda p, q, b: json_response({"status": "OK"}))
        s.route("GET", "catalog", self._catalog_get)
        s.route("POST", "catalog", self._catalog_post, action="WRITE")
        s.route("POST", "schemas", self._post_schema, action="WRITE")
        s.route("POST", "tables", self._post_table, action="WRITE")
        s.route("DELETE", "tables", self._delete_table, action="ADMIN")
        s.route("POST", "segments", self._post_segment, action="WRITE")
        s.route("GET", "segments", self._get_segment)
        s.route("DELETE", "segments", self._delete_segment, action="ADMIN")
        s.route("POST", "segmentConsumed", self._segment_consumed, action="WRITE")
        s.route("POST", "segmentCommitStart", self._segment_commit_start,
                action="WRITE")
        s.route("POST", "segmentCommitEnd", self._segment_commit_end,
                action="WRITE")
        s.route("GET", "deepstore", self._deepstore_get)
        s.route("POST", "deepstore", self._deepstore_post, action="WRITE")
        s.route("GET", "tableStatus", self._table_status)
        s.route("GET", "tables", self._get_tables)
        s.route("GET", "schemas", self._get_schema)
        s.route("GET", "segmentsMeta", self._segments_meta)
        s.route("POST", "reload", self._reload_table, action="WRITE")
        s.route("GET", "tenants", self._list_tenants)
        s.route("GET", "clusterConfigs", self._get_cluster_configs)
        s.route("POST", "clusterConfigs", self._set_cluster_config,
                action="ADMIN")
        s.route("POST", "tableState", self._table_state, action="ADMIN")
        s.route("POST", "instanceTags", self._update_instance_tags, action="ADMIN")
        s.route("POST", "pauseConsumption", self._pause_consumption, action="ADMIN")
        s.route("POST", "resumeConsumption", self._resume_consumption, action="ADMIN")
        s.route("POST", "rebalance", self._rebalance, action="ADMIN")
        s.route("POST", "validate", self._validate, action="ADMIN")
        # minion task protocol (reference: Helix task framework; claims are
        # atomic against the authoritative catalog, so N remote minions can
        # never double-claim)
        s.route("POST", "tasks", self._tasks_post, action="WRITE")
        s.route("GET", "tasks", self._tasks_get)
        s.route("POST", "replaceSegments", self._replace_segments, action="WRITE")
        s.route("POST", "ingestJobs", self._ingest_jobs, action="WRITE")
        s.route("GET", "metrics", _metrics_route)
        s.route("GET", "debug", self._debug)
        s.route("POST", "sql", self._sql_proxy)  # query console backend
        s.route("GET", "", self._ui)       # admin UI at /
        s.route("GET", "ui", self._ui)
        self.http.start()

    @property
    def url(self) -> str:
        return self.http.url

    def stop(self) -> None:
        self.http.stop()

    def _debug(self, parts, params, body):
        """GET /debug — controller rollup (periodic tasks, verdict planes).
        GET /debug/events — this process's journal (incremental, ?since=).
        GET /debug/timeline — the merged cluster timeline in causal order
        (?kind= ?table= ?severity= ?since= ?limit= filters). GET
        /debug/incidents — the flight recorder's retained bundles
        (?id=<n> resolves one, 404 when evicted/unknown)."""
        if parts and parts[0] == "events":
            return _events_route(params)
        if parts and parts[0] == "timeline":
            try:
                since = float(params["since"]) if "since" in params else None
            except (TypeError, ValueError):
                since = None
            try:
                limit = int(params["limit"]) if "limit" in params else None
            except (TypeError, ValueError):
                limit = None
            rows = self.controller.timeline(
                kind=params.get("kind"), table=params.get("table"),
                severity=params.get("severity"), since=since, limit=limit)
            return json_response({"events": rows, "count": len(rows)})
        if parts and parts[0] == "incidents":
            inc_id = params.get("id")
            if inc_id:
                for b in self.controller.incidents():
                    if str(b.get("id")) == str(inc_id):
                        return json_response(b)
                return error_response(f"unknown incident {inc_id}", 404)
            try:
                limit = int(params["limit"]) if "limit" in params else None
            except (TypeError, ValueError):
                limit = None
            rows = self.controller.incidents(limit)
            return json_response({"incidents": rows, "count": len(rows)})
        return json_response(self.controller.debug_stats())

    _UI_STYLE = (
        "<style>body{font-family:sans-serif;margin:2em}table{border-collapse:"
        "collapse}td,th{border:1px solid #ccc;padding:4px 10px;text-align:left}"
        ".err{color:#b00}.warn{background:#fff3cd}nav a{margin-right:1em}"
        "textarea{width:100%;font-family:monospace}</style>"
        "<nav><a href=/ui>overview</a><a href=/ui/tasks>tasks</a>"
        "<a href=/ui/query>query console</a><a href=/metrics>metrics</a></nav>")

    def _ui(self, parts, params, body):
        """GET /ui[/...] — server-rendered admin console (stand-in for the
        reference's React controller app): cluster overview, per-table
        segment drill-down with replica placement (skew is visible at a
        glance), task states (stuck/failed tasks diagnosable from the
        browser), and a query console proxying to a live broker (reference:
        PinotQueryResource)."""
        page = parts[0] if parts else ""
        if page == "table" and len(parts) > 1:
            return self._ui_table(parts[1])
        if page == "tasks":
            return self._ui_tasks()
        if page == "query":
            return self._ui_query()
        return self._ui_overview()

    def _ui_overview(self):
        from html import escape
        with self.catalog._lock:
            tables = {
                t: {"segments": len(self.catalog.segments.get(t, {})),
                    "replication": cfg.replication,
                    "type": "REALTIME" if cfg.stream else "OFFLINE"}
                for t, cfg in self.catalog.table_configs.items()}
            instances = [(i.instance_id, i.role, "UP" if i.alive else "DOWN")
                         for i in self.catalog.instances.values()]
            # per-server segment counts across all tables: load skew at a glance
            load: Dict[str, int] = {}
            for t, ev in self.catalog.external_view.items():
                for seg, states in ev.items():
                    for srv, st in states.items():
                        if st in ("ONLINE", "CONSUMING"):
                            load[srv] = load.get(srv, 0) + 1
        # escape EVERY catalog-derived value: table/instance names are
        # client-supplied and would otherwise be stored XSS in the operator UI
        rows = "".join(
            f"<tr><td><a href='/ui/table/{escape(t)}'>{escape(t)}</a></td>"
            f"<td>{d['type']}</td><td>{d['segments']}</td>"
            f"<td>{d['replication']}</td></tr>" for t, d in sorted(tables.items()))
        inst = "".join(
            f"<tr><td>{escape(i)}</td><td>{escape(r)}</td><td>{s}</td>"
            f"<td>{load.get(i, 0)}</td></tr>" for i, r, s in sorted(instances))
        html = (
            "<!doctype html><title>pinot-tpu controller</title>"
            f"{self._UI_STYLE}<h1>pinot-tpu controller</h1>"
            "<h2>Tables</h2><table><tr><th>table</th><th>type</th>"
            f"<th>segments</th><th>replication</th></tr>{rows}</table>"
            "<h2>Instances</h2><table><tr><th>instance</th><th>role</th>"
            f"<th>status</th><th>segments served</th></tr>{inst}</table>")
        return 200, "text/html", html.encode()

    def _ui_table(self, table):
        """Per-segment drill-down: status, docs, size, time range, replica
        placement and per-server counts — a skewed table shows up as uneven
        'segments per server' and lopsided placements."""
        from html import escape
        with self.catalog._lock:
            segs = dict(self.catalog.segments.get(table, {}))
            ev = {s: dict(m) for s, m in
                  self.catalog.external_view.get(table, {}).items()}
        if not segs and not ev:
            return error_response(f"unknown table {table}", 404)
        per_server: Dict[str, int] = {}
        rows = []
        for name in sorted(set(segs) | set(ev)):
            m = segs.get(name)
            states = ev.get(name, {})
            for srv, st in states.items():
                if st in ("ONLINE", "CONSUMING"):
                    per_server[srv] = per_server.get(srv, 0) + 1
            placement = ", ".join(f"{escape(s)}:{escape(str(st))}"
                                  for s, st in sorted(states.items()))
            rows.append(
                f"<tr><td>{escape(name)}</td>"
                f"<td>{escape(str(m.status)) if m else '?'}</td>"
                f"<td>{m.num_docs if m else '?'}</td>"
                f"<td>{m.size_bytes if m else '?'}</td>"
                f"<td>{m.start_time_ms if m else ''}..{m.end_time_ms if m else ''}</td>"
                f"<td>{escape(str(m.download_path)) if m else ''}</td>"
                f"<td>{placement}</td></tr>")
        srv_rows = "".join(f"<tr><td>{escape(s)}</td><td>{n}</td></tr>"
                           for s, n in sorted(per_server.items()))
        html = (
            f"<!doctype html><title>{escape(table)}</title>{self._UI_STYLE}"
            f"<h1>table {escape(table)}</h1>"
            "<h2>Segments per server</h2>"
            f"<table><tr><th>server</th><th>segments</th></tr>{srv_rows}</table>"
            "<h2>Segments</h2><table><tr><th>segment</th><th>status</th>"
            "<th>docs</th><th>bytes</th><th>time range</th><th>download</th>"
            f"<th>placement</th></tr>{''.join(rows)}</table>")
        return 200, "text/html", html.encode()

    def _ui_tasks(self):
        """Task/job states: a STUCK task is RUNNING with an old lease, a
        failed one shows its error inline (reference: task states in the
        controller console)."""
        import time as _t
        from html import escape
        from ..minion.tasks import TaskQueue
        now_ms = int(_t.time() * 1000)
        rows = []
        for t in TaskQueue(self.catalog).tasks():
            age_s = (now_ms - t.claimed_ms) / 1000 if t.claimed_ms else None
            stuck = t.state == "RUNNING" and age_s is not None and age_s > 600
            cls = " class=warn" if stuck else ""
            rows.append(
                f"<tr{cls}><td>{escape(t.task_id)}</td>"
                f"<td>{escape(t.task_type)}</td><td>{escape(t.table)}</td>"
                f"<td>{escape(t.state)}{' (stale lease)' if stuck else ''}</td>"
                f"<td>{escape(t.worker)}</td>"
                f"<td>{f'{age_s:.0f}s' if age_s is not None else ''}</td>"
                f"<td class=err>{escape(t.error)}</td></tr>")
        html = (
            f"<!doctype html><title>tasks</title>{self._UI_STYLE}"
            "<h1>Minion tasks</h1><table><tr><th>task</th><th>type</th>"
            "<th>table</th><th>state</th><th>worker</th><th>claimed age</th>"
            f"<th>error</th></tr>{''.join(rows)}</table>"
            "<p>POST /tasks/gc requeues stale RUNNING tasks; POST "
            "/tasks/generate runs the generators now.</p>")
        return 200, "text/html", html.encode()

    def _ui_query(self):
        """Query console: textarea -> POST /sql (the controller-side broker
        proxy, reference: PinotQueryResource.handlePostSql)."""
        html = (
            f"<!doctype html><title>query console</title>{self._UI_STYLE}"
            "<h1>Query console</h1>"
            "<textarea id=q rows=4>SELECT * FROM mytable LIMIT 10</textarea>"
            "<p><button onclick='run()'>Run</button></p><div id=out></div>"
            "<script>async function run(){"
            "const r=await fetch('/sql',{method:'POST',headers:{'Content-Type'"
            ":'application/json'},body:JSON.stringify({sql:document."
            "getElementById('q').value})});const d=await r.json();"
            "const o=document.getElementById('out');if(d.error){o.innerHTML="
            "'<p class=err></p>';o.firstChild.textContent=d.error;return;}"
            "const t=d.resultTable||{};const cols=(t.dataSchema||{})."
            "columnNames||[];let h='<table><tr>'+cols.map(c=>'<th></th>')."
            "join('')+'</tr>'+(t.rows||[]).map(r=>'<tr>'+r.map(c=>'<td></td>')"
            ".join('')+'</tr>').join('')+'</table>';o.innerHTML=h;"
            "const cells=o.querySelectorAll('th');cols.forEach((c,i)=>cells[i]"
            ".textContent=c);let k=0;const tds=o.querySelectorAll('td');"
            "(t.rows||[]).forEach(r=>r.forEach(v=>tds[k++].textContent="
            "String(v)));}</script>")
        return 200, "text/html", html.encode()

    def _sql_proxy(self, parts, params, body):
        """POST /sql {\"sql\": ...} — forward to a live broker (reference:
        the controller's PinotQueryResource proxy, the query console's
        backend). Tries each live broker instance until one answers."""
        from .http_service import post_json
        d = json.loads(body.decode())
        with self.catalog._lock:
            brokers = [(i.instance_id, i.url)
                       for i in self.catalog.instances.values()
                       if i.role == "broker" and i.alive and i.port]
        last = "no live broker registered"
        for _bid, url in sorted(brokers):
            try:
                resp = post_json(f"{url}/query", {"sql": d["sql"]},
                                 timeout=60.0)
                return json_response(resp)
            except Exception as e:
                last = f"{type(e).__name__}: {e}"
        return json_response({"error": f"broker unavailable: {last}"},
                             status=503)

    # -- catalog API (the ZooKeeper stand-in) -------------------------------
    def _bump_version(self, event: str, table: str) -> None:
        with self._version_cv:
            self._version += 1
            self._version_cv.notify_all()

    def _catalog_get(self, parts, params, body):
        if parts and parts[0] == "snapshot":
            with self.catalog._lock:
                snap = {
                    "version": self._version,
                    "schemas": {k: v.to_json()
                                for k, v in self.catalog.schemas.items()},
                    "tableConfigs": {k: v.to_json()
                                     for k, v in self.catalog.table_configs.items()},
                    "segments": {t: {s: m.to_json() for s, m in segs.items()}
                                 for t, segs in self.catalog.segments.items()},
                    "idealState": self.catalog.ideal_state,
                    "externalView": self.catalog.external_view,
                    "instances": {k: v.to_json()
                                  for k, v in self.catalog.instances.items()},
                    "properties": self.catalog.properties,
                }
            return json_response(snap)
        if parts and parts[0] == "watch":
            since = int(params.get("since", -1))
            timeout = float(params.get("timeoutSec", 10.0))
            with self._version_cv:
                self._version_cv.wait_for(lambda: self._version != since,
                                          timeout=timeout)
                return json_response({"version": self._version})
        return error_response("not found", 404)

    def _catalog_post(self, parts, params, body):
        d = json.loads(body.decode())
        if parts and parts[0] == "instances":
            if "role" in d:
                self.catalog.register_instance(InstanceInfo.from_json(d))
            else:  # liveness update
                self.catalog.set_instance_alive(d["instance_id"], d["alive"])
            return json_response({"status": "OK"})
        if parts and parts[0] == "externalView":
            self.catalog.report_state(d["table"], d["segment"], d["server"],
                                      d["state"])
            return json_response({"status": "OK"})
        if parts and parts[0] == "property":
            self.catalog.put_property(d["key"], d.get("value"))
            return json_response({"status": "OK"})
        return error_response("not found", 404)

    # -- admin: schemas / tables / segments ---------------------------------
    def _post_schema(self, parts, params, body):
        self.controller.add_schema(Schema.from_json(json.loads(body.decode())))
        return json_response({"status": "OK"})

    def _post_table(self, parts, params, body):
        d = json.loads(body.decode())
        cfg = TableConfig.from_json(d["config"] if "config" in d else d)
        if cfg.stream is not None:
            segs = self.controller.add_realtime_table(
                cfg, int(d.get("numPartitions", 1)))
            return json_response({"status": "OK", "consumingSegments": segs})
        self.controller.add_table(cfg)
        return json_response({"status": "OK"})

    def _delete_table(self, parts, params, body):
        self.controller.drop_table(parts[0])
        return json_response({"status": "OK"})

    def _post_segment(self, parts, params, body):
        """POST /segments/{tableNameWithType}?name=...[&custom=json] with the
        tar as the body (reference: segment push via
        PinotSegmentUploadDownloadRestletResource)."""
        table = parts[0]
        from ..auth import require_table_access
        require_table_access(table, "WRITE")
        name = params["name"]
        custom = json.loads(params["custom"]) if params.get("custom") else None
        with tempfile.TemporaryDirectory() as tmp:
            seg_dir = _untar_body(body, name, tmp)
            meta = self.controller.upload_segment(table, seg_dir, custom=custom)
        return json_response({"status": "OK", "segment": meta.name})

    def _get_segment(self, parts, params, body):
        """GET /segments/{table}/{name} — download the committed tar by URL."""
        table, name = parts[0], parts[1]
        from ..auth import require_table_access
        require_table_access(table, "READ")  # raw data = same ACL as queries
        meta = self.catalog.segments.get(table, {}).get(name)
        if meta is None or not meta.download_path:
            return error_response(f"no such segment {table}/{name}", 404)
        with tempfile.TemporaryDirectory() as tmp:
            local = os.path.join(tmp, "seg.tar.gz")
            from .peers import download_segment_tar
            download_segment_tar(self.controller.deepstore, self.catalog,
                                 table, name, local, meta.download_path)
            with open(local, "rb") as f:
                return binary_response(f.read())

    def _delete_segment(self, parts, params, body):
        permanent = str(params.get("permanent", "")).lower() in ("true", "1")
        self.controller.delete_segment(parts[0], parts[1], permanent=permanent)
        return json_response({"status": "OK"})

    # -- minion task protocol -----------------------------------------------
    def _tasks_post(self, parts, params, body):
        """POST /tasks/claim {"worker", "taskTypes"} -> spec | null
        POST /tasks/finish {"taskId", "worker", "error"} -> {"applied": bool}
        POST /tasks/generate -> run every generator once (tests/admin)."""
        from ..minion.tasks import TaskQueue
        queue = TaskQueue(self.catalog)
        op = parts[0] if parts else ""
        d = json.loads(body.decode()) if body else {}
        if op == "claim":
            spec = queue.claim(d["worker"], list(d["taskTypes"]))
            return json_response({"task": spec.to_json() if spec else None})
        if op == "finish":
            applied = queue.finish(d["taskId"], error=d.get("error", ""),
                                   worker_id=d.get("worker"))
            return json_response({"applied": applied})
        if op == "generate":
            specs = self.controller.task_manager.generate_all()
            return json_response({"generated": [s.task_id for s in specs]})
        if op == "gc":
            # admin/ops: requeue stale RUNNING tasks (dead worker) + drop old
            # terminal entries; leaseMs override lets operators force-release
            n = queue.gc(lease_ms=int(d.get("leaseMs", 600_000)))
            return json_response({"removed": n})
        return error_response("claim|finish|generate|gc", 404)

    def _ingest_jobs(self, parts, params, body):
        """POST /ingestJobs {"table", "inputPaths": [...], ...} — split a
        batch ingestion job into one SegmentGenerationAndPushTask per input
        file and queue them for the minion fleet (the distributed analog of
        the reference's hadoop/spark batch runners: N workers ingest N files
        in parallel; reference: IngestionJobLauncher + per-file
        SegmentGenerationJobRunner units)."""
        import uuid as _uuid

        from ..auth import require_table_access
        from ..minion.tasks import (SEGMENT_GENERATION_AND_PUSH, TaskQueue,
                                    TaskSpec)
        d = json.loads(body.decode())
        table = d["table"]
        require_table_access(table, "WRITE")
        if table not in self.catalog.table_configs:
            return error_response(f"unknown table {table}", 404)
        paths = list(d.get("inputPaths") or [])
        if not paths:
            return error_response("inputPaths required", 400)
        logical = table.rsplit("_", 1)[0] if table.endswith(
            ("_OFFLINE", "_REALTIME")) else table
        prefix = (d.get("segmentNamePrefix")
                  or f"{logical}_batch_{_uuid.uuid4().hex[:6]}")
        queue = TaskQueue(self.catalog)
        ids = []
        for i, path in enumerate(paths):
            spec = TaskSpec(
                task_id=(f"{SEGMENT_GENERATION_AND_PUSH}_{table}_{i}_"
                         f"{_uuid.uuid4().hex[:8]}"),
                task_type=SEGMENT_GENERATION_AND_PUSH, table=table,
                config={"inputPath": path,
                        "inputFormat": d.get("inputFormat"),
                        "segmentNamePrefix": prefix,
                        "segmentRows": int(d.get("segmentRows", 1_000_000)),
                        "filterExpr": d.get("filterExpr"),
                        "columnTransforms": d.get("columnTransforms") or {},
                        "sequence": i})
            queue.submit(spec)
            ids.append(spec.task_id)
        return json_response({"tasks": ids, "segmentNamePrefix": prefix})

    def _tasks_get(self, parts, params, body):
        """GET /tasks[?table=...&type=...] — task states (admin surface)."""
        from ..minion.tasks import TaskQueue
        out = TaskQueue(self.catalog).tasks(params.get("table") or None,
                                            params.get("type") or None)
        return json_response({"tasks": [t.to_json() for t in out]})

    def _replace_segments(self, parts, params, body):
        """POST /replaceSegments/{table} {"from": [names], "stagedTars":
        [deep-store staging uris], "custom": {...}}: the minion stages the new
        segment tars through the deep-store proxy first, then this endpoint
        runs the controller's ATOMIC lineage swap (reference:
        startReplaceSegments/endReplaceSegments)."""
        table = parts[0]
        from ..auth import require_table_access
        require_table_access(table, "WRITE")
        d = json.loads(body.decode())
        new_dirs = []
        try:
            with tempfile.TemporaryDirectory() as tmp:
                for i, uri in enumerate(d.get("stagedTars", [])):
                    local = os.path.join(tmp, f"staged_{i}.tar.gz")
                    self.controller.deepstore.download(uri, local)
                    new_dirs.append(untar_segment(local,
                                                  os.path.join(tmp, f"d{i}")))
                new_names = self.controller.replace_segments(
                    table, list(d["from"]), new_dirs, custom=d.get("custom"))
        finally:
            # staged tars are consumed (or the swap failed) either way —
            # leaving them would accumulate unbounded deep-store garbage
            # across failed merge attempts
            for uri in d.get("stagedTars", []):
                try:
                    self.controller.deepstore.delete(uri)
                # graftcheck: ignore[exception-hygiene] -- staged-tar GC is
                # best-effort; a missed delete is re-collected by the next
                # merge round, never a correctness issue
                except Exception:
                    pass
        return json_response({"status": "OK", "segments": new_names})

    def _table_status(self, parts, params, body):
        return json_response(self.controller.table_status(parts[0]))

    # -- admin/read APIs (reference: PinotTableRestletResource et al.) -------
    # reads snapshot under catalog._lock: handlers run on concurrent HTTP
    # threads while writers mutate the same dicts in place (same discipline as
    # _catalog_get above)
    def _get_tables(self, parts, params, body):
        # GET /tables/{t}/ingestionStatus (reference:
        # /tables/{tableName}/ingestionStatus) polls servers over HTTP, so it
        # must not run under the catalog lock
        if len(parts) == 2 and parts[1] == "ingestionStatus":
            try:
                return json_response(self.controller.ingestion_status(parts[0]))
            except ValueError as e:
                return error_response(str(e), 404)
        # GET /tables/{t}/sloStatus — the burn-rate verdict computed by the
        # controller's periodic SLO check (companion of ingestionStatus)
        if len(parts) == 2 and parts[1] == "sloStatus":
            try:
                return json_response(self.controller.slo_status(parts[0]))
            except ValueError as e:
                return error_response(str(e), 404)
        # GET /tables/{t}/memoryStatus — the cluster HBM residency verdict
        # computed by the controller's periodic memory check
        if len(parts) == 2 and parts[1] == "memoryStatus":
            try:
                return json_response(self.controller.memory_status(parts[0]))
            except ValueError as e:
                return error_response(str(e), 404)
        with self.catalog._lock:
            if parts:  # GET /tables/{nameWithType} -> the table config
                cfg = self.catalog.table_configs.get(parts[0])
                resp = None if cfg is None else {"config": cfg.to_json()}
            else:
                resp = {"tables": sorted(self.catalog.table_configs)}
        if resp is None:
            return error_response(f"unknown table {parts[0]}", 404)
        return json_response(resp)

    def _get_schema(self, parts, params, body):
        with self.catalog._lock:
            schema = self.catalog.schemas.get(parts[0]) if parts else None
            resp = schema.to_json() if schema is not None else None
        if resp is None:
            return error_response(f"unknown schema {parts[0] if parts else ''}", 404)
        return json_response(resp)

    def _segments_meta(self, parts, params, body):
        """GET /segmentsMeta/{tableNameWithType} — per-segment metadata list."""
        table = parts[0]
        with self.catalog._lock:
            segs = self.catalog.segments.get(table)
            resp = None if segs is None else \
                {"segments": {s: m.to_json() for s, m in segs.items()}}
        if resp is None:
            return error_response(f"unknown table {table}", 404)
        return json_response(resp)

    def _reload_table(self, parts, params, body):
        if parts[0] not in self.catalog.table_configs:
            return error_response(f"unknown table {parts[0]}", 404)
        self.controller.reload_table(parts[0])
        return json_response({"status": "OK", "table": parts[0]})

    def _get_cluster_configs(self, parts, params, body):
        """GET /clusterConfigs (reference: /cluster/configs +
        OperateClusterConfigCommand) — cluster-level dynamic settings, stored
        in the catalog property store under clusterConfig/."""
        with self.catalog._lock:
            out = {k.split("/", 1)[1]: v for k, v in self.catalog.properties.items()
                   if k.startswith("clusterConfig/")}
        return json_response({"clusterConfigs": out})

    def _set_cluster_config(self, parts, params, body):
        """POST /clusterConfigs with {"key": ..., "value": ...} (value null
        deletes)."""
        d = json.loads(body.decode())
        self.catalog.put_property(f"clusterConfig/{d['key']}", d.get("value"))
        return json_response({"status": "OK", "key": d["key"],
                              "value": d.get("value")})

    def _table_state(self, parts, params, body):
        """POST /tableState/{table}?state=enable|disable (reference:
        ChangeTableState)."""
        state = str(params.get("state", "")).lower()
        if state not in ("enable", "disable"):
            return error_response("state must be enable|disable", 400)
        try:
            self.controller.set_table_state(parts[0], state == "enable")
        except ValueError as e:
            return error_response(str(e), 404)
        return json_response({"status": "OK", "table": parts[0], "state": state})

    def _list_tenants(self, parts, params, body):
        """GET /tenants (reference: PinotTenantRestletResource.getAllTenants)."""
        return json_response({"tenants": self.controller.list_tenants()})

    def _update_instance_tags(self, parts, params, body):
        """POST /instanceTags/{instanceId} with {"tags": [...]} (reference:
        PinotInstanceRestletResource.updateInstanceTags)."""
        d = json.loads(body.decode())
        try:
            self.controller.update_instance_tags(parts[0], list(d["tags"]))
        except ValueError as e:
            return error_response(str(e), 404)
        return json_response({"status": "OK", "instance": parts[0],
                              "tags": d["tags"]})

    def _pause_consumption(self, parts, params, body):
        """POST /pauseConsumption/{tableNameWithType} (reference:
        PinotRealtimeTableResource.pauseConsumption)."""
        try:
            return json_response(self.controller.llc.pause_consumption(parts[0]))
        except ValueError as e:
            return error_response(str(e), 400)

    def _resume_consumption(self, parts, params, body):
        try:
            return json_response(self.controller.llc.resume_consumption(parts[0]))
        except ValueError as e:
            return error_response(str(e), 400)

    def _rebalance(self, parts, params, body):
        moves = self.controller.rebalance(parts[0])
        return json_response({"status": "OK", "idealState": moves})

    def _validate(self, parts, params, body):
        """POST /validate — run one RealtimeSegmentValidationManager round now
        (successor repair, dead-replica reassignment, peer-segment healing);
        the same work the 60s periodic task does, on demand for operators."""
        return json_response(self.controller.llc.validate())

    # -- segment completion protocol ----------------------------------------
    def _segment_consumed(self, parts, params, body):
        d = json.loads(body.decode())
        return json_response(self.controller.llc.segment_consumed(
            d["segment"], d["server"], int(d["offset"])))

    def _segment_commit_start(self, parts, params, body):
        d = json.loads(body.decode())
        return json_response({"status": self.controller.llc.segment_commit_start(
            d["segment"], d["server"])})

    def _segment_commit_end(self, parts, params, body):
        """Commit with segment upload: body is the built segment tar."""
        segment = params["segment"]
        server = params["server"]
        offset = int(params["offset"])
        with tempfile.TemporaryDirectory() as tmp:
            seg_dir = _untar_body(body, segment, tmp)
            status = self.controller.llc.segment_commit_end(
                segment, server, seg_dir, offset)
        return json_response({"status": status})

    # -- deep-store proxy ----------------------------------------------------
    def _deepstore_get(self, parts, params, body):
        uri = "/".join(parts)
        # deep-store URIs lead with the table name ("{table}/{segment}.tar.gz"):
        # a table-scoped reader must not exfiltrate raw segments of denied tables
        from ..auth import require_table_access
        if parts:
            require_table_access(parts[0], "READ")
        with tempfile.TemporaryDirectory() as tmp:
            local = os.path.join(tmp, "blob")
            self.controller.deepstore.download(uri, local)
            with open(local, "rb") as f:
                return binary_response(f.read())

    def _deepstore_post(self, parts, params, body):
        uri = "/".join(parts)
        with tempfile.TemporaryDirectory() as tmp:
            local = os.path.join(tmp, "blob")
            with open(local, "wb") as f:
                f.write(body)
            self.controller.deepstore.upload(local, uri)
        return json_response({"status": "OK"})


class ServerService:
    """Server role process: query endpoint over the binary wire format."""

    def __init__(self, server: ServerNode, host: str = "127.0.0.1", port: int = 0,
                 access_control=None, ssl_context=None):
        self.server = server
        # graftfault: cluster-wide chaos drills install the plane at role
        # startup from the `fault.schedule` clusterConfig knob
        from ..utils.faults import activate_from_config
        activate_from_config(server.catalog)
        _configure_journal(server.catalog, server.instance_id)
        self.http = HttpService(host, port, access_control=access_control,
                                ssl_context=ssl_context)
        # mux executor: queries demuxed off mux streams run here, NOT on the
        # HTTP worker that owns the stream (it is busy reading frames); sized
        # by `server.mux.workers` — the scheduler underneath still enforces
        # its own admission control, this only bounds decode/dispatch threads
        workers = int(server.catalog.get_property(
            "clusterConfig/server.mux.workers", 16))
        self._mux_pool = ThreadPoolExecutor(max_workers=max(1, workers),
                                            thread_name_prefix="mux-exec")
        self._mux_open = 0           # open mux streams (gauge has no inc/dec)
        self._mux_lock = threading.Lock()
        # answers encoded and the wall it took: the encode cannot ride in its
        # own payload, so /health's `device` block counts it
        self._encodes = 0
        self._encode_ms = 0.0
        self.http.route("POST", "query", self._query)
        self.http.route("POST", "mux", self._mux, duplex=True)
        self.http.route("POST", "explain", self._explain)
        self.http.route("POST", "stage", self._stage)
        # peer-to-peer mailbox shuffle (reference: GrpcMailboxService +
        # MailboxSend/ReceiveOperator; see multistage/shuffle.py)
        self.http.route("POST", "mailbox", self._mailbox, stream_body=True)
        self.http.route("DELETE", "mailbox", self._mailbox_cancel)
        self.http.route("POST", "leafStage", self._leaf_stage)
        self.http.route("POST", "leafAgg", self._leaf_agg)
        self.http.route("POST", "joinStage", self._join_stage)
        self.http.route("POST", "aggStage", self._agg_stage)
        self.http.route("GET", "health", self._health)
        self.http.route("GET", "debug", self._debug)
        self.http.route("GET", "segments", self._segments)
        self.http.route("GET", "segmentData", self._segment_data)
        self.http.route("GET", "metrics", _metrics_route)
        self.http.start()
        # advertise the query endpoint so brokers can find us (reference: Helix
        # instance config carries host/port)
        info = server.catalog.instances.get(server.instance_id)
        tags = info.tags if info else ["DefaultTenant"]
        server.catalog.register_instance(InstanceInfo(
            server.instance_id, "server", host=self.http.host,
            port=self.http.port, tags=tags, scheme=self.http.scheme))
        # device-routed shuffle: mark this process as the owner of our mailbox
        # endpoint so exchange legs targeting it skip the HTTP hop
        from ..multistage.shuffle import register_local_endpoint
        register_local_endpoint(self.http.url)
        # tiered storage: the HBM pressure sweep runs as a background
        # periodic task in real server processes (tests drive
        # tiering.run_pressure_sweep() directly for determinism)
        server.start_pressure_loop()

    @property
    def url(self) -> str:
        return self.http.url

    def stop(self) -> None:
        from ..multistage.shuffle import unregister_local_endpoint
        unregister_local_endpoint(self.http.url)
        self.server.stop_pressure_loop()
        self.http.stop()
        self._mux_pool.shutdown(wait=False)

    def _mux(self, parts, params, body):
        """POST /mux — one duplex multiplexed query stream (cluster/mux.py):
        tagged request frames demux into the mux executor under a per-stream
        flow-control window (`server.mux.max.inflight`); response frames
        stream back out of order as queries finish. The 200 + chunked headers
        go out before any frame is read — the client reads and writes
        concurrently on the one exchange."""
        from ..auth import current_principal
        from ..utils.metrics import get_registry
        from .mux import serve_mux_stream
        reg = get_registry()
        frames = reg.counter("pinot_server_mux_frames")
        streams_gauge = reg.gauge("pinot_server_mux_streams")
        with self._mux_lock:
            self._mux_open += 1
            streams_gauge.set(self._mux_open)
        max_inflight = int(self.server.catalog.get_property(
            "clusterConfig/server.mux.max.inflight", 64))
        inner = serve_mux_stream(body, self._mux_execute,
                                 executor=self._mux_pool,
                                 max_inflight=max(1, max_inflight),
                                 principal=current_principal(),
                                 on_frame=frames.inc)

        def gen():
            try:
                yield from inner
            finally:
                with self._mux_lock:
                    self._mux_open -= 1
                    streams_gauge.set(self._mux_open)
        return 200, "application/octet-stream", gen()

    def _count_encode(self, ms: float) -> None:
        with self._mux_lock:
            self._encodes += 1
            self._encode_ms += ms

    def _reject_body(self, e) -> dict:
        """429 body: the error plus a Retry-After hint. The scheduler stamps
        its drain-rate estimate on the exception; when absent (e.g. a quota
        bucket rejection) fall back to asking the scheduler directly so every
        429 tells the client WHEN retrying could succeed."""
        body = {"error": str(e)}
        hint = getattr(e, "retry_after_ms", None)
        if hint is None and self.server.scheduler is not None:
            hint = self.server.scheduler.retry_after_ms()
        if hint is not None:
            body["retryAfterMs"] = round(float(hint), 3)
        return body

    @staticmethod
    def _timeout_body(e) -> dict:
        """408 body: the error plus the absolute deadline that expired, so the
        client can see exactly how stale its budget was."""
        body = {"error": str(e)}
        d = getattr(e, "deadline_epoch_ms", None)
        if d is not None:
            body["deadlineEpochMs"] = round(float(d), 3)
        return body

    def _mux_execute(self, payload, flow_wait_ms):
        """One mux request frame -> (status, response parts). Mirrors
        `_query` exactly — same ACL check, trace-splice surface, and
        backpressure statuses (429/408 ride the frame like HTTP statuses so
        the broker's failure taxonomy is transport-agnostic) — plus the
        flow-control wait recorded as a span and a stats key, keeping the
        milliseconds a frame spent gated by the window attributable. The
        response is gathered `encode_segment_result_parts` buffers: array
        payloads go to the socket without an intermediate join."""
        from ..auth import require_table_access
        from ..query.scheduler import QueryRejectedError, QueryTimeoutError
        from ..utils.trace import request_trace
        from .wire import encode_segment_result_parts
        with stage("server.decode") as decoded:
            req = decode_query_request(payload)
        require_table_access(req["table"], "READ")
        try:
            with request_trace(bool(req.get("trace")),
                               trace_id=req.get("traceId") or None) as tr:
                if tr is not None and flow_wait_ms:
                    # pre-origin: the window wait and the wire decode both
                    # preceded this trace's origin
                    tr.record("mux:flow_control",
                              -(decoded.ms + flow_wait_ms), flow_wait_ms)
                result = self.server.execute_partial(
                    req["table"], req["sql"], req["segments"],
                    time_filter=req.get("timeFilter"),
                    sole=bool(req.get("sole")))
        except QueryRejectedError as e:  # backpressure, not a server fault
            return 429, [json.dumps(self._reject_body(e)).encode()]
        except QueryTimeoutError as e:
            return 408, [json.dumps(self._timeout_body(e)).encode()]
        qstats.add_ms(result, (qstats.MUX_FLOW_CONTROL_MS, flow_wait_ms),
                      (qstats.SERVER_DECODE_MS, decoded.ms))
        spans = None
        if tr is not None:
            spans = [dict(s,
                          name=f"server:{self.server.instance_id}/{s['name']}")
                     for s in tr.to_rows()]
        with stage("server.encode") as encoded:
            parts = encode_segment_result_parts(result, trace_spans=spans)
        self._count_encode(encoded.ms)
        return 200, parts

    def _query(self, parts, params, body):
        from ..auth import require_table_access
        from ..query.scheduler import QueryRejectedError, QueryTimeoutError
        from ..utils.trace import request_trace
        with stage("server.decode") as decoded:
            req = decode_query_request(body)
        require_table_access(req["table"], "READ")
        try:
            # traceId propagates the dispatching broker's trace context so this
            # server's spans splice into the SAME distributed trace
            with request_trace(bool(req.get("trace")),
                               trace_id=req.get("traceId") or None) as tr:
                result = self.server.execute_partial(
                    req["table"], req["sql"], req["segments"],
                    time_filter=req.get("timeFilter"),
                    sole=bool(req.get("sole")))
        except QueryRejectedError as e:   # backpressure, not a server fault
            return 429, "application/json", json.dumps(
                self._reject_body(e)).encode()
        except QueryTimeoutError as e:
            return 408, "application/json", json.dumps(
                self._timeout_body(e)).encode()
        qstats.add_ms(result, (qstats.SERVER_DECODE_MS, decoded.ms))
        spans = None
        if tr is not None:
            # prefix with this server's id so the broker's spliced view reads like
            # its own scatter spans (server:<id>/segment:...)
            spans = [dict(s, name=f"server:{self.server.instance_id}/{s['name']}")
                     for s in tr.to_rows()]
        with stage("server.encode") as encoded:
            payload = encode_segment_result(result, trace_spans=spans)
        self._count_encode(encoded.ms)
        return binary_response(payload)

    def _health(self, parts, params, body):
        """GET /health — pure liveness, always 200 while the process serves
        HTTP; GET /health/readiness — 503 until every ideal-state-assigned
        segment is served or consuming (reference: /health vs
        /health/readiness gated on ServiceStatus). Both are credential-less
        so orchestrators can probe without a token."""
        st = self.server.startup_status()
        st["instance"] = self.server.instance_id
        if self.server.device_pipeline is not None:
            # device-serving observability: batch sizes prove the pipeline
            # amortized fetches; tests/bench read this to verify the served
            # path actually executed on the device
            st["device"] = self.server.device_pipeline.stats()
            # the process's collections and this service's answer encodes
            # beside them: a reader's delta over a window says whether a
            # collection or the encode was behind a stall
            st["device"].update(gc_stats())
            with self._mux_lock:
                st["device"].update(encodes=self._encodes,
                                    encodeMs=round(self._encode_ms, 3))
        if parts and parts[0] == "readiness":
            return json_response(st, status=200 if st["ready"] else 503)
        return json_response(st, status=200)

    def _debug(self, parts, params, body):
        """GET /debug — server metric rollup + gauge rings; GET
        /debug/consuming — consumingSegmentsInfo analog: per-consuming-segment
        offsets, lag, and consumer state for every realtime table; GET
        /debug/memory — the HBM residency ledger panel (top segments by
        bytes, kind breakdown, watermark history, headroom)."""
        from ..utils.metrics import get_registry
        if parts and parts[0] == "consuming":
            return json_response({"instance": self.server.instance_id,
                                  "tables": self.server.ingestion_snapshot()})
        if parts and parts[0] == "memory":
            return json_response(self.server.memory_snapshot())
        if parts and parts[0] == "events":
            return _events_route(params)
        reg = get_registry()
        return json_response({
            "instance": self.server.instance_id,
            "serverMetrics": {k: v for k, v in reg.snapshot().items()
                              if k.startswith("pinot_server")},
            "gaugeHistories": reg.gauge_histories("pinot_server"),
        })

    def _explain(self, parts, params, body):
        from ..auth import require_table_access
        req = decode_query_request(body)
        require_table_access(req["table"], "READ")  # plans leak schema/indexes
        rows = self.server.explain_partial(req["table"], req["sql"],
                                           req["segments"])
        return json_response({"rows": rows})

    # rows per streamed stage-output frame: bounded buffering on both sides
    STAGE_FRAME_ROWS = 65536

    def _stage(self, parts, params, body):
        """POST /stage — run one multistage stage partition on this server:
        hash join, plus the partial GROUP BY when the broker marks this the
        final aggregation stage (reference: an intermediate-stage worker
        consuming its mailbox + AggregateOperator partial mode).

        The response STREAMS over chunked HTTP as length-prefixed wire
        frames: joined rows leave in bounded-row block frames as they are
        sliced (the mailbox-stream analog — neither side buffers the whole
        joined output), and a partial-aggregation result is one frame."""
        import struct

        from ..multistage.runtime import (agg_spec_from_json, run_join_stage,
                                          spec_from_json)
        from ..utils.metrics import get_registry
        from .wire import (decode_block, decode_value, encode_segment_result,
                           encode_value)
        d = decode_value(body)
        out = run_join_stage(spec_from_json(d["spec"]),
                             decode_block(d["left"]), decode_block(d["right"]),
                             agg_spec_from_json(d.get("agg")))
        get_registry().counter("pinot_server_join_stages").inc()

        def frame(obj) -> bytes:
            payload = encode_value(obj)
            return struct.pack(">I", len(payload)) + payload

        def gen():
            if isinstance(out, dict):  # joined block -> bounded row frames
                n = 0
                for v in out.values():
                    n = len(v)
                    break
                step = self.STAGE_FRAME_ROWS
                for lo in range(0, max(n, 1), step):
                    yield frame({"kind": "rows",
                                 "block": {c: v[lo:lo + step]
                                           for c, v in out.items()}})
            else:  # partial aggregation result
                yield frame({"kind": "partial",
                             "result": encode_segment_result(out)})
            yield frame({"kind": "end"})
        return 200, "application/octet-stream", gen()

    # -- peer-to-peer mailbox shuffle endpoints ------------------------------

    def _mailbox(self, parts, params, body):
        """POST /mailbox/{queryId}/{mailboxId} — a PEER streams partition
        frames into this server's mailbox as a chunked request body. Frames
        are enqueued into a BOUNDED per-mailbox queue; when the consuming
        worker falls behind, the enqueue blocks, this thread stops reading the
        socket, and TCP flow control backpressures the sender (reference: the
        gRPC mailbox stream's flow-control window, mailbox.proto:43)."""
        from ..multistage.shuffle import (REGISTRY, MailboxCancelled,
                                          read_frame)
        from .wire import decode_block, decode_segment_result
        qid, mid = parts[0], parts[1]
        from ..utils.metrics import get_registry
        try:
            box = REGISTRY.open(qid, mid)
            while True:
                d = read_frame(body)
                if d["kind"] == "eos":
                    box.put(("eos", d["sender"]))
                    break
                if d["kind"] == "block":
                    box.put(("block", decode_block(d["block"])))
                else:
                    box.put(("partial", decode_segment_result(d["result"])))
                get_registry().counter("pinot_server_mailbox_frames").inc()
        except MailboxCancelled:
            # the 409 must also drain (see below) or the RST race turns a
            # clean "query cancelled" into a misleading connection reset on
            # the sender; the remainder is bounded by the sender's in-memory
            # partition
            try:
                body.drain()
            # graftcheck: ignore[exception-hygiene] -- best-effort drain on
            # the cancel path; the 409 below reports the real outcome
            except Exception:
                pass
            return error_response("query cancelled", 409)
        # drain the chunked-body terminator BEFORE responding: closing the
        # socket with unread bytes in the receive buffer sends a TCP RST that
        # races the 200 on the sender's side (flaky "connection reset")
        body.drain()
        return json_response({"ok": True})

    def _mailbox_cancel(self, parts, params, body):
        """DELETE /mailbox/{queryId} — cancel every mailbox of a query: wakes
        blocked senders and consumers so a failed query unwinds instead of
        hanging on backpressure."""
        from ..multistage.shuffle import REGISTRY
        REGISTRY.cancel_query(parts[0])
        return json_response({"ok": True})

    def _leaf_stage(self, parts, params, body):
        """POST /leafStage — scan local segments, hash-partition on the join
        keys, stream partition frames DIRECTLY to the stage workers' mailboxes
        (the MailboxSendOperator on top of the v1 leaf executor). The broker
        never sees these rows."""
        from ..auth import require_table_access
        from ..multistage.shuffle import run_leaf_join_task
        from .wire import decode_value, encode_value
        task = decode_value(body)
        require_table_access(task["table"], "READ")
        return binary_response(encode_value(run_leaf_join_task(
            self.server, task)))

    def _leaf_agg(self, parts, params, body):
        """POST /leafAgg — distributed single-table GROUP BY leaf: partial
        aggregation locally, group partials hash-partitioned by key and
        streamed to the merge workers."""
        from ..auth import require_table_access
        from ..multistage.shuffle import run_leaf_agg_task
        from .wire import decode_value, encode_value
        task = decode_value(body)
        require_table_access(task["table"], "READ")
        return binary_response(encode_value(run_leaf_agg_task(
            self.server, task)))

    def _join_stage(self, parts, params, body):
        """POST /joinStage — one join-stage partition: consume both side
        mailboxes, join, and either forward to the next stage's mailboxes or
        stream final partial frames back. Errors surface as a terminal error
        frame so the broker reports the cause instead of a truncated stream."""
        from ..multistage.shuffle import frame_bytes, run_join_stage_task
        from ..utils.metrics import get_registry
        from .wire import decode_value
        task = decode_value(body)
        get_registry().counter("pinot_server_join_stages").inc()

        def gen():
            try:
                yield from run_join_stage_task(task)
            except Exception as e:
                yield frame_bytes({"kind": "error",
                                   "message": f"{type(e).__name__}: {e}"})
        return 200, "application/octet-stream", gen()

    def _agg_stage(self, parts, params, body):
        """POST /aggStage — one merge partition of a distributed GROUP BY:
        merge this disjoint key range, apply HAVING + top-k trim, stream the
        merged partial back."""
        from ..multistage.shuffle import frame_bytes, run_agg_stage_task
        from .wire import decode_value
        task = decode_value(body)

        def gen():
            try:
                yield from run_agg_stage_task(task)
            except Exception as e:
                yield frame_bytes({"kind": "error",
                                   "message": f"{type(e).__name__}: {e}"})
        return 200, "application/octet-stream", gen()

    def _segments(self, parts, params, body):
        return json_response({"segments": self.server.segments_served(parts[0])})

    def _segment_data(self, parts, params, body):
        """GET /segmentData/{table}/{segment} — tar of this server's LOADED
        copy (reference: peer download scheme; every ONLINE replica can serve
        the committed bytes when the deep store can't)."""
        import tempfile as _tf

        from ..auth import require_table_access
        from .deepstore import tar_segment
        table, name = parts[0], parts[1]
        require_table_access(table, "READ")  # raw data = same ACL as queries
        seg_dir = self.server.local_segment_dir(table, name)
        if seg_dir is None:
            return error_response(f"{table}/{name} not served here", 404)
        with _tf.TemporaryDirectory() as tmp:
            tar_path = os.path.join(tmp, "seg.tar.gz")
            tar_segment(seg_dir, tar_path)
            with open(tar_path, "rb") as f:
                return binary_response(f.read())


class MinionService:
    """Minion role process: claims tasks from the controller and executes them
    (reference: `pinot-minion/.../MinionStarter.java` — a worker that registers
    with Helix, polls the task framework, and runs registered executors).

    The claim loop runs on a daemon thread: claim one task, execute, repeat;
    sleep `poll_s` when the queue is empty. Task failures never kill the loop
    (MinionWorker.run_once already fences + records them)."""

    def __init__(self, worker, host: str = "127.0.0.1", port: int = 0,
                 poll_s: float = 1.0, access_control=None, ssl_context=None):
        self.worker = worker
        self.poll_s = poll_s
        self._stop = threading.Event()
        self.http = HttpService(host, port, access_control=access_control,
                                ssl_context=ssl_context)
        self.http.route("GET", "health", self._health)
        self.http.route("GET", "tasks", self._tasks)
        self.http.route("GET", "metrics", _metrics_route)
        self.http.start()
        worker.catalog.register_instance(InstanceInfo(
            worker.instance_id, "minion", host=self.http.host,
            port=self.http.port, scheme=self.http.scheme))
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"{worker.instance_id}-loop")
        self._thread.start()

    @property
    def url(self) -> str:
        return self.http.url

    def _loop(self) -> None:
        from ..utils.metrics import get_registry
        reg = get_registry()
        while not self._stop.is_set():
            try:
                spec = self.worker.run_once()
            except Exception:
                # claim-transport hiccup (controller restarting): back off
                reg.counter("pinot_minion_claim_errors").inc()
                spec = None
            if spec is None:
                self._stop.wait(self.poll_s)
            else:
                reg.counter("pinot_minion_tasks_executed").inc()

    def _health(self, parts, params, body):
        return json_response({"status": "OK",
                              "instance": self.worker.instance_id,
                              "completed": self.worker.completed,
                              "failed": self.worker.failed})

    def _tasks(self, parts, params, body):
        return json_response({"completed": self.worker.completed,
                              "failed": self.worker.failed})

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        self.http.stop()


class BrokerService:
    """Broker role process: SQL entry over HTTP; discovers servers via catalog."""

    def __init__(self, broker: Broker, host: str = "127.0.0.1", port: int = 0,
                 access_control=None, ssl_context=None,
                 mux: Optional[bool] = None):
        self.broker = broker
        # graftfault: brokers join cluster-wide chaos drills too (frame drops
        # and conn resets inject on the dispatching side)
        from ..utils.faults import activate_from_config
        activate_from_config(broker.catalog)
        _configure_journal(broker.catalog, broker.instance_id)
        self._registered: Dict[str, str] = {}   # instance_id -> endpoint url
        self._handles: Dict[str, RemoteServerHandle] = {}  # for close()
        # `mux` pins the server-dispatch transport (tests dispatch both ways
        # and diff); None defers to the `broker.mux.enabled` knob per handle
        self._mux_override = mux
        self.http = HttpService(host, port, access_control=access_control,
                                ssl_context=ssl_context)
        self.http.route("POST", "query", self._query)
        self.http.route("POST", "queryStream", self._query_stream)
        self.http.route("GET", "health",
                        lambda p, q, b: json_response({"status": "OK"}))
        self.http.route("GET", "metrics", _metrics_route)
        # GET /debug — query rollups + recent slow queries (JSON); the
        # operator-facing companion to the Prometheus /metrics exposition.
        # GET /debug/traces — the sampled-trace ring (see _debug).
        self.http.route("GET", "debug", self._debug)
        # subscribe BEFORE the initial scan: a server registering in between then
        # fires an event we handle (re-scan), instead of being silently missed
        broker.catalog.subscribe(self._on_event)
        self._wire_server_handles()
        self.broker.failure_detector.start()  # background re-probe loop
        self.http.start()
        # advertise the SQL endpoint (the controller's query-console proxy
        # and external clients discover brokers through the catalog)
        broker.catalog.register_instance(InstanceInfo(
            broker.instance_id, "broker", host=self.http.host,
            port=self.http.port, scheme=self.http.scheme))

    @property
    def url(self) -> str:
        return self.http.url

    def stop(self) -> None:
        self.broker.failure_detector.stop()  # kill the background probe loop
        for handle in self._handles.values():
            handle.close()   # retire mux streams (goodbye frame, join threads)
        self._handles.clear()
        self.http.stop()

    def _mux_enabled(self) -> bool:
        if self._mux_override is not None:
            return self._mux_override
        v = self.broker.catalog.get_property(
            "clusterConfig/broker.mux.enabled", True)
        return str(v).lower() not in ("false", "0", "no")

    def _mux_streams(self) -> int:
        try:
            return max(1, int(self.broker.catalog.get_property(
                "clusterConfig/broker.mux.streams", 1)))
        except (TypeError, ValueError):
            return 1

    def _debug(self, parts, params, body):
        """GET /debug — broker query rollups. GET /debug/traces — the retained
        (sampled + slow) trace ring: `?id=<traceId>` resolves one trace (404
        when evicted/unknown), `?limit=N` bounds the listing, `?format=chrome`
        renders a Chrome trace-event document loadable in Perfetto.
        GET /debug/workload — the workload registry: per-shape profiles
        ranked by time share (`?k=N` trims the ranking, `?fp=<fingerprint>`
        drills into one shape, 404 when unknown/evicted)."""
        if parts and parts[0] == "workload":
            fp = params.get("fp")
            if fp:
                prof = self.broker.workload.shape(fp)
                if prof is None:
                    return error_response(f"unknown shape {fp}", 404)
                return json_response(prof)
            try:
                k = int(params["k"]) if "k" in params else None
            except (TypeError, ValueError):
                k = None
            return json_response(self.broker.workload.snapshot(k))
        if parts and parts[0] == "events":
            return _events_route(params)
        if parts and parts[0] == "traces":
            from ..utils.trace import to_chrome_trace
            ring = self.broker.trace_ring
            trace_id = params.get("id")
            if trace_id:
                entry = ring.get(trace_id)
                if entry is None:
                    return error_response(f"unknown trace {trace_id}", 404)
                if params.get("format") == "chrome":
                    return json_response(to_chrome_trace(entry))
                return json_response(entry)
            try:
                limit = int(params["limit"]) if "limit" in params else None
            except (TypeError, ValueError):
                limit = None
            traces = ring.entries(limit)
            if params.get("format") == "chrome":
                return json_response(to_chrome_trace(traces))
            return json_response({"traces": traces, "retained": len(ring),
                                  "capacity": ring.capacity})
        return (200, "application/json",
                json.dumps(self.broker.debug_stats(), default=str).encode())

    def _on_event(self, event: str, _key: str) -> None:
        if event == "instance":
            self._wire_server_handles()

    def _wire_server_handles(self) -> None:
        """Register an HTTP handle for every advertised live server instance.

        Only new/changed endpoints are (re)registered — re-registering marks the
        server healthy, which must not resurrect a server the failure detector
        already excluded (reference: routing exclusion survives until the
        detector's retry probe succeeds). Decommissioned/dead instances are
        FORGOTTEN by the detector so their probes stop and a reused port can
        never re-admit a dead server id."""
        for info in list(self.broker.catalog.instances.values()):
            if info.role != "server" or not info.port:
                continue
            if not info.alive:
                if self._registered.pop(info.instance_id, None):
                    self.broker.unregister_server(info.instance_id)
                    old = self._handles.pop(info.instance_id, None)
                    if old is not None:
                        old.close()
                continue
            url = info.url
            if self._registered.get(info.instance_id) == url:
                continue
            self._registered[info.instance_id] = url
            handle = RemoteServerHandle(url, use_mux=self._mux_enabled(),
                                        mux_streams=self._mux_streams())
            old = self._handles.pop(info.instance_id, None)
            if old is not None:
                old.close()  # endpoint changed: retire the old mux streams
            self._handles[info.instance_id] = handle

            def probe(u=url):
                # /health is auth-exempt; ready=false still proves liveness
                from .http_service import HttpError, http_call
                try:
                    http_call("GET", f"{u}/health", timeout=2.0)
                    return True
                except HttpError as e:
                    return e.status == 503  # alive but not ready: re-admit
                except Exception:
                    return False
            self.broker.register_server_handle(info.instance_id, handle,
                                               explain_handle=handle.explain,
                                               probe=probe,
                                               stage_handle=handle.join_stage,
                                               url=url)

    def _query(self, parts, params, body):
        d = json.loads(body.decode())
        sql = d["sql"]
        # table-level ACL before any work (reference: broker AccessControl
        # .hasAccess(requesterIdentity, tables) right after compile). The parsed
        # statement is handed to the broker so the SQL is parsed ONCE; a parse
        # failure defers to handle_query, which raises AND counts the broker
        # query-exception meter.
        from ..auth import current_principal, require_table_access
        stmt = None
        if current_principal() is not None:
            from ..sql.parser import parse_query
            try:
                stmt = parse_query(sql)
            except Exception:
                stmt = None
            if stmt is not None:
                for table in [stmt.table] + [j.table for j in stmt.joins]:
                    require_table_access(table, "READ")
        result = self.broker.handle_query(sql, stmt=stmt)
        return json_response(result.to_json())

    def _query_stream(self, parts, params, body):
        """POST /queryStream — JSON-lines over chunked HTTP: one
        {"columns": [...]} line, then {"rows": [...]} lines per batch
        (reference: the gRPC streaming endpoint server.proto:42)."""
        d = json.loads(body.decode())
        sql = d["sql"]
        from ..auth import current_principal, require_table_access
        stmt = None
        if current_principal() is not None:
            from ..sql.parser import parse_query
            try:
                stmt = parse_query(sql)
            except Exception:
                stmt = None
            if stmt is not None:
                for table in [stmt.table] + [j.table for j in stmt.joins]:
                    require_table_access(table, "READ")

        def gen(stmt=stmt):
            from ..query.result import _jsonify
            for kind, payload in self.broker.stream_query(sql, stmt=stmt):
                if kind == "schema":
                    yield (json.dumps({"columns": payload}) + "\n").encode()
                else:
                    yield (json.dumps(
                        {"rows": [[_jsonify(v) for v in r] for r in payload]})
                        + "\n").encode()
        return 200, "application/x-ndjson", gen()
