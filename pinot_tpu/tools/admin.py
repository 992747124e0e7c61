"""pinot-tpu admin CLI: operate a cluster without writing Python.

Analog of the reference's `pinot-admin.sh` command surface
(`pinot-tools/src/main/java/org/apache/pinot/tools/admin/PinotAdministrator.java`):
role starters, schema/table management, segment push, queries, and segment
tools, all against the controller/broker HTTP APIs.

    python -m pinot_tpu.tools.admin start-controller --work-dir /data --run-dir /run
    python -m pinot_tpu.tools.admin add-schema    --controller URL --file schema.json
    python -m pinot_tpu.tools.admin add-table     --controller URL --file table.json
    python -m pinot_tpu.tools.admin list-tables   --controller URL
    python -m pinot_tpu.tools.admin upload-segment --controller URL --table t_OFFLINE --dir seg/
    python -m pinot_tpu.tools.admin build-segment --schema schema.json --input rows.json \\
                                                  --out dir --name seg_0
    python -m pinot_tpu.tools.admin query         --broker URL --sql "SELECT ..."
    python -m pinot_tpu.tools.admin table-status  --controller URL --table t_OFFLINE
    python -m pinot_tpu.tools.admin reload-table  --controller URL --table t_OFFLINE
    python -m pinot_tpu.tools.admin dump-segment  --dir seg/
    python -m pinot_tpu.tools.admin verify-segment --dir seg/
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Optional, Sequence


def _print(obj: Any) -> None:
    print(json.dumps(obj, indent=2, default=str))


def _controller(args):
    from ..cluster.process import ControllerClient
    return ControllerClient(args.controller)


def cmd_start_role(args) -> int:
    from ..cluster import process
    from ..utils.compile_cache import place_compile_cache
    place_compile_cache()
    if args.cmd == "start-controller":
        process.run_controller(args.work_dir, args.run_dir, args.port, args.config)
    elif args.cmd == "start-server":
        process.run_server(args.controller, args.instance_id or "server_0",
                           args.work_dir, args.run_dir, args.port, args.config)
    else:
        process.run_broker(args.controller, args.instance_id or "broker_0",
                           args.run_dir, args.port, args.config)
    return 0


def cmd_add_schema(args) -> int:
    from ..schema import Schema
    with open(args.file) as f:
        schema = Schema.from_json(json.load(f))
    _controller(args).add_schema(schema)
    _print({"status": "OK", "schema": schema.name})
    return 0


def cmd_add_table(args) -> int:
    from ..table import TableConfig
    with open(args.file) as f:
        cfg = TableConfig.from_json(json.load(f))
    resp = _controller(args).add_table(cfg, num_partitions=args.num_partitions)
    _print(resp)
    return 0


def cmd_list_tables(args) -> int:
    _print(_controller(args).list_tables())
    return 0


def cmd_table_status(args) -> int:
    _print(_controller(args).table_status(args.table))
    return 0


def cmd_upload_segment(args) -> int:
    _print(_controller(args).upload_segment(args.table, args.dir))
    return 0


def cmd_build_segment(args) -> int:
    """Build a segment from a JSON-lines (or CSV) file + schema json
    (reference: CreateSegmentCommand)."""
    from ..ingest.readers import reader_for
    from ..schema import Schema
    from ..segment.writer import SegmentBuilder, SegmentGeneratorConfig
    with open(args.schema) as f:
        schema = Schema.from_json(json.load(f))
    rows = list(reader_for(args.input, args.format or None).rows())
    cols = {c: [r.get(c) for r in rows] for c in schema.column_names}
    path = SegmentBuilder(schema, SegmentGeneratorConfig()).build(
        cols, args.out, args.name)
    _print({"status": "OK", "segmentDir": path, "rows": len(rows)})
    return 0


def cmd_reload_table(args) -> int:
    _print(_controller(args).reload_table(args.table))
    return 0


def cmd_query(args) -> int:
    from ..cluster.process import BrokerClient
    resp = BrokerClient(args.broker).query(args.sql)
    if args.json:
        _print(resp)
        return 0
    table = resp.get("resultTable", {})
    names = table.get("dataSchema", {}).get("columnNames", [])
    rows = table.get("rows", [])
    if names:
        print("\t".join(map(str, names)))
    for row in rows:
        print("\t".join(map(str, row)))
    stats = {k: v for k, v in resp.items() if k != "resultTable"}
    print(f"-- {len(rows)} rows, {json.dumps(stats, default=str)}", file=sys.stderr)
    return 0


def cmd_dump_segment(args) -> int:
    from .segment import dump_segment
    _print(dump_segment(args.dir, max_rows=args.rows))
    return 0


def cmd_verify_segment(args) -> int:
    from .segment import verify_segment
    report = verify_segment(args.dir)
    _print(report)
    return 0 if report["ok"] else 1


def cmd_recommend_config(args) -> int:
    """Reference: the controller recommender endpoint (schema + query
    patterns + throughput -> config advice)."""
    from .tuner import (recommend, recommend_from_workload,
                        recommend_realtime_provisioning)
    if args.queries:
        with open(args.queries) as f:
            queries = [ln.strip() for ln in f if ln.strip()]
        rec = recommend_from_workload(args.segment_dir, queries,
                                      num_servers=args.num_servers)
    else:
        rec = recommend(args.segment_dir)
    rec.pop("profile", None)   # advice, not the raw dump
    if args.events_per_sec:
        rec["realtimeProvisioning"] = recommend_realtime_provisioning(
            args.events_per_sec, args.avg_row_bytes,
            retention_hours=args.retention_hours,
            host_memory_gb=args.host_memory_gb,
            num_hosts=args.num_servers)
    _print(rec)
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="pinot-tpu-admin", description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd", required=True)

    def role(name):
        sp = sub.add_parser(name)
        # only the controller bootstraps without a controller URL
        sp.add_argument("--controller", required=(name != "start-controller"),
                        default="")
        sp.add_argument("--instance-id", default="")
        sp.add_argument("--work-dir", default="")
        sp.add_argument("--run-dir", required=True)
        sp.add_argument("--port", type=int, default=0)
        sp.add_argument("--config", default="")
        sp.set_defaults(fn=cmd_start_role)
    role("start-controller")
    role("start-server")
    role("start-broker")

    sp = sub.add_parser("start-service-manager")
    sp.add_argument("--work-dir", required=True)
    sp.add_argument("--run-dir", required=True)
    sp.add_argument("--port", type=int, default=0)
    sp.add_argument("--config", default="")
    sp.set_defaults(fn=cmd_start_service_manager)

    sp = sub.add_parser("add-schema")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--file", required=True)
    sp.set_defaults(fn=cmd_add_schema)

    sp = sub.add_parser("add-table")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--file", required=True)
    sp.add_argument("--num-partitions", type=int, default=1)
    sp.set_defaults(fn=cmd_add_table)

    sp = sub.add_parser("list-tables")
    sp.add_argument("--controller", required=True)
    sp.set_defaults(fn=cmd_list_tables)

    sp = sub.add_parser("table-status")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--table", required=True)
    sp.set_defaults(fn=cmd_table_status)

    sp = sub.add_parser("upload-segment")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--table", required=True)
    sp.add_argument("--dir", required=True)
    sp.set_defaults(fn=cmd_upload_segment)

    sp = sub.add_parser("build-segment")
    sp.add_argument("--schema", required=True)
    sp.add_argument("--input", required=True)
    sp.add_argument("--format", default="")
    sp.add_argument("--out", required=True)
    sp.add_argument("--name", required=True)
    sp.set_defaults(fn=cmd_build_segment)

    sp = sub.add_parser("reload-table")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--table", required=True)
    sp.set_defaults(fn=cmd_reload_table)

    sp = sub.add_parser("query")
    sp.add_argument("--broker", required=True)
    sp.add_argument("--sql", required=True)
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_query)

    sp = sub.add_parser("dump-segment")
    sp.add_argument("--dir", required=True)
    sp.add_argument("--rows", type=int, default=10)
    sp.set_defaults(fn=cmd_dump_segment)

    sp = sub.add_parser("verify-segment")
    sp.add_argument("--dir", required=True)
    sp.set_defaults(fn=cmd_verify_segment)

    sp = sub.add_parser("recommend-config")
    sp.add_argument("--segment-dir", required=True,
                    help="a representative built segment")
    sp.add_argument("--queries", default=None,
                    help="file with one representative SQL query per line")
    sp.add_argument("--num-servers", type=int, default=2)
    sp.add_argument("--events-per-sec", type=float, default=0.0,
                    help="also emit realtime provisioning advice")
    sp.add_argument("--avg-row-bytes", type=int, default=256)
    sp.add_argument("--retention-hours", type=int, default=72)
    sp.add_argument("--host-memory-gb", type=float, default=16.0)
    sp.set_defaults(fn=cmd_recommend_config)

    sp = sub.add_parser("quickstart")
    sp.add_argument("--type", dest="qtype", default="batch",
                    choices=["batch", "realtime", "hybrid"])
    sp.add_argument("--rows", type=int, default=10_000)
    sp.add_argument("--work-dir", default=None)
    sp.add_argument("--exit-after-queries", action="store_true")
    sp.set_defaults(fn=cmd_quickstart)

    sp = sub.add_parser("infer-schema")
    sp.add_argument("--input", required=True, help=".csv or .jsonl sample")
    sp.add_argument("--table-name", default=None)
    sp.add_argument("--time-column", default=None)
    sp.set_defaults(fn=cmd_infer_schema)

    sp = sub.add_parser("ingest-job")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--spec", required=True, help="job spec JSON/YAML file")
    sp.add_argument("--distributed", action="store_true",
                    help="queue one task per input file for the minion fleet "
                         "(POST /ingestJobs) instead of running standalone")
    sp.set_defaults(fn=cmd_ingest_job)

    sp = sub.add_parser("cluster-info")
    sp.add_argument("--controller", required=True)
    sp.set_defaults(fn=cmd_cluster_info)

    sp = sub.add_parser("list-tenants")
    sp.add_argument("--controller", required=True)
    sp.set_defaults(fn=cmd_list_tenants)

    sp = sub.add_parser("tag-instance")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--instance", required=True)
    sp.add_argument("--tags", required=True, help="comma-separated")
    sp.set_defaults(fn=cmd_tag_instance)

    sp = sub.add_parser("pause-consumption")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--table", required=True)
    sp.set_defaults(fn=cmd_pause_consumption)

    sp = sub.add_parser("resume-consumption")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--table", required=True)
    sp.set_defaults(fn=cmd_resume_consumption)

    sp = sub.add_parser("rebalance-table")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--table", required=True)
    sp.set_defaults(fn=cmd_rebalance_table)

    sp = sub.add_parser("change-table-state")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--table", required=True)
    sp.add_argument("--state", required=True, choices=["enable", "disable"])
    sp.set_defaults(fn=cmd_change_table_state)

    sp = sub.add_parser("cluster-config")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--set", default=None, help="key=value (omit to list)")
    sp.add_argument("--delete", default=None, help="key to delete")
    sp.set_defaults(fn=cmd_cluster_config)

    sp = sub.add_parser("drop-table")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--table", required=True, help="table name with type")
    sp.set_defaults(fn=cmd_drop_table)

    sp = sub.add_parser("generate-data")
    sp.add_argument("--schema-file", required=True)
    sp.add_argument("--rows", type=int, default=1000)
    sp.add_argument("--out", required=True, help=".csv or .jsonl output path")
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--cardinality", action="append", default=[],
                    help="col=N, repeatable")
    sp.set_defaults(fn=cmd_generate_data)

    sp = sub.add_parser("anonymize-data")
    sp.add_argument("--input", required=True, help=".csv or .jsonl input")
    sp.add_argument("--out", required=True)
    sp.add_argument("--columns", required=True, help="comma-separated")
    sp.set_defaults(fn=cmd_anonymize_data)

    sp = sub.add_parser("compat-check")
    sp.add_argument("--controller", required=True)
    sp.add_argument("--broker", required=True)
    sp.add_argument("--ops", required=True, help="YAML op-sequence file")
    sp.set_defaults(fn=cmd_compat_check)
    return p


def cmd_start_service_manager(args) -> int:
    """Reference: StartServiceManagerCommand — all roles in one process."""
    from ..cluster.process import run_service_manager
    from ..utils.compile_cache import place_compile_cache
    place_compile_cache()
    run_service_manager(args.work_dir, args.run_dir, args.port, args.config)
    return 0


def cmd_quickstart(args) -> int:
    """Reference: Quickstart / RealtimeQuickStart / HybridQuickstart."""
    from .quickstart import run_quickstart
    return run_quickstart(args.qtype, rows=args.rows, work_dir=args.work_dir,
                          exit_after_queries=args.exit_after_queries)


def cmd_infer_schema(args) -> int:
    """Reference: JsonToPinotSchema / AvroSchemaToPinotSchema."""
    from .datagen import infer_schema
    schema = infer_schema(args.input, table_name=args.table_name,
                          time_column=args.time_column)
    _print(schema.to_json())
    return 0


def cmd_ingest_job(args) -> int:
    """Reference: LaunchDataIngestionJobCommand over a job-spec file."""
    import json as _json
    from ..cluster.process import ControllerClient
    from ..ingest.batch import BatchIngestionJobSpec, run_batch_ingestion

    with open(args.spec) as f:
        text = f.read()
    try:
        d = _json.loads(text)
    except ValueError:
        import yaml
        d = yaml.safe_load(text)
    if getattr(args, "distributed", False):
        # scale-out path: the controller splits the job per input file and
        # the minion fleet executes in parallel (hadoop/spark-runner analog)
        from ..cluster.http_service import post_json
        resp = post_json(f"{args.controller.rstrip('/')}/ingestJobs", {
            "table": d["table"],
            "inputPaths": d.get("inputPaths", d.get("input_paths", [])),
            "inputFormat": d.get("inputFormat"),
            "segmentNamePrefix": d.get("segmentNamePrefix", ""),
            "segmentRows": int(d.get("segmentRows", 1_000_000)),
            "filterExpr": d.get("filterExpr"),
            "columnTransforms": d.get("columnTransforms", {}),
        })
        print(f"queued {len(resp['tasks'])} tasks: {resp['tasks']}")
        return 0
    spec = BatchIngestionJobSpec(
        input_paths=d.get("inputPaths", d.get("input_paths", [])),
        input_format=d.get("inputFormat"),
        table=d["table"],
        segment_name_prefix=d.get("segmentNamePrefix", ""),
        segment_rows=int(d.get("segmentRows", 1_000_000)),
        filter_expr=d.get("filterExpr"),
        column_transforms=d.get("columnTransforms", {}),
    )
    import tempfile
    with tempfile.TemporaryDirectory() as work:
        pushed = run_batch_ingestion(spec, _RemoteJobController(
            ControllerClient(args.controller), spec.table), work_dir=work)
    print(f"pushed {len(pushed)} segments: {pushed}")
    return 0


class _RemoteJobController:
    """Minimal controller facade the batch runner needs, over HTTP — fetches
    only the job's table config + schema (not the whole cluster's)."""

    def __init__(self, client, table: str):
        self._client = client
        from ..schema import Schema
        from ..table import TableConfig

        class _Cat:
            pass
        cfg = TableConfig.from_json(client.table_config(table)["config"])
        self.catalog = _Cat()
        self.catalog.table_configs = {table: cfg}
        self.catalog.schemas = {
            cfg.name: Schema.from_json(client.get_schema(cfg.name))}

    def upload_segment(self, table, seg_dir, custom=None):
        import os
        import types
        resp = self._client.upload_segment(table, seg_dir)
        # normalize the HTTP response to the in-proc SegmentMeta surface the
        # batch runner consumes
        return types.SimpleNamespace(
            name=resp.get("segment") or os.path.basename(seg_dir.rstrip("/")))


def cmd_cluster_info(args) -> int:
    """Reference: ShowClusterInfo / VerifyClusterState."""
    from ..cluster.http_service import get_json
    from ..cluster.process import ControllerClient
    c = ControllerClient(args.controller)
    tables = c.list_tables().get("tables", {})
    tenants = get_json(f"{c.url}/tenants", token=c.token).get("tenants", {})
    print(f"tenants: {tenants}")
    ok = True
    for name in tables:
        st = c.table_status(name)
        ok &= bool(st.get("converged"))
        print(f"{name}: segments={st.get('segments')} "
              f"converged={st.get('converged')}")
    print("cluster state: " + ("GOOD" if ok else "NOT CONVERGED"))
    return 0 if ok else 1


def cmd_list_tenants(args) -> int:
    from ..cluster.http_service import get_json
    from ..cluster.process import ControllerClient
    c = ControllerClient(args.controller)
    _print(get_json(f"{c.url}/tenants", token=c.token))
    return 0


def cmd_tag_instance(args) -> int:
    from ..cluster.http_service import post_json
    from ..cluster.process import ControllerClient
    c = ControllerClient(args.controller)
    _print(post_json(f"{c.url}/instanceTags/{args.instance}",
                     {"tags": args.tags.split(",")}, token=c.token))
    return 0


def cmd_pause_consumption(args) -> int:
    from ..cluster.http_service import post_json
    from ..cluster.process import ControllerClient
    c = ControllerClient(args.controller)
    _print(post_json(f"{c.url}/pauseConsumption/{args.table}", {}, token=c.token))
    return 0


def cmd_resume_consumption(args) -> int:
    from ..cluster.http_service import post_json
    from ..cluster.process import ControllerClient
    c = ControllerClient(args.controller)
    _print(post_json(f"{c.url}/resumeConsumption/{args.table}", {}, token=c.token))
    return 0


def cmd_rebalance_table(args) -> int:
    from ..cluster.process import ControllerClient
    _print(ControllerClient(args.controller).rebalance(args.table))
    return 0


def cmd_change_table_state(args) -> int:
    from ..cluster.http_service import http_call
    from ..cluster.process import ControllerClient
    import json as _json
    c = ControllerClient(args.controller)
    out = http_call("POST", f"{c.url}/tableState/{args.table}?state={args.state}",
                    b"{}", token=c.token)
    _print(_json.loads(out.decode()))
    return 0


def cmd_cluster_config(args) -> int:
    """Reference: OperateClusterConfigCommand (GET/POST/DELETE cluster configs)."""
    from ..cluster.http_service import get_json, post_json
    from ..cluster.process import ControllerClient
    c = ControllerClient(args.controller)
    if args.set:
        key, _, value = args.set.partition("=")
        _print(post_json(f"{c.url}/clusterConfigs",
                         {"key": key, "value": value}, token=c.token))
    elif args.delete:
        _print(post_json(f"{c.url}/clusterConfigs",
                         {"key": args.delete, "value": None}, token=c.token))
    else:
        _print(get_json(f"{c.url}/clusterConfigs", token=c.token))
    return 0


def cmd_drop_table(args) -> int:
    from ..cluster.process import ControllerClient
    ControllerClient(args.controller).drop_table(args.table)
    print(f"dropped {args.table}")
    return 0


def cmd_generate_data(args) -> int:
    """Reference: GenerateDataCommand."""
    import json as _json
    from ..schema import Schema
    from .datagen import generate_columns, write_csv, write_jsonl
    with open(args.schema_file) as f:
        schema = Schema.from_json(_json.load(f))
    cards = {}
    for spec in args.cardinality:
        col, _, n = spec.partition("=")
        cards[col] = int(n)
    cols = generate_columns(schema, args.rows, seed=args.seed,
                            cardinalities=cards)
    (write_csv if args.out.endswith(".csv") else write_jsonl)(args.out, cols)
    print(f"wrote {args.rows} rows to {args.out}")
    return 0


def cmd_anonymize_data(args) -> int:
    """Reference: AnonymizeDataCommand."""
    from .datagen import anonymize_file
    anonymize_file(args.input, args.out, args.columns.split(","))
    print(f"anonymized {args.columns} -> {args.out}")
    return 0


def cmd_compat_check(args) -> int:
    """Reference: pinot-compatibility-verifier CompatibilityOpsRunner CLI."""
    from .compat import CompatibilityOpsRunner
    runner = CompatibilityOpsRunner(args.controller, args.broker)
    ok = runner.run(args.ops)
    for line in runner.log:
        print(line)
    print("PASS" if ok else "FAIL")
    return 0 if ok else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    import os
    token = os.environ.get("PINOT_TPU_AUTH_TOKEN")
    if token:  # bearer identity for every remote call this invocation makes
        from ..cluster.http_service import set_default_token
        set_default_token(token)
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    # die quietly when the downstream pipe closes (e.g. `... | head`)
    import signal
    signal.signal(signal.SIGPIPE, signal.SIG_DFL)
    sys.exit(main())
