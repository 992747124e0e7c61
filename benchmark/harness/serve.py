"""The system under test, started the way its users start it: every role in
this one chip-owning process through `run_service_manager(block=False)`
(copied from chip_smoke.py: start_services, stop_services, the pipeline's
counters). The segments reach the server through the controller's
`upload_segment`: metadata, assignment and ideal state as in production."""

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor, as_completed

from . import build

LOAD_TIMEOUT_S = 600.0


def start_services(work: str, cluster_cfg: dict):
    from pinot_tpu.cluster.process import run_service_manager
    os.makedirs(work, exist_ok=True)
    cfg_path = os.path.join(work, "cluster.json")
    with open(cfg_path, "w") as f:
        json.dump(cluster_cfg, f)
    return run_service_manager(os.path.join(work, "work"),
                               os.path.join(work, "run"),
                               config_path=cfg_path, block=False)


def stop_services(handles) -> None:
    handles["minion"].stop()  # claim loop first: it polls the controller
    handles["server_obj"].shutdown()
    handles["controller_obj"].stop_periodic_tasks()
    for c in handles["catalogs"]:
        c.close()
    for role in ("controller", "server", "broker"):
        handles[role].stop()


def server_segment_dir(work: str, table_with_type: str) -> str:
    """Where server_0 keeps a table's segments. A segment directory that is
    already there is loaded without a download (a server that restarts warm)."""
    return os.path.join(work, "work", "server_0", table_with_type)


def create_table(handles, config: dict, table_with_type: str) -> None:
    """Schema + OFFLINE table; `table_with_type` is the name the segment
    directories were made under, which has to be the program's."""
    from pinot_tpu.cluster.process import ControllerClient
    from pinot_tpu.table import TableConfig
    ctrl = ControllerClient(handles["controller"].url)
    ctrl.add_schema(build.make_schema(config))
    table = TableConfig(config["table"])
    if table.table_name_with_type != table_with_type:
        raise SystemExit(f"the program names the table "
                         f"{table.table_name_with_type!r}, not {table_with_type!r}")
    ctrl.add_table(table)


def upload_as_built(handles, table_with_type: str, builds) -> tuple:
    """Each segment, as soon as its worker has built it, through the
    controller object's `upload_segment` (gzip into the deep store, metadata,
    assignment; zlib frees the GIL, so one thread a segment). `builds` are the
    workers' futures; returns (their results, the seconds the uploads took
    after the last build)."""
    controller = handles["controller_obj"]
    built, uploads = [], []
    with ThreadPoolExecutor(max_workers=len(builds)) as pool:
        for f in as_completed(builds):
            b = f.result()
            built.append(b)
            uploads.append(pool.submit(controller.upload_segment,
                                       table_with_type, b["seg_dir"]))
        t0 = time.perf_counter()
        for u in uploads:
            u.result()
    return built, time.perf_counter() - t0


def wait_loaded(handles, config: dict, rows: int) -> float:
    """Until the broker counts every row; the seconds it took."""
    from pinot_tpu.cluster.process import BrokerClient
    broker = BrokerClient(handles["broker"].url)
    t0 = time.perf_counter()
    loaded = -1
    while loaded != rows:
        if time.perf_counter() - t0 > LOAD_TIMEOUT_S:
            raise TimeoutError(f"only {loaded}/{rows} rows loaded after "
                               f"{LOAD_TIMEOUT_S:.0f}s")
        r = broker.query(f"SELECT COUNT(*) FROM {config['table']}")
        r = r["resultTable"]["rows"]
        loaded = r[0][0] if r else 0
        if loaded != rows:
            time.sleep(0.1)
    return time.perf_counter() - t0


def pipeline_counters(handles) -> dict:
    """The device pipeline's counters, over the server's HTTP surface."""
    from pinot_tpu.cluster.http_service import get_json
    return get_json(f"{handles['server'].url}/health")["device"]


def kernel_cache_misses() -> int:
    from pinot_tpu.utils.metrics import get_registry
    return int(get_registry().counter("pinot_kernel_cache_misses").value)
