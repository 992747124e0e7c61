"""Multi-process cluster: each role as an OS process, joined over HTTP.

This is the real deployment shape (reference: one JVM per role started by
`PinotAdministrator` Start*Command; here one Python process per role started by
`python -m pinot_tpu.cluster.process` or the admin CLI). The controller owns the
catalog + deep store; servers and brokers join with `RemoteCatalog` (watch-based
mirror) and talk data-plane over the binary wire format.

`ProcessCluster` is the test/quickstart harness that spawns the processes and waits
for readiness (reference: ClusterTest boots embedded roles; here they are genuinely
separate processes so a kill is a real process death).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.parse
from typing import Dict, List, Optional, Sequence

from .http_service import HttpError, get_json, http_call, post_json


def _write_ready(run_dir: str, name: str, payload: Dict) -> None:
    path = os.path.join(run_dir, f"{name}.ready")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def _load_config(config_path: str, cli_port: int, port_key: str):
    """Shared config stack for role starters. The CLI port (when explicitly
    given) is the topmost override layer, matching the documented precedence
    explicit args > env > files > defaults."""
    from .. import plugins
    from ..config import Configuration
    overrides = {port_key: cli_port} if cli_port else {}
    cfg = Configuration.load(config_path or None, overrides=overrides)
    plugins.load_from_config(cfg)
    return cfg


def _setup_auth(cfg):
    """Access control for this role's endpoints + this process's outgoing
    identity (reference: BasicAuthAccessControlFactory + per-service tokens)."""
    from ..auth import StaticTokenAccessControl
    from .http_service import set_default_token
    set_default_token(cfg.get_str("auth.service.token"))
    return StaticTokenAccessControl.from_config(cfg)


def _apply_client_tls(cfg) -> bool:
    """Apply the config's tls.* trust to THIS process's outgoing clients.
    Returns whether TLS is enabled (one parser for every consumer)."""
    from .http_service import set_default_tls
    if not cfg.get_bool("tls.enabled"):
        return False
    set_default_tls(cafile=cfg.get_str("tls.ca"),
                    insecure=cfg.get_bool("tls.insecure"))
    return True


def _setup_tls(cfg):
    """Server-side SSL context + this process's outgoing trust, from tls.*
    config (reference: pinot.*.tls.* keystore/truststore keys,
    TlsIntegrationTest): `tls.enabled`, `tls.cert`/`tls.key` (PEM), `tls.ca`
    (the cluster's CA bundle — self-signed in tests)."""
    if not _apply_client_tls(cfg):
        return None
    import ssl
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(cfg.get_str("tls.cert"), cfg.get_str("tls.key"))
    return ctx


def run_controller(work_dir: str, run_dir: str, port: int = 0,
                   config_path: str = "") -> None:
    from .catalog import Catalog
    from .controller import Controller
    from .deepstore import create_fs
    from .services import ControllerService

    cfg = _load_config(config_path, port, "controller.port")
    access_control = _setup_auth(cfg)
    ssl_ctx = _setup_tls(cfg)
    catalog = Catalog()
    # deep store is configurable by scheme (reference:
    # controller.data.dir + pinot.controller.storage.factory.class.*),
    # optionally wrapped by the segment crypter (encryption at rest)
    from ..crypt import wrap_deepstore_from_config
    deepstore = wrap_deepstore_from_config(create_fs(cfg.get_str(
        "controller.deepstore",
        f"local://{os.path.join(work_dir, 'deepstore')}")), cfg)
    controller = Controller("controller_0", catalog, deepstore,
                            os.path.join(work_dir, "controller"))
    svc = ControllerService(controller, port=cfg.get_int("controller.port", 0),
                            access_control=access_control,
                            ssl_context=ssl_ctx)
    controller.start_periodic_tasks()  # retention/repair/relocation/status
    _write_ready(run_dir, "controller_0", {"url": svc.url})
    signal.sigwait({signal.SIGTERM, signal.SIGINT})
    controller.stop_periodic_tasks()


def run_server(controller_url: str, instance_id: str, work_dir: str,
               run_dir: str, port: int = 0, config_path: str = "") -> None:
    from ..query.scheduler import scheduler_from_config
    from .remote import ControllerDeepStore, RemoteCatalog, RemoteCompletion
    from .server import ServerNode
    from .services import ServerService

    # defaults < config file < PINOT_TPU_* env < CLI args (reference:
    # PinotConfiguration stack consumed by HelixServerStarter)
    cfg = _load_config(config_path, port, "server.port")
    access_control = _setup_auth(cfg)
    ssl_ctx = _setup_tls(cfg)
    catalog = RemoteCatalog(controller_url)
    deepstore = ControllerDeepStore(controller_url)
    from .device_server import pipeline_from_config
    server = ServerNode(instance_id, catalog, deepstore,
                        os.path.join(work_dir, instance_id),
                        tags=cfg.get_list("server.tenant.tags") or None,
                        completion=RemoteCompletion(controller_url),
                        scheduler=scheduler_from_config(cfg),
                        auto_consume=True,  # real processes pump themselves
                        device_pipeline=pipeline_from_config(cfg))
    svc = ServerService(server, port=cfg.get_int("server.port", 0),
                        access_control=access_control, ssl_context=ssl_ctx)
    _write_ready(run_dir, instance_id, {"url": svc.url})
    signal.sigwait({signal.SIGTERM, signal.SIGINT})
    server.shutdown()


def run_minion(controller_url: str, instance_id: str, work_dir: str,
               run_dir: str, port: int = 0, config_path: str = "") -> None:
    """Minion role process (reference: MinionStarter): joins via RemoteCatalog,
    claims tasks through the controller's atomic REST queue, fetches inputs
    through the deep-store proxy, pushes outputs through the standard segment
    upload/replace endpoints."""
    from ..minion.tasks import MinionWorker
    from .remote import (ControllerDeepStore, RemoteCatalog, RemoteController,
                         RemoteTaskQueue)
    from .services import MinionService

    cfg = _load_config(config_path, port, "minion.port")
    access_control = _setup_auth(cfg)
    ssl_ctx = _setup_tls(cfg)
    catalog = RemoteCatalog(controller_url)
    worker = MinionWorker(instance_id, catalog,
                          ControllerDeepStore(controller_url),
                          RemoteController(controller_url,
                                           cfg.get_str("auth.service.token")),
                          os.path.join(work_dir, instance_id),
                          queue=RemoteTaskQueue(controller_url))
    svc = MinionService(worker, port=cfg.get_int("minion.port", 0),
                        poll_s=cfg.get_float("minion.poll.seconds", 1.0),
                        access_control=access_control, ssl_context=ssl_ctx)
    _write_ready(run_dir, instance_id, {"url": svc.url})
    signal.sigwait({signal.SIGTERM, signal.SIGINT})
    svc.stop()
    catalog.close()


def run_broker(controller_url: str, instance_id: str, run_dir: str,
               port: int = 0, config_path: str = "") -> None:
    from .broker import Broker
    from .remote import RemoteCatalog
    from .services import BrokerService

    cfg = _load_config(config_path, port, "broker.port")
    access_control = _setup_auth(cfg)
    ssl_ctx = _setup_tls(cfg)
    catalog = RemoteCatalog(controller_url)
    broker = Broker(instance_id, catalog,
                    max_scatter_threads=cfg.get_int("broker.scatter.threads", 8))
    svc = BrokerService(broker, port=cfg.get_int("broker.port", 0),
                        access_control=access_control, ssl_context=ssl_ctx)
    _write_ready(run_dir, instance_id, {"url": svc.url})
    signal.sigwait({signal.SIGTERM, signal.SIGINT})


def run_service_manager(work_dir: str, run_dir: str, port: int = 0,
                        config_path: str = "", block: bool = True):
    """All roles in ONE process from one bootstrap config (reference:
    PinotServiceManager / StartServiceManagerCommand — the quickstarts' and
    small deployments' topology). Controller, one server, and a broker share
    the process; the server/broker still talk to the controller over its HTTP
    catalog so the wiring matches a distributed deployment."""
    from .broker import Broker
    from .catalog import Catalog
    from .controller import Controller
    from .deepstore import create_fs
    from .remote import ControllerDeepStore, RemoteCatalog, RemoteCompletion
    from .server import ServerNode
    from .services import BrokerService, ControllerService, ServerService

    os.makedirs(run_dir, exist_ok=True)
    cfg = _load_config(config_path, port, "controller.port")
    access_control = _setup_auth(cfg)
    ssl_ctx = _setup_tls(cfg)
    from ..crypt import wrap_deepstore_from_config
    catalog = Catalog()
    deepstore = wrap_deepstore_from_config(create_fs(cfg.get_str(
        "controller.deepstore",
        f"local://{os.path.join(work_dir, 'deepstore')}")), cfg)
    controller = Controller("controller_0", catalog, deepstore,
                            os.path.join(work_dir, "controller"))
    csvc = ControllerService(controller, port=cfg.get_int("controller.port", 0),
                             access_control=access_control,
                             ssl_context=ssl_ctx)
    controller.start_periodic_tasks()

    from ..query.scheduler import scheduler_from_config
    from .device_server import pipeline_from_config
    server_catalog = RemoteCatalog(csvc.url)
    server = ServerNode("server_0", server_catalog,
                        ControllerDeepStore(csvc.url),
                        os.path.join(work_dir, "server_0"),
                        tags=cfg.get_list("server.tenant.tags") or None,
                        completion=RemoteCompletion(csvc.url),
                        scheduler=scheduler_from_config(cfg),
                        auto_consume=True,
                        device_pipeline=pipeline_from_config(cfg))
    ssvc = ServerService(server, port=cfg.get_int("server.port", 0),
                         access_control=access_control, ssl_context=ssl_ctx)

    broker_catalog = RemoteCatalog(csvc.url)
    broker = Broker("broker_0", broker_catalog,
                    max_scatter_threads=cfg.get_int("broker.scatter.threads", 8))
    bsvc = BrokerService(broker, port=cfg.get_int("broker.port", 0),
                         access_control=access_control, ssl_context=ssl_ctx)

    from ..minion.tasks import MinionWorker
    from .remote import RemoteController, RemoteTaskQueue
    from .services import MinionService
    minion_catalog = RemoteCatalog(csvc.url)
    minion = MinionWorker("minion_0", minion_catalog,
                          ControllerDeepStore(csvc.url),
                          RemoteController(csvc.url,
                                           cfg.get_str("auth.service.token")),
                          os.path.join(work_dir, "minion_0"),
                          queue=RemoteTaskQueue(csvc.url))
    msvc = MinionService(minion, port=cfg.get_int("minion.port", 0),
                         poll_s=cfg.get_float("minion.poll.seconds", 1.0),
                         access_control=access_control, ssl_context=ssl_ctx)
    _write_ready(run_dir, "controller_0", {"url": csvc.url})
    _write_ready(run_dir, "server_0", {"url": ssvc.url})
    _write_ready(run_dir, "broker_0", {"url": bsvc.url})
    _write_ready(run_dir, "minion_0", {"url": msvc.url})
    handles = {"controller": csvc, "server": ssvc, "broker": bsvc,
               "minion": msvc,
               "catalogs": (server_catalog, broker_catalog, minion_catalog),
               "controller_obj": controller, "server_obj": server,
               "minion_obj": minion}
    if block:
        signal.sigwait({signal.SIGTERM, signal.SIGINT})
        # graceful teardown, same order as the per-role processes: server
        # first (consuming handlers flush/stop), then periodic tasks/watchers
        msvc.stop()
        server.shutdown()
        controller.stop_periodic_tasks()
        for c in (server_catalog, broker_catalog, minion_catalog):
            c.close()
        return None
    return handles


def main(argv: Optional[Sequence[str]] = None) -> None:
    p = argparse.ArgumentParser(prog="pinot_tpu.cluster.process")
    p.add_argument("--role", required=True,
                   choices=["controller", "server", "broker", "minion",
                            "service-manager"])
    p.add_argument("--controller-url", default="")
    p.add_argument("--instance-id", default="")
    p.add_argument("--work-dir", default="")
    p.add_argument("--run-dir", required=True)
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--config", default="", help="properties/json config file")
    a = p.parse_args(argv)
    from ..utils.compile_cache import place_compile_cache
    place_compile_cache()
    if a.role == "controller":
        run_controller(a.work_dir, a.run_dir, a.port, config_path=a.config)
    elif a.role == "server":
        run_server(a.controller_url, a.instance_id, a.work_dir, a.run_dir, a.port,
                   config_path=a.config)
    elif a.role == "minion":
        run_minion(a.controller_url, a.instance_id, a.work_dir, a.run_dir,
                   a.port, config_path=a.config)
    elif a.role == "service-manager":
        run_service_manager(a.work_dir, a.run_dir, a.port, config_path=a.config)
    else:
        run_broker(a.controller_url, a.instance_id, a.run_dir, a.port,
                   config_path=a.config)


class ControllerClient:
    """HTTP admin client for a controller (reference: the java-client /
    controller REST API consumers). `token` is per-client: each request carries
    it explicitly, never via process-global state."""

    def __init__(self, url: str, token: Optional[str] = None):
        self.url = url.rstrip("/")
        self.token = token

    def add_schema(self, schema) -> None:
        post_json(f"{self.url}/schemas", schema.to_json(), token=self.token)

    def add_table(self, config, num_partitions: int = 1) -> Dict:
        return post_json(f"{self.url}/tables",
                         {"config": config.to_json(),
                          "numPartitions": num_partitions}, token=self.token)

    def drop_table(self, table: str) -> None:
        http_call("DELETE", f"{self.url}/tables/{table}", token=self.token)

    def upload_segment(self, table: str, segment_dir: str) -> Dict:
        """Tar a built segment dir and push it (reference: segment tar push)."""
        from .deepstore import tar_segment
        name = os.path.basename(segment_dir.rstrip("/"))
        with tempfile.TemporaryDirectory() as tmp:
            tar_path = os.path.join(tmp, f"{name}.tar.gz")
            tar_segment(segment_dir, tar_path)
            with open(tar_path, "rb") as f:
                payload = f.read()
        q = urllib.parse.urlencode({"name": name})
        return json.loads(http_call(
            "POST", f"{self.url}/segments/{table}?{q}", payload,
            content_type="application/octet-stream", timeout=120.0,
            token=self.token).decode())

    def table_status(self, table: str) -> Dict:
        return get_json(f"{self.url}/tableStatus/{table}", token=self.token)

    def get_schema(self, name: str) -> Dict:
        return get_json(f"{self.url}/schemas/{name}", token=self.token)

    def list_tables(self) -> Dict:
        return get_json(f"{self.url}/tables", token=self.token)

    def table_config(self, table: str) -> Dict:
        return get_json(f"{self.url}/tables/{table}", token=self.token)

    def segments_meta(self, table: str) -> Dict:
        return get_json(f"{self.url}/segmentsMeta/{table}", token=self.token)

    def reload_table(self, table: str) -> Dict:
        return post_json(f"{self.url}/reload/{table}", {}, token=self.token)

    def rebalance(self, table: str) -> Dict:
        return post_json(f"{self.url}/rebalance/{table}", {}, token=self.token)


class BrokerClient:
    def __init__(self, url: str, token: Optional[str] = None):
        self.url = url.rstrip("/")
        self.token = token

    def query(self, sql: str, timeout: float = 120.0) -> Dict:
        return post_json(f"{self.url}/query", {"sql": sql}, timeout=timeout,
                         token=self.token)

    def query_stream(self, sql: str, timeout: float = 600.0):
        """Incremental results: yields the columns list first, then row
        batches as the broker streams them (chunked HTTP; reference: the gRPC
        streaming query endpoint). Use for large exports — rows are consumed
        without buffering the full result anywhere."""
        # graftcheck: ignore[transport-bypass] -- line-oriented response
        # streaming (iterates the raw response); the pooled client exposes
        # block reads only, and an export-sized stream amortizes its own
        # connection
        import urllib.request

        from .http_service import client_ssl_context
        req = urllib.request.Request(
            f"{self.url}/queryStream",
            data=json.dumps({"sql": sql}).encode(),
            headers={"Content-Type": "application/json",
                     **({"Authorization": f"Bearer {self.token}"}
                        if self.token else {})})
        with urllib.request.urlopen(req, timeout=timeout,
                                    context=client_ssl_context()) as resp:
            for line in resp:
                if not line.strip():
                    continue
                d = json.loads(line)
                if "error" in d:
                    # a mid-stream failure arrives as the final event (headers
                    # were already 200/chunked by then)
                    raise RuntimeError(f"stream failed: {d['error']}")
                if "columns" in d:
                    yield ("schema", d["columns"])
                else:
                    yield ("rows", d["rows"])


class ProcessCluster:
    """Spawn controller + N servers + broker as OS processes and wait for ready.

    A chip belongs to one process: roles that own none (controller, broker,
    minion, host-engine servers) are started with `JAX_PLATFORMS=cpu`; a server
    with `server.device.enabled=true` (config file, environment or
    `server_env`) owns the chip and inherits the platform untouched.
    """

    def __init__(self, num_servers: int = 2, work_dir: Optional[str] = None,
                 server_env: Optional[Dict[str, str]] = None,
                 startup_timeout_s: float = 60.0, num_minions: int = 0,
                 config_path: str = ""):
        self.work_dir = work_dir or tempfile.mkdtemp(prefix="pinot_tpu_proc_")
        self.run_dir = os.path.join(self.work_dir, "run")
        os.makedirs(self.run_dir, exist_ok=True)
        self.procs: Dict[str, subprocess.Popen] = {}
        self._timeout = startup_timeout_s
        self._config_path = config_path
        if config_path:
            # the config is the single source of truth: apply its tls.* trust
            # to THIS process's clients too, so cluster.query() works against
            # the TLS cluster we are about to start without a separate
            # set_default_tls call
            from ..config import Configuration
            _apply_client_tls(Configuration.load(config_path))

        # one process for each chip: a server with server.device.enabled=true
        # is the process that owns the chip and inherits the platform as this
        # process was given it (so this parent must not have touched JAX on
        # an accelerator). Every other role never needs a device and is held
        # to CPU jax.
        env = dict(os.environ)
        env.update(server_env or {})
        from ..config import Configuration
        device_server = Configuration.load(
            config_path or None, env=env).get_bool("server.device.enabled",
                                                   False)
        self._device_env = env if device_server else None
        self._cpu_env = dict(env, JAX_PLATFORMS="cpu")
        self._cpu_env.pop("XLA_FLAGS", None)

        self._spawn("controller_0", ["--role", "controller",
                                     "--work-dir", self.work_dir])
        self.controller_url = self._await_ready("controller_0")
        for i in range(num_servers):
            sid = f"server_{i}"
            self._spawn(sid, ["--role", "server", "--instance-id", sid,
                              "--controller-url", self.controller_url,
                              "--work-dir", self.work_dir])
        for i in range(num_servers):
            self._await_ready(f"server_{i}")
        self._spawn("broker_0", ["--role", "broker", "--instance-id", "broker_0",
                                 "--controller-url", self.controller_url])
        for i in range(num_minions):
            mid = f"minion_{i}"
            self._spawn(mid, ["--role", "minion", "--instance-id", mid,
                              "--controller-url", self.controller_url,
                              "--work-dir", self.work_dir])
        self.broker_url = self._await_ready("broker_0")
        for i in range(num_minions):
            self._await_ready(f"minion_{i}")
        self.controller = ControllerClient(self.controller_url)
        self.broker = BrokerClient(self.broker_url)

    def _spawn(self, name: str, args: List[str]) -> None:
        cmd = [sys.executable, "-m", "pinot_tpu.cluster.process",
               "--run-dir", self.run_dir] + args
        if self._config_path:
            cmd += ["--config", self._config_path]
        with open(os.path.join(self.run_dir, f"{name}.log"), "wb") as log:
            # the child holds its own dup of the fd; close the parent's copy
            # graftcheck: ignore[unbounded-keyed-accumulation] -- one handle
            # per launched OS process (cluster topology, reaped on stop)
            self.procs[name] = subprocess.Popen(
                cmd, env=self._role_env(args[args.index("--role") + 1]),
                stdout=log, stderr=subprocess.STDOUT)

    def _role_env(self, role: str) -> Dict[str, str]:
        """The chip-owning device server inherits the platform; every other
        role runs CPU jax."""
        if role == "server" and self._device_env is not None:
            return self._device_env
        return self._cpu_env

    def _await_ready(self, name: str) -> str:
        path = os.path.join(self.run_dir, f"{name}.ready")
        deadline = time.time() + self._timeout
        while time.time() < deadline:
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)["url"]
            proc = self.procs.get(name)
            if proc is not None and proc.poll() is not None:
                log = open(os.path.join(self.run_dir, f"{name}.log")).read()
                raise RuntimeError(f"{name} died at startup:\n{log[-4000:]}")
            time.sleep(0.05)
        raise TimeoutError(f"{name} not ready after {self._timeout}s")

    def query(self, sql: str) -> Dict:
        return self.broker.query(sql)

    def kill_server(self, instance_id: str) -> None:
        """SIGKILL a server process — a real process death, not a flag flip."""
        proc = self.procs.get(instance_id)
        if proc is not None:
            proc.kill()
            proc.wait()

    def _restart(self, instance_id: str, role: str) -> str:
        proc = self.procs.get(instance_id)
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        ready = os.path.join(self.run_dir, f"{instance_id}.ready")
        if os.path.exists(ready):
            os.remove(ready)  # _await_ready must see the NEW process's file
        self._spawn(instance_id, ["--role", role,
                                  "--instance-id", instance_id,
                                  "--controller-url", self.controller_url,
                                  "--work-dir", self.work_dir])
        return self._await_ready(instance_id)

    def restart_server(self, instance_id: str) -> str:
        """Start a fresh server process under the same instance id (reference:
        server restart recovery — it re-registers, reloads its assigned
        segments from the deep store, and resumes consuming from the
        checkpointed offsets). Returns the new process's URL."""
        return self._restart(instance_id, "server")

    def restart_minion(self, instance_id: str) -> str:
        """Fresh minion process under the same id (after a kill): it resumes
        claiming from the controller queue; lease gc requeues whatever the
        dead incarnation held."""
        return self._restart(instance_id, "minion")

    def shutdown(self) -> None:
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in self.procs.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    def __enter__(self) -> "ProcessCluster":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


if __name__ == "__main__":
    main()
