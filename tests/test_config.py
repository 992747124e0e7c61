"""Config system, plugin manager, and service lifecycle tests.

Reference patterns: PinotConfiguration precedence tests (pinot-spi env),
PluginManager registration, ServiceStatus/readiness gating
(BaseServerStarter.startupServiceStatusCheck).
"""

import sys

import numpy as np
import pytest

from pinot_tpu import plugins
from pinot_tpu.config import Configuration, read_config_file


# -- configuration layering ----------------------------------------------------

def test_precedence_defaults_file_env_overrides(tmp_path):
    f = tmp_path / "server.properties"
    f.write_text("# comment\nserver.port=9000\nserver.tenant.tags=a,b\n"
                 "query.timeout.ms=5000\n")
    cfg = Configuration.load(
        str(f),
        defaults={"server.port": 8000, "only.default": "d"},
        env={"PINOT_TPU_QUERY_TIMEOUT_MS": "7000", "UNRELATED": "x"},
        overrides={"server.tenant.tags": "c"},
    )
    assert cfg.get_int("server.port") == 9000          # file beats default
    assert cfg.get_int("query.timeout.ms") == 7000     # env beats file
    assert cfg.get_list("server.tenant.tags") == ["c"]  # override beats file
    assert cfg.get("only.default") == "d"
    assert "unrelated" not in cfg


def test_json_config_flattens(tmp_path):
    f = tmp_path / "cfg.json"
    f.write_text('{"server": {"scheduler": {"enabled": true, "max": {"concurrent": 8}}}}')
    cfg = Configuration.load(str(f))
    assert cfg.get_bool("server.scheduler.enabled") is True
    assert cfg.get_int("server.scheduler.max.concurrent") == 8


def test_typed_getters_and_subset():
    cfg = Configuration({"a.x": "10", "a.y": "true", "a.z": "1.5",
                         "a.list": "p, q ,r", "b.k": "v"})
    sub = cfg.subset("a")
    assert sub.get_int("x") == 10
    assert sub.get_bool("y") is True
    assert sub.get_float("z") == 1.5
    assert sub.get_list("list") == ["p", "q", "r"]
    assert "k" not in sub
    assert cfg.get_bool("missing", True) is True
    assert cfg.get_int("missing", 3) == 3


def test_properties_parse_errors(tmp_path):
    f = tmp_path / "bad.properties"
    f.write_text("no_equals_sign_here\n")
    with pytest.raises(ValueError):
        read_config_file(str(f))


def test_scheduler_from_config():
    from pinot_tpu.query.scheduler import scheduler_from_config
    assert scheduler_from_config(Configuration({})) is None
    s = scheduler_from_config(Configuration({
        "server.scheduler.enabled": "true",
        "server.scheduler.max.concurrent": "2",
        "server.scheduler.max.pending": "5",
    }))
    assert s is not None and s.max_concurrent == 2 and s.max_pending == 5
    s.stop()


# -- plugin manager ------------------------------------------------------------

def test_plugin_inventory_covers_builtins():
    inv = plugins.inventory()
    assert "memory" in inv[plugins.STREAM]
    assert "kafkalite" in inv[plugins.STREAM]   # lazily imported builtin
    assert "json" in inv[plugins.DECODER]
    assert "csv" in inv[plugins.READER]
    assert "local" in inv[plugins.FS]


def test_plugin_get_and_errors():
    factory = plugins.get(plugins.STREAM, "memory")
    assert callable(factory)
    with pytest.raises(KeyError, match="no stream plugin"):
        plugins.get(plugins.STREAM, "nope")
    with pytest.raises(KeyError, match="unknown plugin kind"):
        plugins.get("bogus", "x")


def test_plugin_module_loading(tmp_path):
    """An external module registers its plugin at import (the reference's
    plugin-dir classloading analog)."""
    mod = tmp_path / "my_decoder_plugin.py"
    mod.write_text(
        "from pinot_tpu.ingest.stream import register_decoder\n"
        "register_decoder('upper_json', lambda b: {'v': b.decode().upper()})\n")
    sys.path.insert(0, str(tmp_path))
    try:
        cfg = Configuration({"plugins.modules": "my_decoder_plugin"})
        assert plugins.load_from_config(cfg) == ["my_decoder_plugin"]
        assert "upper_json" in plugins.available(plugins.DECODER)
    finally:
        sys.path.remove(str(tmp_path))


# -- service lifecycle ---------------------------------------------------------

def test_server_lifecycle_and_readiness(tmp_path):
    from pinot_tpu.cluster import QuickCluster
    from pinot_tpu.cluster.services import ServerService
    from pinot_tpu.cluster.http_service import HttpError, get_json, http_call
    from pinot_tpu.schema import DataType, Schema, dimension, metric
    from pinot_tpu.table import TableConfig

    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    schema = Schema("t", [dimension("s"), metric("v", DataType.DOUBLE)])
    cfg = cluster.create_table(schema, TableConfig("t"))
    cluster.ingest_columns(cfg, {"s": ["a"], "v": np.array([1.0])})
    node = cluster.servers[0]
    st = node.startup_status()
    assert st == {"status": "UP", "assignedSegments": 1, "loadedSegments": 1,
                  "ready": True}

    svc = ServerService(node)
    try:
        health = get_json(f"{svc.url}/health")
        assert health["ready"] is True and health["status"] == "UP"
        assert get_json(f"{svc.url}/health/readiness")["ready"] is True
        # a not-yet-started server answers 503 to READINESS probes, while the
        # bare liveness probe stays 200 (the process is up, just not ready)
        node.status = "STARTING"
        with pytest.raises(HttpError) as ei:
            http_call("GET", f"{svc.url}/health/readiness")
        assert ei.value.status == 503
        assert get_json(f"{svc.url}/health")["status"] == "STARTING"
        node.status = "UP"
    finally:
        svc.stop()

    # graceful shutdown flips liveness + state
    node.shutdown()
    assert node.status == "SHUTTING_DOWN"
    assert cluster.catalog.instances[node.instance_id].alive is False


def test_every_env_variable_is_documented():
    """The `PINOT_TPU_*` names in the program and in README.md are the same
    set: a variable is neither read undocumented nor documented unread."""
    import os
    import re
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = re.compile(r"PINOT_TPU_[A-Z0-9_]*[A-Z0-9]")
    read = set()
    for d, _, files in os.walk(os.path.join(root, "pinot_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    read |= set(name.findall(fh.read()))
    with open(os.path.join(root, "README.md")) as fh:
        documented = set(name.findall(fh.read()))
    assert read == documented, (sorted(read - documented),
                                sorted(documented - read))
