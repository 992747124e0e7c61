"""CLI admin tool, segment tools, and controller admin REST tests.

Reference pattern: pinot-admin command tests (AddTable/UploadSegment/PostQuery),
SegmentDumpTool, ValidateSegment.
"""

import json

import numpy as np
import pytest

from pinot_tpu.schema import DataType, FieldSpec, FieldRole, Schema, dimension, metric
from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig
from pinot_tpu.tools.admin import main as admin_main
from pinot_tpu.tools.segment import dump_segment, verify_segment

SCHEMA = Schema("trips", [
    dimension("city", DataType.STRING),
    FieldSpec("tags", DataType.STRING, FieldRole.DIMENSION, single_value=False),
    metric("fare", DataType.DOUBLE),
])


@pytest.fixture(scope="module")
def seg_dir(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tools")
    return SegmentBuilder(SCHEMA, SegmentGeneratorConfig(
        inverted_index_columns=["city"])).build(
        {"city": ["nyc", "sf", "nyc"], "tags": [["a"], ["a", "b"], None],
         "fare": np.array([10.0, 20.0, 30.0])}, str(tmp), "trips_0")


# -- segment tools -------------------------------------------------------------

def test_dump_segment(seg_dir):
    d = dump_segment(seg_dir, max_rows=2)
    assert d["segmentName"] == "trips_0"
    assert d["totalDocs"] == 3
    assert d["columns"]["city"]["indexes"] == ["inverted"]
    assert d["columns"]["tags"]["multiValue"] is True
    assert d["columns"]["fare"]["minValue"] == 10.0
    assert len(d["sampleRows"]) == 2
    assert d["sampleRows"][0][0] == "nyc"
    json.dumps(d)  # fully JSON-serializable


def test_verify_segment_clean(seg_dir):
    report = verify_segment(seg_dir)
    assert report["ok"], report
    names = [c["name"] for c in report["checks"]]
    assert "crc" in names and "column:tags" in names


def test_verify_segment_detects_corruption(tmp_path):
    seg = SegmentBuilder(SCHEMA).build(
        {"city": ["a"], "tags": [["t"]], "fare": np.array([1.0])},
        str(tmp_path), "bad_0")
    # flip bytes in a column file -> crc must fail
    import glob
    import os
    victim = sorted(glob.glob(os.path.join(seg, "cols", "fare*")))[0]
    data = bytearray(open(victim, "rb").read())
    data[-1] ^= 0xFF
    open(victim, "wb").write(bytes(data))
    report = verify_segment(seg)
    assert not report["ok"]
    assert any(c["name"] == "crc" and not c["ok"] for c in report["checks"])


# -- CLI ----------------------------------------------------------------------

def test_cli_dump_and_verify(seg_dir, capsys):
    assert admin_main(["dump-segment", "--dir", seg_dir]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["segmentName"] == "trips_0"
    assert admin_main(["verify-segment", "--dir", seg_dir]) == 0


def test_cli_build_segment(tmp_path, capsys):
    schema_file = tmp_path / "schema.json"
    schema_file.write_text(json.dumps(SCHEMA.to_json()))
    rows_file = tmp_path / "rows.jsonl"
    rows_file.write_text('{"city": "la", "tags": ["x"], "fare": 5.5}\n'
                         '{"city": "sd", "tags": ["y"], "fare": 6.5}\n')
    rc = admin_main(["build-segment", "--schema", str(schema_file),
                     "--input", str(rows_file), "--out", str(tmp_path / "segs"),
                     "--name", "built_0"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["rows"] == 2
    from pinot_tpu.segment.reader import load_segment
    seg = load_segment(out["segmentDir"])
    assert seg.num_docs == 2


def test_cli_against_http_cluster(tmp_path, capsys):
    """Schema/table/segment/query round-trip through the CLI against real HTTP
    services (the pinot-admin quickstart path)."""
    from pinot_tpu.cluster.broker import Broker
    from pinot_tpu.cluster.catalog import Catalog
    from pinot_tpu.cluster.controller import Controller
    from pinot_tpu.cluster.deepstore import LocalDeepStore
    from pinot_tpu.cluster.remote import (ControllerDeepStore, RemoteCatalog,
                                          RemoteServerHandle)
    from pinot_tpu.cluster.server import ServerNode
    from pinot_tpu.cluster.services import (BrokerService, ControllerService,
                                            ServerService)
    from pinot_tpu.table import TableConfig

    catalog = Catalog()
    ctrl = Controller("c0", catalog, LocalDeepStore(str(tmp_path / "ds")),
                      str(tmp_path / "c"))
    csvc = ControllerService(ctrl)
    rc_cat = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
    node = ServerNode("server_0", rc_cat, ControllerDeepStore(csvc.url),
                      str(tmp_path / "s0"))
    ssvc = ServerService(node)
    brc = RemoteCatalog(csvc.url, poll_timeout_s=1.0)
    broker = Broker("b0", brc)
    bsvc = BrokerService(broker)
    try:
        schema_file = tmp_path / "schema.json"
        schema_file.write_text(json.dumps(SCHEMA.to_json()))
        table_file = tmp_path / "table.json"
        table_file.write_text(json.dumps(TableConfig("trips").to_json()))
        assert admin_main(["add-schema", "--controller", csvc.url,
                           "--file", str(schema_file)]) == 0
        assert admin_main(["add-table", "--controller", csvc.url,
                           "--file", str(table_file)]) == 0
        capsys.readouterr()
        assert admin_main(["list-tables", "--controller", csvc.url]) == 0
        assert "trips_OFFLINE" in json.loads(capsys.readouterr().out)["tables"]

        seg = SegmentBuilder(SCHEMA).build(
            {"city": ["nyc", "sf"], "tags": [["a"], ["b"]],
             "fare": np.array([1.0, 2.0])}, str(tmp_path / "b"), "trips_0")
        assert admin_main(["upload-segment", "--controller", csvc.url,
                           "--table", "trips_OFFLINE", "--dir", seg]) == 0
        # retry until the broker's catalog mirror + routing converge (segment
        # load and broker snapshot polls race the first query)
        import time
        deadline = time.time() + 20
        rows = None
        while time.time() < deadline:
            capsys.readouterr()
            try:
                rc = admin_main(["query", "--broker", bsvc.url, "--json",
                                 "--sql", "SELECT SUM(fare) FROM trips"])
            except Exception:  # broker mirror not converged: 500 -> retry
                time.sleep(0.2)
                continue
            if rc == 0:
                rows = json.loads(capsys.readouterr().out)["resultTable"]["rows"]
                if rows and rows[0][0] == 3.0:
                    break
            time.sleep(0.2)
        assert rows and rows[0][0] == 3.0, f"no converged result: {rows}"

        assert admin_main(["table-status", "--controller", csvc.url,
                           "--table", "trips_OFFLINE"]) == 0
        status = json.loads(capsys.readouterr().out)
        assert status["converged"] is True

        # admin read APIs
        from pinot_tpu.cluster.http_service import get_json
        metas = get_json(f"{csvc.url}/segmentsMeta/trips_OFFLINE")["segments"]
        assert "trips_0" in metas
        cfg = get_json(f"{csvc.url}/tables/trips_OFFLINE")["config"]
        assert cfg["tableName"] == "trips" or "trips" in json.dumps(cfg)
        schema_json = get_json(f"{csvc.url}/schemas/trips")
        assert schema_json["schemaName"] == "trips"
    finally:
        rc_cat.close()
        brc.close()
        for s in (csvc, ssvc, bsvc):
            s.stop()


# -- compile cache placement ----------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "/operator/chose/this"])
def test_place_compile_cache(monkeypatch, env_dir):
    """Env set -> code sets no directory (JAX reads the variable itself); env
    unset -> the fixed in-checkout path, the same on every call."""
    import os

    import jax

    import pinot_tpu
    from pinot_tpu.utils.compile_cache import place_compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    first, second = place_compile_cache(), place_compile_cache()
    assert first == second
    dirs = [v for k, v in updates if k == "jax_compilation_cache_dir"]
    if env_dir is None:
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(pinot_tpu.__file__)))
        assert first == os.path.join(checkout, ".jax_cache")
        assert dirs == [first, first]
    else:
        assert first == env_dir and dirs == []
    # small scan kernels must be written too
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in updates
