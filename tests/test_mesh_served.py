"""The deployment of `ssb10-flat-mesh4` at a small size on the CPU's virtual
devices: one server process whose mesh is four devices, 16 `ssb_flat`
segments placed four a device, driven over broker HTTP through
`run_service_manager` as benchmark/run.py drives it. Every SSB template
answers as benchmark/harness/reference.py does and as the same server does
on a mesh of one, and says what crossed the chips: `meshLaunches`,
`scatterLaunches`, `collectiveBytes` (PR 28)."""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmark.harness import build, cells, reference, serve, traffic
from pinot_tpu.parallel.combine import SCATTER_MIN_KEYS, MeshQueryExecutor
from pinot_tpu.parallel.mesh import default_mesh, pad_slots
from pinot_tpu.query import stats as qstats
from pinot_tpu.query.context import compile_query
from pinot_tpu.segment import load_segment

CELL = "ssb10-flat-mesh4.flights-c4"
SEED = 2800000028
SEGMENT_ROWS = 4096         # a segment's first 2,406 rows walk every key space
TEMPLATES = ("q1.1", "q1.2", "q1.3", "q2.1", "q2.2", "q2.3", "q3.1", "q3.2",
             "q3.3", "q3.4", "q4.1", "q4.2", "q4.3")
# by padded key count: Q1.x has no GROUP BY and Q4.1 175 keys (plain psum);
# the others are at or over combine.SCATTER_MIN_KEYS and divisible by 4
SCATTERS = {t: t not in ("q1.1", "q1.2", "q1.3", "q4.1") for t in TEMPLATES}
MESH_KEYS = ("meshLaunches", "scatterLaunches", "collectiveBytes")
# past combine's chunk cap (131,072 keys) a GROUP BY takes the partitioned
# sort, whose program holds both decodes (PR 29); the filters leave a few rows
WIDE_KEY = ("q3.2", "q3.3", "q3.4", "q4.3")
DECODE_KEYS = ("compactDecodeLaunches", "denseDecodeLaunches")
# ... and both sorts (PR 33): the rows that passed, compacted tile by tile, or all
SORT_KEYS = ("presortCompactLaunches", "fullSortLaunches")


def _serve_and_ask(work, config, mesh_devices, seg_src, pool):
    """The benchmark's own set-up (harness/serve.py) with the configuration's
    `cluster` keys at `mesh_devices`; every query of the pool once, over
    broker HTTP. Returns ({template: response}, /health's device block
    before and after the queries)."""
    from pinot_tpu.cluster.process import BrokerClient
    table = config["table"] + "_OFFLINE"
    seg_out = serve.server_segment_dir(work, table)
    shutil.copytree(seg_src, seg_out)
    handles = serve.start_services(work, dict(
        config["cluster"], **{"server.mesh.devices": str(mesh_devices)}))
    try:
        serve.create_table(handles, config, table)
        for name in sorted(os.listdir(seg_out)):
            handles["controller_obj"].upload_segment(
                table, os.path.join(seg_out, name))
        serve.wait_loaded(handles, config,
                          SEGMENT_ROWS * int(config["segments"]))
        before = serve.pipeline_counters(handles)
        broker = BrokerClient(handles["broker"].url)
        answers = {p["template"]: broker.query(p["sql"]) for p in pool}
        return answers, before, serve.pipeline_counters(handles)
    finally:
        serve.stop_services(handles)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cell = cells.load_cell(CELL)
    config = cell["config"]
    assert config["cluster"]["server.mesh.devices"] == "4"
    n_seg = int(config["segments"])
    assert pad_slots(n_seg, 4) == n_seg            # four a device, no padding
    root = tmp_path_factory.mktemp("mesh_served")
    seg_src = str(root / "segments")
    os.makedirs(seg_src)
    for i in range(n_seg):
        build.build_segment({"config": config, "seed": SEED, "index": i,
                             "rows": SEGMENT_ROWS, "out_dir": seg_src})
    gen = cells.load_generator(config)
    tables = gen.tables(config)
    pool = traffic.build_pool(dict(cell["traffic"], variants_per_template=1),
                              cell["templates"], tables, SEED)
    assert tuple(p["template"] for p in pool) == TEMPLATES
    parts = [[reference.partial(p["spec"],
                                gen.segment(config, SEED, i, SEGMENT_ROWS),
                                tables) for p in pool] for i in range(n_seg)]
    want = {p["template"]: reference.finish(
        p["spec"], reference.merge([seg[q] for seg in parts]), tables)
        for q, p in enumerate(pool)}
    out = {"config": config, "pool": {p["template"]: p for p in pool},
           "want": want, "seg_src": seg_src,
           "limit": float(config["guarantees"]["sum_rel_gap"])}
    for n in (4, 1):
        out[n] = _serve_and_ask(str(root / f"mesh{n}"), config, n, seg_src,
                                pool)
    return out


@pytest.mark.parametrize("template", TEMPLATES)
def test_mesh_of_four_answers_as_the_reference_and_as_a_mesh_of_one(
        served, template):
    spec = served["pool"][template]["spec"]
    by_mesh = {}
    for n in (4, 1):
        resp = served[n][0][template]
        assert not resp.get("exceptions") and not resp.get("partialResult")
        assert resp["numServersResponded"] == resp["numServersQueried"] == 1
        by_mesh[n] = resp["resultTable"]["rows"]
        c = reference.compare(spec, by_mesh[n], served["want"][template],
                              served["limit"])
        assert c["wrong"] == 0 and c["count_wrong"] == 0, (n, c["why"])
        assert c["sum_gap"] <= served["limit"], (n, c["sum_gap"])
    c = reference.compare(spec, by_mesh[4], by_mesh[1], served["limit"])
    assert c["wrong"] == 0 and c["count_wrong"] == 0, c["why"]
    assert c["sum_gap"] <= served["limit"]


@pytest.mark.parametrize("template", TEMPLATES)
def test_answer_says_what_crossed_the_chips(served, template):
    """One launch a query, on four devices; reduce-scattered where the padded
    key count allows it; and nothing of the kind on a mesh of one."""
    four, one = served[4][0][template], served[1][0][template]
    assert four["deviceLaunches"] == one["deviceLaunches"] == 1
    assert four["meshLaunches"] == 1
    assert four["scatterLaunches"] == int(SCATTERS[template])
    assert four["collectiveBytes"] > 0
    assert [one[k] for k in MESH_KEYS] == [0, 0, 0]
    assert four["deviceSkewPct"] == 0.0 and "deviceSkewPct" not in one
    assert "collectiveMs" not in four and "collectiveMs" not in one


@pytest.mark.parametrize("template", TEMPLATES)
def test_wide_key_templates_say_which_decode_ran(served, template):
    """A wide-key template's launch counts in exactly one of the two decode
    counters, on a mesh of four and of one (at 16,384 rows a device the prefix
    is 256 rows, which Q3.2's filter overflows: both branches answer here,
    and `correct` above holds for both); a template that takes no sort regime
    counts in neither."""
    for n in (4, 1):
        resp = served[n][0][template]
        assert sum(resp[k] for k in DECODE_KEYS) == \
            int(template in WIDE_KEY), (n, template)
    took = {t: DECODE_KEYS[served[4][0][t]["denseDecodeLaunches"]]
            for t in WIDE_KEY}
    assert set(took.values()) == set(DECODE_KEYS), took


@pytest.mark.parametrize("template", TEMPLATES)
def test_wide_key_templates_say_which_sort_ran(served, template):
    """A wide-key template's launch counts in exactly one of the two sort
    counters, on a mesh of four and of one, and a launch that sorted the
    compacted rows decoded them compactly (at 16,384 rows a device Q3.2's
    filter passes more rows than the compact decode reads, so it sorts every
    row; the other three fit their tiles' slots: both sorts answer here, and
    `correct` above holds for both); a template that takes no sort regime
    counts in neither. `/health` sums what the answers said."""
    for n in (4, 1):
        resp = served[n][0][template]
        assert sum(resp[k] for k in SORT_KEYS) == \
            int(template in WIDE_KEY), (n, template)
        assert resp["presortCompactLaunches"] <= resp["compactDecodeLaunches"]
    if template == TEMPLATES[0]:
        for n in (4, 1):
            answers, before, after = served[n]
            for k in SORT_KEYS:
                assert after[k] - before[k] == \
                    sum(a[k] for a in answers.values()), (n, k)
            assert sum(after[k] - before[k] for k in SORT_KEYS) \
                == len(WIDE_KEY)
        took = {SORT_KEYS[served[n][0][t]["fullSortLaunches"]]
                for t in WIDE_KEY for n in (4, 1)}
        assert took == set(SORT_KEYS), took


def test_health_sums_what_the_answers_said(served):
    for n in (4, 1):
        answers, before, after = served[n]
        for k in MESH_KEYS + DECODE_KEYS:
            assert after[k] - before[k] == sum(a[k] for a in answers.values())
        for k in ("deviceErrors", "fallbacks", "timeouts"):
            assert after[k] == before[k], k
        assert after["launches"] - before["launches"] == len(answers)
    assert served[4][2]["scatterLaunches"] - served[4][1]["scatterLaunches"] \
        == sum(SCATTERS.values()) == 9
    assert sum(served[4][2][k] - served[4][1][k] for k in DECODE_KEYS) \
        == len(WIDE_KEY)


@pytest.fixture(scope="module")
def segments(served):
    return [load_segment(os.path.join(served["seg_src"], name))
            for name in sorted(os.listdir(served["seg_src"]))]


@pytest.mark.parametrize("template", ("q1.1", "q2.1", "q4.1", "q4.3"))
def test_collective_bytes_are_the_bytes_of_the_output_shapes(
        served, segments, template):
    """What a device hands to its collectives is every per-shard output:
    whole where it is `psum`med (the global result has that shape, replicated),
    without its overflow row where it is reduce-scattered (the global result is
    the key axis, sharded)."""
    mex = MeshQueryExecutor(default_mesh(4))
    ctx = compile_query(served["pool"][template]["sql"], segments[0].schema)
    p = mex.prepare_partial(ctx, segments)
    with qstats.collect_stats() as st:
        outs = mex._get_shard_kernel(p.spec, p.s_pad, p.rows)(p.inputs)
    pad = p.spec.num_keys_pad
    scatters = pad >= SCATTER_MIN_KEYS and pad % 4 == 0
    assert scatters == SCATTERS[template]
    sharded = [k for k, v in outs.items()
               if not v.sharding.is_fully_replicated]
    assert bool(sharded) == scatters
    assert all(outs[k].shape[0] == pad for k in sharded)
    assert st.counters[qstats.MESH_LAUNCHES] == 1
    assert st.counters[qstats.SCATTER_LAUNCHES] == int(scatters)
    assert st.counters[qstats.COLLECTIVE_BYTES] == \
        sum(v.nbytes for v in outs.values()) == \
        served[4][0][template]["collectiveBytes"]


def _lowered(mex, segments, sql):
    p = mex.prepare_partial(compile_query(sql, segments[0].schema), segments)
    kern = mex._get_shard_kernel(p.spec, p.s_pad, p.rows)
    return kern.__wrapped__.jitted_for(p.inputs).lower(p.inputs).as_text(
        debug_info=True)


def test_lowered_four_device_program_names_its_collectives(segments):
    """Each cross-chip combine carries its own scope under the prefix
    `kernels.collective_share` reads; a mesh of one lowers the same program
    (its collectives are over an axis of one) and records nothing."""
    from benchmark.harness.program_trace import SCOPE_PREFIX, scope_of
    sql = ("SELECT {keys}, SUM(lo_revenue), MIN(lo_revenue), "
           "MAX(lo_revenue) FROM lineorder GROUP BY {keys} LIMIT 100000")
    mex4 = MeshQueryExecutor(default_mesh(4))
    # 62,500 city pairs reduce-scatter their sums; 5 regions psum them
    four = _lowered(mex4, segments, sql.format(keys="c_city, s_city"))
    few = _lowered(mex4, segments, sql.format(keys="c_region"))
    for text, kinds, ops in (
            (four, ("scatter", "minmax"), ("reduce_scatter", "all_reduce")),
            (few, ("sum", "minmax"), ("all_reduce",))):
        for kind in ("scatter", "sum", "minmax"):
            assert (f"pinot.collective.{kind}" in text) == (kind in kinds), \
                kind
        assert all(op in text for op in ops)
    assert "reduce_scatter" not in few
    sql = sql.format(keys="c_city, s_city")
    assert "pinot.collective/" not in four
    assert scope_of("jit(pinot_groupby)/jit(shmap_body)/"
                    "pinot.collective.scatter/reduce_scatter:").startswith(
        SCOPE_PREFIX + "collective")
    mex1 = MeshQueryExecutor(default_mesh(1))
    with qstats.collect_stats() as st:
        mex1.execute(segments, sql)
    assert not any(k in st.counters for k in MESH_KEYS)
    assert "pinot.collective.scatter" not in _lowered(mex1, segments, sql)


def test_served_topk_on_four_devices_counts_as_a_mesh_launch(segments):
    """The flights mix has no selection, so the cell bypasses the sharded
    top-k; its launch still ran on four devices and says so."""
    mex = MeshQueryExecutor(default_mesh(4))
    ctx = compile_query("SELECT lo_revenue, c_city FROM lineorder "
                        "ORDER BY lo_revenue DESC LIMIT 5",
                        segments[0].schema)
    p = mex.prepare_partial(ctx, segments)
    assert p is not None and p.kind == "topk"
    (outs, finish, _, recorded), = mex.dispatch_prepared([p])
    assert recorded[qstats.MESH_LAUNCHES] == 1
    assert qstats.SCATTER_LAUNCHES not in recorded
    part = p.decode(finish(jax.device_get(outs))[0])
    top = max(np.asarray(s.column("lo_revenue").values()).max()
              for s in segments)
    assert max(r[0] for r in part.rows) == top


def test_pipeline_spans_carry_the_mesh_width():
    """`pinot:pipeline.launch` and `.fetch` say how many chips one launch
    enqueues on and one fetch reads; an executor without a mesh (the tests'
    fakes) reads 1."""
    from pinot_tpu.cluster.device_server import DeviceQueryPipeline
    four = DeviceQueryPipeline(MeshQueryExecutor(default_mesh(4)),
                               start=False)
    assert four.devices == 4
    assert DeviceQueryPipeline(object(), start=False).devices == 1
    assert {k: 0 for k in MESH_KEYS}.items() <= four.stats().items()


def test_configuration_is_ssb10_flat_on_four_chips():
    """The file states the deployment: `ssb10-flat`'s table, generator, schema
    and guarantees; four chips, one server, `server.mesh.devices` 4."""
    flat = cells.read_json(cells.BENCH, "configs", "ssb10-flat.json")
    mesh = cells.read_json(cells.BENCH, "configs", "ssb10-flat-mesh4.json")
    differ = {k for k in set(flat) | set(mesh) if flat.get(k) != mesh.get(k)}
    assert differ == {"name", "source", "deployment", "chips", "cluster",
                      "reduced"}
    assert mesh["chips"] == 4 and mesh["servers"] == 1
    assert mesh["cluster"] == dict(flat["cluster"],
                                   **{"server.mesh.devices": "4"})
    assert set(mesh["reduced"]) == set(flat["reduced"]) | {"hbm_fill"}
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == mesh["name"]]
    assert entry["source"] == mesh["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(mesh["reduced"])
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "flights-c4"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    json.dumps(mesh)

