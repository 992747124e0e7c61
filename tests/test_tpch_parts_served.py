"""The deployment of `tpch10-parts` at TPC-H SF1's widths: LINEITEM's six
columns the Top-N queries of the Druid TPC-H benchmark read, as the
benchmark's generator draws them (200,000 part keys over 4 x 262,144 rows, so
the merged part-key space passes `chunk_cap` and the sort regime runs with
its default caps), the three templates served through `run_service_manager`
over broker HTTP as benchmark/run.py drives them, against
`benchmark/harness/reference.py`. The one server the broker routes to cuts
the part-key tables to their LIMIT on its device and takes the details
template's MIN and MAX from its sorted rows; the commit-date table (2,466
keys, the chunk64 rung) is fetched whole."""

import os
import shutil

import numpy as np
import pytest

from benchmark.harness import build, cells, readers, reference, serve, traffic
from pinot_tpu.engine.caps import KernelCaps, get_caps
from pinot_tpu.query import stats as qstats

CELL = "tpch10-parts.druid-topn-c4"
SEED = 4300000043
SEGMENTS = 4
SEGMENT_ROWS = 1 << 18
PARTS = 200_000                 # SF1: 200,000 x SF part keys
VARIANTS = 2
NEW_METRICS = ("kernels.dense_trimmed_share", "kernels.sorted_minmax_share",
               "kernels.topn_hbm_roofline")
TEMPLATES = ("top_100_parts", "top_100_parts_details", "top_100_commitdate")


@pytest.fixture(scope="module")
def cell():
    c = cells.load_cell(CELL)
    return dict(c, config=dict(c["config"], segments=SEGMENTS, parts=PARTS))


@pytest.fixture(scope="module")
def gen(cell):
    return cells.load_generator(cell["config"])


@pytest.fixture(scope="module")
def tables(cell, gen):
    return gen.tables(cell["config"])


# -- (a) the configuration, the queries and the cell ------------------------------

def test_configuration_holds_the_published_numbers(cell):
    ours = cells.read_json(cells.BENCH, "configs", "tpch10-parts.json")
    lineitem = cells.read_json(cells.BENCH, "configs", "tpch10-lineitem.json")
    assert (ours["rows"], ours["segments"], ours["chips"]) == (67108864, 16, 1)
    assert ours["parts"] == 2_000_000
    assert "dbgen -s 10" in ours["source"] and "top_100_parts" in \
        ours["source"]
    for k in ("cluster", "deployment", "servers", "replication", "table",
              "no_dictionary_columns"):
        assert ours[k] == lineitem[k], k
    assert ours["guarantees"]["sum_rel_gap"] == 2e-05
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == ours["name"]]
    assert entry["source"] == ours["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/tpch10-parts.json"
    assert sorted(entry["reduced"]) == sorted(ours["reduced"]) == [
        "l_unread_columns", "server.device.stacking.enabled"]
    found, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert found["chips"] == 1 and found["traffic"] == "druid-topn-c4"
    assert len(found["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    mix = cell["traffic"]
    assert (mix["clients"], mix["queue"], mix["variants_per_template"],
            mix["timeout_s"]) == (4, "shared", 4, 120)
    assert mix["templates"] == [f"tpch/{t}" for t in TEMPLATES]


@pytest.mark.parametrize("name", TEMPLATES)
def test_template_is_the_sources_query_over_a_window(cell, tables, name):
    t, = [t for t in cell["templates"] if t["name"] == name]
    starts = t["holes"][0]["choice"]
    assert len(starts) == 52 and starts[0] == 19920301 \
        and starts[-1] == 19960601
    assert all(s % 100 == 1 for s in starts)
    spec = reference.bind(t["reference"], {"a": 19940301, "b": 19960301})
    key = "l_commitdate" if name.endswith("commitdate") else "l_partkey"
    assert t["sql"].endswith(f"GROUP BY {key} ORDER BY SUM(l_quantity) DESC, "
                             f"{key} LIMIT 100")
    assert spec["group_by"] == [key] and spec["limit"] == 100
    assert spec["order_by"][0] == ["units", "desc"]
    # a row's least bytes: part 4 or commit day 2, ship day 2, quantity 1,
    # and for the details price 4, discount 1
    want = {"top_100_parts": 7, "top_100_parts_details": 12,
            "top_100_commitdate": 5}[name]
    assert readers.least_bytes(spec, cell["config"]) == \
        want * cell["config"]["rows"]
    pool = traffic.build_pool(cell["traffic"], [t], tables, 9)
    assert len({p["sql"] for p in pool}) == 4
    assert all(p["spec"]["filters"][1]["args"][0]
               == p["spec"]["filters"][0]["args"][0] + 20000 for p in pool)


@pytest.mark.parametrize("keys,runs", [
    (qstats.COUNTER_KEYS, True),
    (tuple(k for k in qstats.COUNTER_KEYS
           if k != qstats.DENSE_TRIMMED_LAUNCHES), False),     # the parent
    (None, False)])
def test_generator_fails_cleanly_on_a_program_without_the_cut(
        cell, gen, monkeypatch, keys, runs):
    if keys is None:
        monkeypatch.delattr(qstats, "COUNTER_KEYS")
    else:
        monkeypatch.setattr(qstats, "COUNTER_KEYS", keys)
    if runs:
        assert gen.tables(cell["config"])
        return
    with pytest.raises(SystemExit) as exit_:
        gen.tables(cell["config"])
    assert exit_.value.code not in (0, None)
    assert "denseTrimmedLaunches" in str(exit_.value.code)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_for_the_new_cell_alone(name):
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    meta = cells.read_json(cells.BENCH, "metrics", name + ".json")
    for k in ("layer", "unit", "better", "source", "moves"):
        assert meta[k] == entry[k], k
    owed = {m["name"] for m in cells.load_cell(CELL)["per_layer"]}
    assert name in owed


# -- (b) served ----------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory, cell, gen, tables):
    """The benchmark's own set-up at SF1's widths with the program's default
    caps; every query of the pool over broker HTTP, one at a time, with
    /health's device block before and after each."""
    from pinot_tpu.cluster.process import BrokerClient
    assert get_caps() == KernelCaps()
    config = cell["config"]
    root = tmp_path_factory.mktemp("tpch_parts_served")
    work = str(root / "work")
    table = config["table"] + "_OFFLINE"
    seg_out = serve.server_segment_dir(work, table)
    os.makedirs(seg_out)
    for i in range(SEGMENTS):
        build.build_segment({"config": config, "seed": SEED, "index": i,
                             "rows": SEGMENT_ROWS, "out_dir": seg_out})
    pool = traffic.build_pool(dict(cell["traffic"],
                                   variants_per_template=VARIANTS),
                              cell["templates"], tables, SEED)
    segs = [gen.segment(config, SEED, i, SEGMENT_ROWS)
            for i in range(SEGMENTS)]
    want = [reference.finish(p["spec"], reference.merge(
        [reference.partial(p["spec"], s, tables) for s in segs]), tables)
        for p in pool]
    handles = serve.start_services(work, config["cluster"])
    try:
        serve.create_table(handles, config, table)
        for name in sorted(os.listdir(seg_out)):
            handles["controller_obj"].upload_segment(
                table, os.path.join(seg_out, name))
        serve.wait_loaded(handles, config, SEGMENT_ROWS * SEGMENTS)
        broker = BrokerClient(handles["broker"].url)
        answers = [(None, serve.pipeline_counters(handles))]
        for p in pool:
            answers.append((broker.query(p["sql"]),
                            serve.pipeline_counters(handles)))
    finally:
        serve.stop_services(handles)
        shutil.rmtree(work, ignore_errors=True)
    return {"pool": pool, "want": want, "answers": answers, "segs": segs,
            "limit": float(config["guarantees"]["sum_rel_gap"])}


@pytest.mark.parametrize("q", range(len(TEMPLATES) * VARIANTS))
def test_topn_answers_as_the_reference_from_the_device(served, q):
    p, want = served["pool"][q], served["want"][q]
    resp = served["answers"][1 + q][0]
    assert not resp.get("exceptions") and not resp.get("partialResult")
    assert resp["numServersResponded"] == resp["numServersQueried"] == 1
    rows = resp["resultTable"]["rows"]
    c = reference.compare(p["spec"], rows, want, served["limit"])
    assert c["wrong"] == 0 and c["count_wrong"] == 0, c["why"]
    assert c["sum_gap"] <= served["limit"] / 10, c["sum_gap"]
    assert len(rows) == len(want) == 100
    # sums of quantity are whole numbers and come back exact
    assert all(float(r[1]) == w[1] for r, w in zip(
        sorted(rows), sorted(want)))
    # the part keys' dictionaries differ by segment: a merged id space
    parts = p["template"] != "top_100_commitdate"
    assert resp["deviceLaunches"] == 1
    assert resp["mergedLaunches"] == int(parts)
    assert resp["denseTrimmedLaunches"] == int(parts)
    assert resp["sortedMinMaxLaunches"] == int(
        p["template"] == "top_100_parts_details")
    assert resp["sparseGroupByLaunches"] == resp["deviceTrimmedLaunches"] == 0
    if parts:       # 100 groups fetched, not a table of 200,704 keys
        assert 0 < resp["bytesFetched"] < 64 * 1024
    else:
        assert resp["bytesFetched"] >= 2466 * 8


def test_health_sums_the_new_counters(served):
    start, end = served["answers"][0][1], served["answers"][-1][1]
    d = {k: end[k] - start[k] for k in ("denseTrimmedLaunches",
                                        "sortedMinMaxLaunches", "launches")}
    assert d == {"denseTrimmedLaunches": 2 * VARIANTS,
                 "sortedMinMaxLaunches": VARIANTS,
                 "launches": 3 * VARIANTS}
    for k in ("deviceErrors", "fallbacks", "timeouts"):
        assert end[k] == start[k], k


def _delta(served):
    start, end = served["answers"][0][1], served["answers"][-1][1]
    return {k: end[k] - start[k] for k in start
            if isinstance(start[k], (int, float))}


@pytest.mark.parametrize("name,want", [
    ("kernels.dense_trimmed_share", 100.0 * 2 / 3),
    ("kernels.sorted_minmax_share", 100.0 / 3)])
def test_share_reads_the_served_counters(served, name, want):
    read = cells.load_reader(name)
    delta = _delta(served)
    assert read({"counters": delta}) == pytest.approx(want)
    assert read({"counters": {"launches": 4}}) is None       # the parent
    assert read({"counters": dict(delta, launches=0)}) is None


def test_topn_hbm_roofline_reads_the_details_solo_replay():
    read = cells.load_reader("kernels.topn_hbm_roofline")
    peaks = cells.peaks("TPU v5 lite")
    rows = 67108864
    solo = [{"template": "top_100_parts_details", "least_bytes": 12 * rows,
             "busy_s": 0.250}]
    assert read({"solo": solo, "peaks": peaks}) == pytest.approx(
        100 * 12 * rows / peaks["hbm_bytes_per_s"] / 0.250)
    assert read({"solo": [dict(solo[0], template="top_100_parts")],
                 "peaks": peaks}) is None
    assert read({"solo": [dict(solo[0], busy_s=0.0)], "peaks": peaks}) is None
    assert read({"solo": solo, "peaks": None}) is None
