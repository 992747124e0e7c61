"""The deployment of `tpch10-lineitem` at a small size (PR 36): TPC-H's
LINEITEM as the benchmark's generator draws it, Q1 and Q6 served through
`run_service_manager` over broker HTTP as benchmark/run.py drives them. Q1's
charge, `l_extendedprice * (100 - l_discount) * (100 + l_tax)`, is up to
1.1e11 a row: the program widens INT arithmetic that leaves int32 (the parent
wrapped: 5,000 rows read -5.66e10 for 1.55e14), says so in
`widenedAggLaunches`, and answers as benchmark/harness/reference.py does."""

import os
import shutil

import numpy as np
import pytest

from benchmark.harness import build, cells, readers, reference, serve, traffic
from pinot_tpu.query import stats as qstats

CELL = "tpch10-lineitem.tpch-q1q6-c4"
SEED = 3600000036
SEGMENTS = 4
SEGMENT_ROWS = 4096
TEMPLATES = ("q1", "q6")
VARIANTS = 2
CUTOFF = 19950617
GROUPS = {("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")}
NEW_METRICS = ("kernels.q1_hbm_roofline", "kernels.widened_agg_share",
               "kernels.masked_groupby_share")           # the last: PR 37


@pytest.fixture(scope="module")
def cell():
    c = cells.load_cell(CELL)
    return dict(c, config=dict(c["config"], segments=SEGMENTS))


@pytest.fixture(scope="module")
def gen(cell):
    return cells.load_generator(cell["config"])


# -- (a) the generator ---------------------------------------------------------

@pytest.mark.parametrize("i", range(SEGMENTS))
def test_segment_follows_dbgens_rules(cell, gen, i):
    config = cell["config"]
    tables = gen.tables(config)
    cols = gen.segment(config, SEED, i, SEGMENT_ROWS)
    assert sorted(cols) == sorted(c["name"] for c in config["schema"])
    ship = tables["l_shipdate"][cols["l_shipdate"]]
    flag = tables["l_returnflag"][cols["l_returnflag"]]
    status = tables["l_linestatus"][cols["l_linestatus"]]
    # the status from the ship date; the flag from the receipt date, which is
    # 1 to 30 days after it: past the cutoff every line is N, a month and more
    # before it none is
    assert np.array_equal(status == "O", ship > CUTOFF)
    assert (flag[ship > CUTOFF] == "N").all()
    early = flag[ship <= 19950517]
    assert not (early == "N").any()
    assert 0.4 < (early == "R").mean() < 0.6
    assert set(zip(flag.tolist(), status.tolist())) == GROUPS
    # extendedprice = quantity x retail price, in cents
    qty = tables["l_quantity"][cols["l_quantity"]]
    price = cols["l_extendedprice"]
    assert (price % qty == 0).all()
    retail = price // qty
    assert retail.min() >= 90_000 and retail.max() <= 209_900
    assert price.max() <= 10_494_950 and price.dtype == np.int32
    # every dictionary value in every segment: the opening rows walk them
    for col, table in tables.items():
        assert len(np.unique(cols[col])) == len(table), col
    assert gen.segment(config, SEED, i, SEGMENT_ROWS)["l_extendedprice"] \
        .tobytes() == price.tobytes()                 # the seed decides
    assert gen.segment(config, SEED + 1, i, SEGMENT_ROWS)["l_extendedprice"] \
        .tobytes() != price.tobytes()


def test_retail_price_is_dbgens(gen):
    partkey = np.array([1, 10, 1000, 200_009, 2_000_000])
    assert gen.retail_price(partkey).tolist() == [
        90_100, 91_001, 90_100, 110_900, 109_991]


def test_tables_are_the_columns_domains(cell, gen):
    tables = gen.tables(cell["config"])
    by_name = {c["name"]: c for c in cell["config"]["schema"]}
    assert tables["l_returnflag"].tolist() == ["A", "N", "R"]
    assert tables["l_linestatus"].tolist() == ["F", "O"]
    days = tables["l_shipdate"]
    assert (len(days), days[0], days[-1]) == (2526, 19920102, 19981201)
    for col, table in tables.items():
        assert len(table) == by_name[col]["cardinality"], col
        if "min" in by_name[col]:
            assert (table[0], table[-1]) == (by_name[col]["min"],
                                             by_name[col]["max"]), col
    assert "l_extendedprice" not in tables


@pytest.mark.parametrize("keys,runs", [
    (qstats.COUNTER_KEYS, True),
    (tuple(k for k in qstats.COUNTER_KEYS
           if k != qstats.WIDENED_AGG_LAUNCHES), False),  # the parent: it wraps
    (None, False)])
def test_generator_fails_cleanly_on_a_program_that_wraps(cell, gen,
                                                         monkeypatch, keys,
                                                         runs):
    if keys is None:
        monkeypatch.delattr(qstats, "COUNTER_KEYS")
    else:
        monkeypatch.setattr(qstats, "COUNTER_KEYS", keys)
    if runs:
        assert gen.tables(cell["config"])
        return
    with pytest.raises(SystemExit) as exit_:
        gen.tables(cell["config"])
    assert exit_.value.code not in (0, None)      # an exit code other than 0
    assert "widenedAggLaunches" in str(exit_.value.code)


# -- (b) the configuration and the cell ----------------------------------------

def test_configuration_holds_the_published_numbers(cell):
    ours = cells.read_json(cells.BENCH, "configs", "tpch10-lineitem.json")
    flat = cells.read_json(cells.BENCH, "configs", "ssb10-flat.json")
    assert (ours["rows"], ours["segments"], ours["chips"]) == (67108864, 16, 1)
    assert ours["rows"] // ours["segments"] == 4194304
    assert "59,986,052" in ours["source"] and "dbgen -s 10" in ours["source"]
    assert ours["cluster"] == flat["cluster"]
    assert ours["deployment"] == flat["deployment"]
    for k in ("complete", "keys_and_order", "counts", "sum_rel_gap"):
        assert ours["guarantees"][k] == flat["guarantees"][k], k
    assert ours["guarantees"]["sum_rel_gap"] == 2e-05
    assert "AVG" in ours["guarantees"]["sums"]
    by_name = {c["name"]: c for c in ours["schema"]}
    assert (by_name["l_extendedprice"]["min"],
            by_name["l_extendedprice"]["max"]) == (90000, 10494950)
    assert ours["no_dictionary_columns"] == ["l_extendedprice"]
    assert {c: by_name[c]["max"] for c in ("l_quantity", "l_discount",
                                           "l_tax")} \
        == {"l_quantity": 50, "l_discount": 10, "l_tax": 8}
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == ours["name"]]
    assert entry["source"] == ours["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(ours["reduced"]) \
        == ["l_unread_columns", "server.device.stacking.enabled"]
    found, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert found["chips"] == 1 and found["traffic"] == "tpch-q1q6-c4"
    assert len(found["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    mix = cell["traffic"]
    assert (mix["clients"], mix["queue"], mix["variants_per_template"]) \
        == (4, "shared", 8)
    assert mix["templates"] == ["tpch/q1", "tpch/q6"]
    # Q1 reads all seven columns: 11 bytes a row at their narrowest
    q1, = [t for t in cell["templates"] if t["name"] == "q1"]
    spec = reference.bind(q1["reference"], {"d": 19980902})
    assert readers.least_bytes(spec, ours) == 11 * ours["rows"]
    assert "." not in q1["sql"]        # whole-number literals only
    assert len(q1["holes"][0]["choice"]) == 61


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_for_the_new_cell_alone(name):
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    meta = cells.read_json(cells.BENCH, "metrics", name + ".json")
    for k in ("layer", "unit", "better", "source", "moves"):
        assert meta[k] == entry[k], k
    assert entry["moves"] == "qps" and entry["layer"] == "kernels"


@pytest.mark.parametrize("name", ("kernels.compact_decode_share",
                                  "kernels.presort_compact_share"))
def test_sort_regime_metrics_list_the_cells_that_reach_it(name):
    """Neither Q1 (9 key cells) nor Q6 (none) reaches the sort regime, where
    alone these two read anything: they list the four accepted cells."""
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [
        "ssb10-flat.flights-c4", "ssb10-flat-quarter.flights-c4",
        "ssb10-flat-mesh4.flights-c4", "ssb10-flat-bytime.flights-c4"]
    owed = {m["name"] for m in cells.load_cell(CELL)["per_layer"]}
    assert name not in owed and set(NEW_METRICS) <= owed


# -- (c) served ----------------------------------------------------------------

def _serve_and_ask(work, config, mesh_devices, seg_src, pool):
    """The benchmark's own set-up with `server.mesh.devices` at
    `mesh_devices`; every query of the pool over broker HTTP, one at a time.
    Returns [(answer, /health's device block after it)], the first entry the
    state before any query."""
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
        serve.wait_loaded(handles, config, SEGMENT_ROWS * SEGMENTS)
        broker = BrokerClient(handles["broker"].url)
        out = [(None, serve.pipeline_counters(handles))]
        for p in pool:
            out.append((broker.query(p["sql"]),
                        serve.pipeline_counters(handles)))
        return out
    finally:
        serve.stop_services(handles)


@pytest.fixture(scope="module")
def served(tmp_path_factory, cell, gen):
    config = cell["config"]
    root = tmp_path_factory.mktemp("tpch_served")
    seg_src = str(root / "segments")
    os.makedirs(seg_src)
    for i in range(SEGMENTS):
        build.build_segment({"config": config, "seed": SEED, "index": i,
                             "rows": SEGMENT_ROWS, "out_dir": seg_src})
    tables = gen.tables(config)
    pool = traffic.build_pool(
        dict(cell["traffic"], variants_per_template=VARIANTS),
        cell["templates"], tables, SEED)
    assert [p["template"] for p in pool] == ["q1"] * VARIANTS + ["q6"] * VARIANTS
    segs = [gen.segment(config, SEED, i, SEGMENT_ROWS) for i in range(SEGMENTS)]
    want = [reference.finish(p["spec"], reference.merge(
        [reference.partial(p["spec"], s, tables) for s in segs]), tables)
        for p in pool]
    out = {"pool": pool, "want": want, "segs": segs, "tables": tables,
           "limit": float(config["guarantees"]["sum_rel_gap"])}
    for n in (1, 4):
        out[n] = _serve_and_ask(str(root / f"mesh{n}"), config, n, seg_src,
                                pool)
    return out


@pytest.mark.parametrize("mesh", (1, 4))
@pytest.mark.parametrize("q", range(2 * VARIANTS))
def test_q1_and_q6_answer_as_the_reference(served, q, mesh):
    p = served["pool"][q]
    resp = served[mesh][1 + q][0]
    assert not resp.get("exceptions") and not resp.get("partialResult")
    assert resp["numServersResponded"] == resp["numServersQueried"] == 1
    c = reference.compare(p["spec"], resp["resultTable"]["rows"],
                          served["want"][q], served["limit"])
    assert c["wrong"] == 0 and c["count_wrong"] == 0, c["why"]
    assert c["sum_gap"] <= served["limit"] / 10, c["sum_gap"]
    # on the device path, in one launch; Q1's charge widened, Q6's product not
    assert resp["deviceLaunches"] == 1
    assert resp["widenedAggLaunches"] == (1 if p["template"] == "q1" else 0)
    # Q1's 9 key cells take the masked reduce (PR 37) and build no slab; Q6
    # has no GROUP BY
    assert resp["maskedGroupByLaunches"] == (
        1 if p["template"] == "q1" else 0)
    assert resp["slabbedLaunches"] == 0
    if p["template"] == "q1":
        assert len(resp["resultTable"]["rows"]) == 4


def test_the_size_is_one_where_int32_wraps(served):
    """What the parent computed: the charge's rows multiplied in int32."""
    segs, tables = served["segs"], served["tables"]
    p = np.concatenate([s["l_extendedprice"] for s in segs])
    d = np.concatenate([tables["l_discount"][s["l_discount"]] for s in segs])
    t = np.concatenate([tables["l_tax"][s["l_tax"]] for s in segs])
    with np.errstate(over="ignore"):
        wrapped = (p.astype(np.int32) * (100 - d).astype(np.int32)
                   * (100 + t).astype(np.int32)).astype(np.int64).sum()
    exact = (p.astype(np.int64) * (100 - d) * (100 + t)).sum()
    assert abs(wrapped - exact) > 0.9 * exact
    charge = sum(r[5] for r in served["want"][0])      # the filter passes 97%
    assert 0.9 * exact < charge <= exact


def test_health_sums_the_widened_launches_and_nothing_fell_back(served):
    for n in (1, 4):
        start, end = served[n][0][1], served[n][-1][1]
        assert end["widenedAggLaunches"] - start["widenedAggLaunches"] \
            == VARIANTS                                    # Q1's, not Q6's
        assert end["maskedGroupByLaunches"] - start["maskedGroupByLaunches"] \
            == VARIANTS
        assert end["launches"] - start["launches"] == 2 * VARIANTS
        for k in ("deviceErrors", "fallbacks", "timeouts"):
            assert end[k] == start[k], (n, k)


def test_host_path_answers_the_same(served, cell, tmp_path):
    """The host executor (numpy: INT operands widened to int64) over the same
    segments: the reference's numbers to float64's last bits."""
    from pinot_tpu.query.executor import execute_query
    from pinot_tpu.segment import load_segment
    config = cell["config"]
    for i in range(SEGMENTS):
        build.build_segment({"config": config, "seed": SEED, "index": i,
                             "rows": SEGMENT_ROWS, "out_dir": str(tmp_path)})
    segments = [load_segment(os.path.join(tmp_path, n))
                for n in sorted(os.listdir(tmp_path))]
    for q, p in enumerate(served["pool"]):
        got = execute_query(segments, p["sql"], use_device=False)
        c = reference.compare(p["spec"], [list(r) for r in got.rows],
                              served["want"][q], served["limit"])
        assert c["wrong"] == 0 and c["count_wrong"] == 0, c["why"]
        assert c["sum_gap"] <= 1e-12, c["sum_gap"]


# -- (d) the new readers -------------------------------------------------------

@pytest.mark.parametrize("name,key,parents", [
    ("kernels.widened_agg_share", "widenedAggLaunches",
     {"launches": 4}),                                    # PR 36's parent
    ("kernels.masked_groupby_share", "maskedGroupByLaunches",
     {"launches": 4, "widenedAggLaunches": 2}),           # PR 37's parent
])
def test_launch_share_reads_the_served_counters(served, name, key, parents):
    read = cells.load_reader(name)
    start, end = served[1][0][1], served[1][-1][1]
    delta = {k: end[k] - start[k] for k in start
             if isinstance(start[k], (int, float))}
    assert read({"counters": delta}) == 50.0              # Q1's of Q1 and Q6
    assert read({"counters": parents}) is None            # no such counter
    assert read({"counters": dict(delta, launches=0)}) is None
    assert read({"counters": dict(delta, **{key: 0})}) == 0.0


def test_q1_hbm_roofline_reads_q1s_solo_replay(cell):
    read = cells.load_reader("kernels.q1_hbm_roofline")
    peaks = cells.peaks("TPU v5 lite")
    rows = 67108864
    solo = [{"template": "q1", "least_bytes": 11 * rows, "busy_s": 0.060},
            {"template": "q6", "least_bytes": 8 * rows, "busy_s": 0.002}]
    share = read({"solo": solo, "peaks": peaks})
    assert share == pytest.approx(
        100 * 11 * rows / peaks["hbm_bytes_per_s"] / 0.060)
    assert 1.0 < share < 2.0
    assert read({"solo": solo[1:], "peaks": peaks}) is None   # no q1
    assert read({"solo": [dict(solo[0], busy_s=0.0)], "peaks": peaks}) is None
    assert read({"solo": solo, "peaks": None}) is None        # a rehearsal
    assert read({"solo": None, "peaks": peaks}) is None
