"""The deployment of `tpch10-flat` at a small size: TPC-H's LINEITEM flattened
with the order and customer attributes Q3 reads, as the benchmark's generator
draws it (whole orders a segment, order keys disjoint from segment to
segment), Q3 served through `run_service_manager` over broker HTTP as
benchmark/run.py drives it. The dense bound is lowered through `set_caps` so
that 16k order ids take the sorted-groups regime the cell's 16.8M do, and the
one server the broker routes to cuts the ORDER BY ... LIMIT on its device."""

import os
import shutil

import numpy as np
import pytest

from benchmark.harness import build, cells, readers, reference, serve, traffic
from pinot_tpu.engine.caps import KernelCaps, get_caps, set_caps
from pinot_tpu.query import stats as qstats

CELL = "tpch10-flat.tpch-q3-c4"
SEED = 4000000040
SEGMENTS = 4
SEGMENT_ROWS = 16384            # 4,096 orders: 2,406 walk the dates, 1,690 drawn
VARIANTS = 4
NEW_METRICS = ("kernels.sparse_groupby_share",
               "executor.fetched_bytes_per_answer", "kernels.q3_hbm_roofline")


@pytest.fixture(scope="module")
def cell():
    c = cells.load_cell(CELL)
    return dict(c, config=dict(c["config"], segments=SEGMENTS))


@pytest.fixture(scope="module")
def gen(cell):
    return cells.load_generator(cell["config"])


@pytest.fixture(scope="module")
def tables(cell, gen):
    return gen.tables(cell["config"])


# -- (a) the generator ---------------------------------------------------------

def _days(yyyymmdd):
    v = np.asarray(yyyymmdd)
    return (np.array([f"{d // 10000:04d}-{d // 100 % 100:02d}-{d % 100:02d}"
                      for d in v], dtype="datetime64[D]"))


@pytest.mark.parametrize("i", range(SEGMENTS))
def test_segment_is_whole_orders_by_dbgens_rules(cell, gen, tables, i):
    config = cell["config"]
    cols = gen.segment(config, SEED, i, SEGMENT_ROWS)
    assert sorted(cols) == sorted(c["name"] for c in config["schema"])
    assert all(len(v) == SEGMENT_ROWS for v in cols.values())
    orders = SEGMENT_ROWS // 4
    code = cols["l_orderkey"]
    # segment i holds orders i * n / 4 onwards, each whole and in key order
    assert code.min() == i * orders and code.max() == (i + 1) * orders - 1
    assert (np.diff(code) >= 0).all()
    lines = np.bincount(code - i * orders)
    assert lines.min() >= 1 and lines.max() <= 7 and lines.sum() == \
        SEGMENT_ROWS
    each = np.bincount(lines, minlength=8)[1:]
    assert (each[[0, 1, 2, 4, 5, 6]] == each[0]).all()      # 1..7 alike
    assert each[3] == each[0] + orders % 7                   # the rest: 4
    # the order's columns are constant over its lines
    heads = np.flatnonzero(np.r_[True, np.diff(code) != 0])
    for col in ("o_orderdate", "c_mktsegment", "o_shippriority"):
        per_order = np.repeat(cols[col][heads], lines)
        assert np.array_equal(per_order, cols[col]), col
    ship = _days(tables["l_shipdate"][cols["l_shipdate"]])
    order = _days(tables["o_orderdate"][cols["o_orderdate"]])
    gap = (ship - order).astype(int)
    assert gap.min() >= 1 and gap.max() <= 121
    assert set(tables["o_shippriority"][cols["o_shippriority"]]) == {0}
    # every order date and segment in every segment: those dictionaries agree
    assert len(np.unique(cols["o_orderdate"])) == len(tables["o_orderdate"])
    assert len(np.unique(cols["c_mktsegment"])) == 5
    price = cols["l_extendedprice"]
    assert price.dtype == np.int32 and price.min() >= 90_000 \
        and price.max() <= 10_494_950
    again = gen.segment(config, SEED, i, SEGMENT_ROWS)
    assert all(np.array_equal(again[c], cols[c]) for c in cols)
    assert not np.array_equal(
        gen.segment(config, SEED + 1, i, SEGMENT_ROWS)["l_extendedprice"],
        price)


def test_order_key_dictionaries_are_disjoint(cell, gen, tables):
    keys = [set(tables["l_orderkey"][gen.segment(
        cell["config"], SEED, i, SEGMENT_ROWS)["l_orderkey"]].tolist())
        for i in range(SEGMENTS)]
    assert sum(len(k) for k in keys) == len(set().union(*keys)) \
        == SEGMENTS * SEGMENT_ROWS // 4


def test_tables_are_the_columns_domains(cell, gen, tables):
    by_name = {c["name"]: c for c in cell["config"]["schema"]}
    key = tables["l_orderkey"]
    assert len(key) == 16_777_216 and (np.diff(key) > 0).all()
    assert key[:10].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33, 34]  # dbgen's
    assert key[-1] == 67_108_840 < 2 ** 31
    assert tables["c_mktsegment"].tolist() == [
        "AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
    for col, table in tables.items():
        c = by_name[col]
        assert len(table) == c["cardinality"], col
        if "min" in c:
            assert (table[0], table[-1]) == (c["min"], c["max"]), col
    assert "l_extendedprice" not in tables


def test_lines_per_order_are_a_shuffled_multiset(gen):
    rng = np.random.default_rng(7)
    lens = gen.lines_per_order(1_048_576, rng)
    assert lens.sum() == 4 * 1_048_576
    assert sorted(np.bincount(lens)[1:].tolist()) == [149_796] * 6 + [149_800]
    assert not (np.diff(lens) >= 0).all()


@pytest.mark.parametrize("keys,runs", [
    (qstats.COUNTER_KEYS, True),
    (tuple(k for k in qstats.COUNTER_KEYS
           if k != qstats.SPARSE_GROUPBY_LAUNCHES), False),   # the parent
    (None, False)])
def test_generator_fails_cleanly_on_a_program_without_sorted_groups(
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
    assert "sparseGroupByLaunches" in str(exit_.value.code)


# -- (b) the configuration, the query and the cell ------------------------------

def test_configuration_holds_the_published_numbers(cell):
    ours = cells.read_json(cells.BENCH, "configs", "tpch10-flat.json")
    lineitem = cells.read_json(cells.BENCH, "configs", "tpch10-lineitem.json")
    assert (ours["rows"], ours["segments"], ours["chips"]) == (67108864, 16, 1)
    assert ours["rows"] // ours["segments"] // 4 == 1_048_576   # orders
    assert "dbgen -s 10" in ours["source"] and "2.4.3" in ours["source"]
    for k in ("cluster", "deployment", "servers", "replication", "table",
              "no_dictionary_columns"):
        assert ours[k] == lineitem[k], k
    assert ours["guarantees"]["sum_rel_gap"] == 2e-05
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == ours["name"]]
    assert entry["source"] == ours["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(ours["reduced"]) == [
        "l_unread_columns", "orders_customer_unbuilt",
        "server.device.stacking.enabled"]
    found, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert found["chips"] == 1 and found["traffic"] == "tpch-q3-c4"
    assert len(found["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    mix = cell["traffic"]
    assert (mix["clients"], mix["queue"], mix["variants_per_template"],
            mix["timeout_s"]) == (4, "shared", 8, 120)
    assert mix["templates"] == ["tpch/q3"]


def test_q3_template_is_the_published_query(cell, tables):
    q3, = cell["templates"]
    holes = {h["name"]: h["choice"] for h in q3["holes"]}
    assert holes["s"] == tables["c_mktsegment"].tolist()
    assert holes["d"] == list(range(19950301, 19950332))      # 2.4.3.3
    spec = reference.bind(q3["reference"], {"s": "BUILDING", "d": 19950315})
    # the seven columns Q3 reads, 15 bytes a row at their narrowest
    assert readers.least_bytes(spec, cell["config"]) == 15 * 67108864
    # the reference orders by revenue alone (its `_in_order` would hold the
    # later keys to order inside a near tie of revenue: PERF.md, section 7); the
    # SQL names all three
    assert spec["order_by"] == [["revenue", "desc"]]
    assert q3["sql"].endswith(
        "ORDER BY SUM(l_extendedprice * (100 - l_discount)) DESC, "
        "MIN(o_orderdate), l_orderkey LIMIT 10")
    pool = traffic.build_pool(cell["traffic"], cell["templates"], tables, 9)
    assert len({p["sql"] for p in pool}) == 8


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


# -- (c) the reference against a loop -------------------------------------------

def _brute_q3(spec, segs, tables):
    """Q3 by a Python loop over the rows: the reference's own check."""
    s, d = spec["filters"][0]["args"][0], spec["filters"][1]["args"][0]
    groups = {}
    for cols in segs:
        for r in range(len(cols["l_orderkey"])):
            if tables["c_mktsegment"][cols["c_mktsegment"][r]] != s:
                continue
            day = int(tables["o_orderdate"][cols["o_orderdate"][r]])
            if not (day < d < int(tables["l_shipdate"][cols["l_shipdate"][r]])):
                continue
            key = int(tables["l_orderkey"][cols["l_orderkey"][r]])
            disc = int(tables["l_discount"][cols["l_discount"][r]])
            g = groups.setdefault(key, [0, day, 0])
            g[0] += int(cols["l_extendedprice"][r]) * (100 - disc)
            g[1] = min(g[1], day)
    rows = sorted(([k, float(v[0]), v[1], v[2]] for k, v in groups.items()),
                  key=lambda r: (-r[1], r[2], r[0]))
    return rows[:10]


@pytest.mark.parametrize("s,d,ties", [("BUILDING", 19950315, False),
                                      ("MACHINERY", 19950301, False),
                                      ("BUILDING", 19950315, True)])
def test_reference_is_the_loop(cell, gen, tables, s, d, ties):
    """The reference's partial, merge and finish against the loop, with the
    ORDER BY's ties: `ties` gives every line the same price, so revenues tie
    across orders and the date and then the key decide."""
    q3, = cell["templates"]
    spec = reference.bind(q3["reference"], {"s": s, "d": d})
    spec["order_by"] = [["revenue", "desc"], ["o_orderdate", "asc"],
                        ["l_orderkey", "asc"]]          # the SQL's, whole
    segs = [gen.segment(cell["config"], SEED, i, 4096 * 2) for i in range(2)]
    if ties:
        for c in segs:
            c["l_extendedprice"] = np.full_like(c["l_extendedprice"], 100_000)
            c["l_discount"] = np.zeros_like(c["l_discount"])
    want = _brute_q3(spec, segs, tables)
    got = reference.finish(spec, reference.merge(
        [reference.partial(spec, c, tables) for c in segs]), tables)
    assert got == want and len(got) == 10
    if ties:        # the cut falls inside a tie of revenue
        assert sum(r[1] == got[-1][1] for r in got) > 1
        # the template's spec, revenue alone, cuts the same set where no
        # revenue ties at the cut
        return
    alone = reference.bind(q3["reference"], {"s": s, "d": d})
    assert {r[0] for r in reference.finish(alone, reference.merge(
        [reference.partial(alone, c, tables) for c in segs]), tables)} \
        == {r[0] for r in got}


# -- (d) served ----------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory, cell, gen, tables):
    """The benchmark's own set-up at 4 x 16,384 rows, the dense bound lowered
    below the 16,384 order ids; every query of the pool over broker HTTP,
    one at a time, with /health's device block before and after each."""
    from pinot_tpu.cluster.process import BrokerClient
    config = cell["config"]
    root = tmp_path_factory.mktemp("tpch_flat_served")
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
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=1024, dense_keys=4096))
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
        set_caps(prev)
        shutil.rmtree(work, ignore_errors=True)
    return {"pool": pool, "want": want, "answers": answers, "segs": segs,
            "root": root, "limit": float(config["guarantees"]["sum_rel_gap"])}


@pytest.mark.parametrize("q", range(VARIANTS))
def test_q3_answers_as_the_reference_from_the_device(served, q):
    p, want = served["pool"][q], served["want"][q]
    resp = served["answers"][1 + q][0]
    assert not resp.get("exceptions") and not resp.get("partialResult")
    assert resp["numServersResponded"] == resp["numServersQueried"] == 1
    rows = resp["resultTable"]["rows"]
    c = reference.compare(p["spec"], rows, want, served["limit"])
    assert c["wrong"] == 0 and c["count_wrong"] == 0, c["why"]
    assert c["sum_gap"] <= served["limit"] / 10, c["sum_gap"]
    assert len(rows) == len(want) == 10
    # one launch, the sorted groups, cut on the device: ten groups fetched
    assert resp["deviceLaunches"] == 1
    assert resp["sparseGroupByLaunches"] == 1
    assert resp["deviceTrimmedLaunches"] == 1
    assert resp["mergedLaunches"] == 1         # order keys differ by segment
    assert 0 < resp["bytesFetched"] < 1024
    # MIN cells are whole dates (yyyymmdd, past 2^24) and the priority 0
    assert all(float(r[2]) == int(r[2]) > 1 << 24 and r[3] == 0
               for r in rows)


def test_health_sums_the_sparse_and_trimmed_launches(served):
    start, end = served["answers"][0][1], served["answers"][-1][1]
    for key in ("sparseGroupByLaunches", "deviceTrimmedLaunches", "launches"):
        assert end[key] - start[key] == VARIANTS, key
    for k in ("deviceErrors", "fallbacks", "timeouts"):
        assert end[k] == start[k], k


def test_host_path_answers_the_same(served, cell, tmp_path):
    from pinot_tpu.query.executor import execute_query
    from pinot_tpu.segment import load_segment
    for i in range(SEGMENTS):
        build.build_segment({"config": cell["config"], "seed": SEED,
                             "index": i, "rows": SEGMENT_ROWS,
                             "out_dir": str(tmp_path)})
    segments = [load_segment(os.path.join(tmp_path, n))
                for n in sorted(os.listdir(tmp_path))]
    for q, p in enumerate(served["pool"]):
        got = execute_query(segments, p["sql"], use_device=False)
        c = reference.compare(p["spec"], [list(r) for r in got.rows],
                              served["want"][q], served["limit"])
        assert c["wrong"] == 0 and c["count_wrong"] == 0, c["why"]
        assert c["sum_gap"] <= 1e-12, c["sum_gap"]


# -- (e) the new readers -------------------------------------------------------

def _delta(served):
    start, end = served["answers"][0][1], served["answers"][-1][1]
    return {k: end[k] - start[k] for k in start
            if isinstance(start[k], (int, float))}


def test_sparse_share_reads_the_served_counters(served):
    read = cells.load_reader("kernels.sparse_groupby_share")
    delta = _delta(served)
    assert read({"counters": delta}) == 100.0
    assert read({"counters": {"launches": 4}}) is None       # the parent
    assert read({"counters": dict(delta, launches=0)}) is None
    assert read({"counters": dict(delta, sparseGroupByLaunches=0)}) == 0.0


def test_fetched_bytes_reads_the_served_answers(served):
    read = cells.load_reader("executor.fetched_bytes_per_answer")
    records = [{"response": r, "latency_ms": 1.0, "pool": 0}
               for r, _ in served["answers"][1:]]
    got = read({"records": records, "counters": _delta(served)})
    assert got == pytest.approx(np.mean(
        [r["bytesFetched"] for r, _ in served["answers"][1:]]))
    assert 0 < got < 1024
    assert read({"records": records, "counters": {"launches": 4}}) is None
    assert read({"records": [], "counters": _delta(served)}) is None


def test_q3_hbm_roofline_reads_q3s_solo_replay():
    read = cells.load_reader("kernels.q3_hbm_roofline")
    peaks = cells.peaks("TPU v5 lite")
    rows = 67108864
    solo = [{"template": "q3", "least_bytes": 15 * rows, "busy_s": 0.050}]
    share = read({"solo": solo, "peaks": peaks})
    assert share == pytest.approx(
        100 * 15 * rows / peaks["hbm_bytes_per_s"] / 0.050)
    assert read({"solo": [dict(solo[0], template="q1")],
                 "peaks": peaks}) is None
    assert read({"solo": [dict(solo[0], busy_s=0.0)], "peaks": peaks}) is None
    assert read({"solo": solo, "peaks": None}) is None
