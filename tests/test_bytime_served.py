"""The deployment of `ssb10-flat-bytime` at a small size: a table pushed in
order-date order, one time range a segment, served through
`run_service_manager` over broker HTTP as benchmark/run.py drives it (PR 32).
The broker prunes by the segments' min/max, the server stages ONE block and
ONE merged view of what it holds, and the segments a query is routed to are
an input of the launch: every SSB template answers as
benchmark/harness/reference.py does, on a mesh of one device (a window of
slots) and of four (a mask of slots), whatever subset its literals draw."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark.harness import build, cells, reference, serve, traffic
from pinot_tpu.parallel.combine import MeshQueryExecutor, _route_window
from pinot_tpu.parallel.merged import MergedSegmentView
from pinot_tpu.parallel.mesh import default_mesh
from pinot_tpu.query import stats as qstats
from pinot_tpu.query.context import compile_query
from pinot_tpu.segment import load_segment

CELL = "ssb10-flat-bytime.flights-c4"
SEED = 3200000032
SEGMENTS = 8
SEGMENT_ROWS = 4096
TEMPLATES = ("q1.1", "q1.2", "q1.3", "q2.1", "q2.2", "q2.3", "q3.1", "q3.2",
             "q3.3", "q3.4", "q4.1", "q4.2", "q4.3")
PRUNED = ("q1.1", "q1.2", "q4.2", "q4.3")     # a year, a month, two years
SLOT_KEYS = ("routedSlots", "residentSlots", "scannedSlots", "mergedLaunches")
STAGE_KEYS = ("setBlocksStaged", "setBlockBytes")
DATE_INTS = ("lo_orderdate", "d_year", "d_yearmonthnum", "d_weeknuminyear")


@pytest.fixture(scope="module")
def cell():
    c = cells.load_cell(CELL)
    return dict(c, config=dict(c["config"], segments=SEGMENTS))


@pytest.fixture(scope="module")
def gen(cell):
    return cells.load_generator(cell["config"])


# -- (a) the generator ---------------------------------------------------------

def test_tables_are_ssb_flats(cell, gen):
    flat = cells.load_generator(dict(cell["config"], generator="ssb_flat"))
    ours, theirs = gen.tables(cell["config"]), flat.tables(cell["config"])
    assert sorted(ours) == sorted(theirs)
    assert all(np.array_equal(ours[c], theirs[c]) for c in ours)


@pytest.mark.parametrize("i", range(SEGMENTS))
def test_segment_holds_its_own_days_and_ssb_flats_other_columns(cell, gen, i):
    config = cell["config"]
    flat = cells.load_generator(dict(config, generator="ssb_flat"))
    tables = gen.tables(config)
    ours = gen.segment(config, SEED, i, SEGMENT_ROWS)
    theirs = flat.segment(config, SEED, i, SEGMENT_ROWS)
    lo, hi = gen.day_range(config, i)
    assert (lo, hi) == (2406 * i // SEGMENTS, 2406 * (i + 1) // SEGMENTS)
    # lo_orderdate's table is sorted by day, so a code IS the natural day
    days = ours["lo_orderdate"]
    assert days.min() == lo and days.max() == hi - 1
    assert len(np.unique(days)) == hi - lo            # the walk covers them
    years = tables["d_year"][ours["d_year"]]
    assert np.array_equal(years, tables["lo_orderdate"][days] // 10000)
    for col in theirs:
        if col not in DATE_INTS + ("d_yearmonth",):
            assert np.array_equal(ours[col], theirs[col]), col
    assert gen.segment(config, SEED, i, SEGMENT_ROWS)["d_year"].tobytes() \
        == ours["d_year"].tobytes()                   # the seed decides


@pytest.mark.parametrize("keys,runs", [
    (qstats.COUNTER_KEYS, True),
    (tuple(k for k in qstats.COUNTER_KEYS if k != qstats.RESIDENT_SLOTS),
     False),                     # the parent: one block a routed subset
    (None, False)])
def test_generator_fails_cleanly_on_a_program_without_the_resident_set(
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
    assert exit_.value.code not in (0, None)      # an exit code other than 0
    assert "residentSlots" in str(exit_.value.code)


def test_configuration_is_ssb10_flat_pushed_by_time():
    flat = cells.read_json(cells.BENCH, "configs", "ssb10-flat.json")
    ours = cells.read_json(cells.BENCH, "configs", "ssb10-flat-bytime.json")
    differ = {k for k in set(flat) | set(ours) if flat.get(k) != ours.get(k)}
    assert differ == {"name", "source", "deployment", "generator",
                      "guarantees", "assumed"}
    assert ours["generator"] == "ssb_flat_bytime"
    assert {k: v for k, v in ours["guarantees"].items() if k != "pruning"} \
        == flat["guarantees"]
    assert "pruned segment holds no row" in ours["guarantees"]["pruning"]
    assert not any(a.startswith("every dictionary value occurs")
                   for a in ours["assumed"])
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == ours["name"]]
    assert entry["source"] == ours["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(ours["reduced"])
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "flights-c4"
    assert len(cell["why"]) <= 200
    # of 4 cells at most 2 may ask for 4 chips, and one does
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


# -- (b) served ----------------------------------------------------------------

def _serve_and_ask(work, config, mesh_devices, seg_src, rounds):
    """The benchmark's own set-up with `server.mesh.devices` at
    `mesh_devices`; each round's queries over broker HTTP. Returns per round
    ({(template, variant): response}, /health's device block after it, the
    process's kernel-cache misses after it), the first entry the state before
    any query, and the executor's (blocks, views) after each round."""
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
        mex = handles["server_obj"].device_pipeline.mesh_exec
        broker = BrokerClient(handles["broker"].url)
        out = [({}, serve.pipeline_counters(handles),
                serve.kernel_cache_misses(), (0, 0))]
        for pool in rounds:
            answers = {(p["template"], p["variant"]): broker.query(p["sql"])
                       for p in pool}
            held = (len(mex._set_blocks), len(mex._views),
                    [id(e[1]) for e in mex._set_blocks.values()])
            out.append((answers, serve.pipeline_counters(handles),
                        serve.kernel_cache_misses(), held))
        return out
    finally:
        serve.stop_services(handles)


@pytest.fixture(scope="module")
def served(tmp_path_factory, cell, gen):
    config = cell["config"]
    root = tmp_path_factory.mktemp("bytime_served")
    seg_src = str(root / "segments")
    os.makedirs(seg_src)
    for i in range(SEGMENTS):
        build.build_segment({"config": config, "seed": SEED, "index": i,
                             "rows": SEGMENT_ROWS, "out_dir": seg_src})
    tables = gen.tables(config)
    pool = traffic.build_pool(dict(cell["traffic"], variants_per_template=2),
                              cell["templates"], tables, SEED)
    assert len(pool) == 2 * len(TEMPLATES)
    parts = [[reference.partial(p["spec"],
                                gen.segment(config, SEED, i, SEGMENT_ROWS),
                                tables) for p in pool]
             for i in range(SEGMENTS)]
    want = {(p["template"], p["variant"]): reference.finish(
        p["spec"], reference.merge([seg[q] for seg in parts]), tables)
        for q, p in enumerate(pool)}
    rounds = [[p for p in pool if p["variant"] == v] for v in (0, 1)]
    out = {"config": config, "tables": tables, "parts": parts, "want": want,
           "pool": {(p["template"], p["variant"]): p for p in pool},
           "order": [(p["template"], p["variant"]) for p in pool],
           "seg_src": seg_src,
           "limit": float(config["guarantees"]["sum_rel_gap"])}
    for n in (1, 4):
        out[n] = _serve_and_ask(str(root / f"mesh{n}"), config, n, seg_src,
                                rounds)
    return out


@pytest.mark.parametrize("variant", (0, 1))
@pytest.mark.parametrize("template", TEMPLATES)
def test_time_ordered_table_answers_as_the_reference(served, template,
                                                     variant):
    key = (template, variant)
    spec = served["pool"][key]["spec"]
    for n in (1, 4):
        resp = served[n][1 + variant][0][key]
        assert not resp.get("exceptions") and not resp.get("partialResult")
        assert resp["numServersResponded"] == resp["numServersQueried"] == 1
        c = reference.compare(spec, resp["resultTable"]["rows"],
                              served["want"][key], served["limit"])
        assert c["wrong"] == 0 and c["count_wrong"] == 0, (n, c["why"])
        assert c["sum_gap"] <= served["limit"], (n, c["sum_gap"])
        assert resp["numSegmentsQueried"] + resp["numSegmentsPruned"] \
            == SEGMENTS
        assert resp["deviceLaunches"] == 1, (n, key)


@pytest.mark.parametrize("template", PRUNED)
def test_broker_prunes_by_the_date_columns_range(served, template):
    for variant in (0, 1):
        resp = served[1][1 + variant][0][(template, variant)]
        assert resp["numSegmentsPruned"] > 0
        assert resp["numSegmentsPrunedByRange"] == resp["numSegmentsPruned"]


@pytest.mark.parametrize("variant", (0, 1))
@pytest.mark.parametrize("template", TEMPLATES)
def test_answer_says_what_was_routed_resident_and_read(served, template,
                                                       variant):
    """On a mesh of one a launch reads the window that covers the routed
    slots; on four every slot, the routed ones a mask. All in the merged id
    space: every template filters or groups on a date column."""
    key = (template, variant)
    for n in (1, 4):
        resp = served[n][1 + variant][0][key]
        routed = resp["numSegmentsQueried"]
        assert resp["routedSlots"] == routed
        assert resp["residentSlots"] == SEGMENTS
        assert resp["mergedLaunches"] == 1
        if n == 4 or routed == SEGMENTS:
            assert resp["scannedSlots"] == SEGMENTS
        else:
            # up to 8 slots the window is the span of the routed slots
            assert routed <= resp["scannedSlots"] <= SEGMENTS
    one = served[1][1 + variant][0][key]
    if template in ("q1.2",):               # a month: one or two segments
        assert one["scannedSlots"] <= 2


def test_other_literals_and_subsets_stage_nothing_and_build_no_block(served):
    """After one query of each template, the second variants (other literals,
    other routed subsets) put no byte on the device, build no block and no
    view, and compile at most the ladder's programs."""
    ladder = SEGMENTS                       # window lengths 1 .. 8
    for n in (1, 4):
        start, first, second = served[n]
        subsets = {v: {k[0]: a["numSegmentsQueried"]
                       for k, a in r[0].items()} for v, r in
                   ((0, first), (1, second))}
        assert subsets[0] != subsets[1]             # other subsets were drawn
        assert first[1]["setBlocksStaged"] - start[1]["setBlocksStaged"] == 1
        assert first[1]["setBlockBytes"] > start[1]["setBlockBytes"]
        for k in STAGE_KEYS:
            assert second[1][k] == first[1][k], (n, k)
        assert second[3] == first[3] and first[3][:2] == (1, 1)
        assert first[2] - start[2] <= len(TEMPLATES) * (ladder + 1)
        assert second[2] - first[2] <= len(TEMPLATES) * ladder
        for k in ("deviceErrors", "fallbacks", "timeouts"):
            assert second[1][k] == start[1][k], (n, k)
        assert second[1]["launches"] - start[1]["launches"] \
            == 2 * len(TEMPLATES)


def test_health_sums_what_the_answers_said(served):
    for n in (1, 4):
        start, first, second = served[n]
        for k in SLOT_KEYS:
            assert second[1][k] - start[1][k] == sum(
                a[k] for r in (first, second) for a in r[0].values()), (n, k)
    one = served[1][2][1]
    assert one["scannedSlots"] < one["residentSlots"]       # windows read less
    four = served[4][2][1]
    assert four["scannedSlots"] == four["residentSlots"]    # the mask form


# -- the new guarantee's control: an unsound prune cannot pass ------------------

@pytest.mark.parametrize("template", ("q1.1", "q1.2", "q1.3", "q4.2"))
def test_reference_with_a_routed_segment_left_out_reads_not_correct(served,
                                                                    template):
    """`selftest.py`'s segment-left-out control for the new guarantee: were
    the broker to prune a segment that holds rows the query matches, the
    answer would be the reference's over the other segments, and the
    comparison has to call that wrong."""
    key = (template, 0)
    q = served["order"].index(key)
    spec = served["pool"][key]["spec"]
    # a partial is (keys, sums, counts): the segments that hold matching rows
    holds = [i for i in range(SEGMENTS)
             if served["parts"][i][q][2].sum() > 0]
    assert holds
    without = reference.finish(spec, reference.merge(
        [served["parts"][i][q] for i in range(SEGMENTS) if i != holds[0]]),
        served["tables"])
    c = reference.compare(spec, without, served["want"][key],
                          served["limit"])
    assert c["wrong"] or c["count_wrong"] or c["sum_gap"] > served["limit"]
    # and a segment the broker did prune holds no row the query matches
    resp = served[1][1][0][key]
    assert len(holds) <= resp["numSegmentsQueried"]


# -- (c) routed-subset semantics on the executor --------------------------------

@pytest.fixture(scope="module")
def segments(served):
    names = sorted(os.listdir(served["seg_src"]),
                   key=lambda n: int(n.rsplit("_", 1)[1]))
    return [load_segment(os.path.join(served["seg_src"], n)) for n in names]


SUBSETS = {"one": (3,), "contiguous": (2, 3, 4), "not_contiguous": (0, 2, 7),
           "whole": tuple(range(SEGMENTS)), "first_two": (0, 1),
           "last": (SEGMENTS - 1,)}
ROUTED_SQL = {
    "scalar": "SELECT COUNT(*), SUM(lo_revenue) FROM lineorder "
              "WHERE lo_discount BETWEEN 1 AND 3",
    "by_year": "SELECT d_year, COUNT(*), SUM(lo_revenue) FROM lineorder "
               "WHERE lo_quantity < 25 GROUP BY d_year LIMIT 100",
    # every routed segment is emptied by the filter: year 1992 is segment 0
    # and 1's alone, and "one" .. "last" do not hold it
    "emptied": "SELECT d_year, COUNT(*) FROM lineorder WHERE d_year = 1992 "
               "GROUP BY d_year LIMIT 100",
}


def _rows(result):
    return sorted(tuple(r) for r in result.rows)


@pytest.mark.parametrize("devices", (1, 4))
@pytest.mark.parametrize("sql", sorted(ROUTED_SQL))
@pytest.mark.parametrize("subset", sorted(SUBSETS))
def test_routed_subset_answers_as_the_subset_alone(segments, subset, sql,
                                                   devices):
    """A launch over the resident block routed to a subset answers as the
    subset served alone does on the host executor: a subset of one, a
    contiguous one, one that is not, the whole set, and routed segments the
    filter empties."""
    from pinot_tpu.query.executor import ServerQueryExecutor
    from pinot_tpu.query.reduce import merge_segment_results, reduce_to_result
    mex = MeshQueryExecutor(default_mesh(devices))
    routed = [segments[i] for i in SUBSETS[subset]]
    ctx = compile_query(ROUTED_SQL[sql], segments[0].schema)
    p = mex.prepare_partial(ctx, routed, segments)
    assert p is not None
    (outs, finish, _, recorded), = mex.dispatch_prepared([p])
    import jax
    part = p.decode(finish(jax.device_get(outs))[0])
    from pinot_tpu.query.aggregates import make_agg
    aggs = [make_agg(f) for f in ctx.aggregations]
    got = reduce_to_result(ctx, merge_segment_results([part], aggs), aggs,
                           list(ctx.group_by))
    want = ServerQueryExecutor().execute(routed, ctx)
    assert len(got.rows) == len(want.rows)
    for g, w in zip(_rows(got), _rows(want)):
        assert g[:-1] == w[:-1] or len(g) == 2
        assert np.allclose(np.asarray(g, dtype=float),
                           np.asarray(w, dtype=float), rtol=1e-6)
    slots = SUBSETS[subset]
    whole = len(slots) == SEGMENTS
    assert recorded[qstats.ROUTED_SLOTS] == len(slots)
    assert recorded[qstats.RESIDENT_SLOTS] == SEGMENTS
    window, start = _route_window(slots, SEGMENTS, devices)
    assert recorded[qstats.SCANNED_SLOTS] == \
        (SEGMENTS if whole or devices > 1 else window)
    assert p.window == (0 if whole else window)
    assert start <= min(slots) and max(slots) < start + window
    assert len(mex._set_blocks) == 1


@pytest.mark.parametrize("slots,s_pad,want", [
    ((3,), 16, (1, 3)), ((2, 3), 16, (2, 2)), ((3, 4), 16, (2, 3)),
    ((5, 6, 7), 16, (3, 5)), ((4, 5, 6, 7, 8), 16, (5, 4)),
    ((0, 15), 16, (16, 0)), ((13, 14, 15), 16, (3, 13)),
    ((0, 2, 7), 8, (8, 0)), ((9, 14), 16, (6, 9)),
    # past 8 slots the length keeps three significant bits: 9 -> 10, 13 ->
    # 14, 15 -> 16, 17 -> 20, and the window ends inside the block
    (tuple(range(2, 11)), 16, (10, 2)), (tuple(range(3, 16)), 16, (14, 2)),
    (tuple(range(1, 16)), 16, (16, 0)), (tuple(range(40, 57)), 64, (20, 40)),
    (tuple(range(50, 64)), 64, (14, 50)), ((30, 63), 64, (40, 24))])
def test_route_window_is_the_ladder_step_that_covers_the_slots(slots, s_pad,
                                                               want):
    window, start = _route_window(slots, s_pad, 1)
    assert (window, start) == want
    span = max(slots) - min(slots) + 1
    assert span <= window < 1.25 * span + 1 and start + window <= s_pad
    assert start <= min(slots) and max(slots) < start + window
    assert _route_window(slots, s_pad, 4) == (s_pad, 0)


def test_route_ladder_has_at_most_four_steps_an_octave():
    for s_pad in (8, 16, 64, 256):
        lengths = {_route_window((0, hi), s_pad, 1)[0] for hi in range(s_pad)}
        assert len(lengths) <= 4 * s_pad.bit_length()
        assert max(lengths) == s_pad


def test_one_block_one_view_whatever_is_routed(segments):
    """Six subsets of one resident set through one executor: one block, one
    merged view, one set of global dictionaries; `d_year` has its 7 keys
    whatever is routed."""
    mex = MeshQueryExecutor(default_mesh(1))
    ctx = compile_query(ROUTED_SQL["by_year"], segments[0].schema)
    pads, blocks = set(), set()
    with qstats.collect_stats() as st:
        for slots in SUBSETS.values():
            p = mex.prepare_partial(ctx, [segments[i] for i in slots],
                                    segments)
            pads.add(p.spec.num_keys_pad)
            blocks.add(p.stack_key[2])
        # the whole set, named in another order than it is held in
        p = mex.prepare_partial(ctx, segments[::-1], segments)
        assert p.window == 0 and p.stack_key[2] in blocks
        staged = st.counters[qstats.SET_BLOCK_BYTES]
        mex.prepare_partial(ctx, [segments[5], segments[1]], segments)
        assert st.counters[qstats.SET_BLOCK_BYTES] == staged
    assert len(mex._set_blocks) == len(mex._views) == len(blocks) == 1
    (_, view), = mex._views.values()
    assert isinstance(view, MergedSegmentView)
    assert view.column("d_year").cardinality == 7 and pads == {8}
    assert st.counters[qstats.SET_BLOCKS_STAGED] == 1
    # a member replaced (another object at the same path) restages, and the
    # superseded block goes
    again = list(segments)
    again[0] = load_segment(segments[0].path)
    mex.prepare_partial(ctx, again[:1], again)
    assert len(mex._set_blocks) == len(mex._views) == 1


# -- (f) the four readers --------------------------------------------------------

NEW_METRICS = ("broker.routed_segment_share", "mesh.scanned_slot_share",
               "mesh.merged_launch_share", "mesh.staged_bytes_in_window")


def _ctx(served, n):
    start, first, second = served[n]
    answers = list(first[0].values()) + list(second[0].values())
    return {"records": [{"latency_ms": 1.0, "pool": 0, "response": a}
                        for a in answers],
            "counters": {k: second[1][k] - first[1][k] for k in first[1]
                         if isinstance(first[1][k], (int, float))}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_reads_the_served_counters(served, name):
    read = cells.load_reader(name)
    ctx = _ctx(served, 1)
    got = read(ctx)
    answers = [r["response"] for r in ctx["records"]]
    queried = sum(a["numSegmentsQueried"] for a in answers)
    if name == "broker.routed_segment_share":
        assert got == pytest.approx(100.0 * queried / (SEGMENTS * len(answers)))
        assert 0 < got < 100
    elif name == "mesh.scanned_slot_share":
        c = ctx["counters"]
        assert got == pytest.approx(100.0 * c["scannedSlots"]
                                    / c["residentSlots"])
        assert 100.0 * c["routedSlots"] / c["residentSlots"] <= got < 100
        assert read(_ctx(served, 4)) == 100.0           # the mask form
    elif name == "mesh.merged_launch_share":
        assert got == 100.0
    else:
        assert got == 0.0                               # warm: nothing staged


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_none_where_the_program_has_no_counter(name):
    """The parent of PR 32: no such counter in /health, and (for the broker's
    share) an answer without the fields."""
    read = cells.load_reader(name)
    ctx = {"records": [{"latency_ms": 1.0, "pool": 0,
                        "response": {"timeUsedMs": 1.0}}],
           "counters": {"launches": 5, "batches": 3}}
    assert read(ctx) is None
    assert read({"records": [], "counters": {}}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_for_the_new_cell_alone(name):
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    meta = cells.read_json(cells.BENCH, "metrics", name + ".json")
    assert entry["workloads"] == [CELL]
    for k in ("layer", "unit", "better", "source", "moves"):
        assert entry[k] == meta[k], k
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    json.dumps(meta)


def test_routed_queries_of_one_subset_stack_into_one_launch(segments):
    """Three queries that differ in literals alone, routed to one subset: one
    stacked launch over the window (the routing operands are the stack's),
    each answering as the subset alone does."""
    import jax
    from pinot_tpu.query.executor import ServerQueryExecutor
    mex = MeshQueryExecutor(default_mesh(1))
    sqls = ["SELECT d_year, COUNT(*), SUM(lo_revenue) FROM lineorder WHERE "
            f"lo_quantity BETWEEN {a} AND {a + 9} GROUP BY d_year LIMIT 100"
            for a in (1, 11, 21)]
    routed = segments[2:5]
    ps = [mex.prepare_partial(compile_query(q, segments[0].schema), routed,
                              segments) for q in sqls]
    assert all(p.stackable and p.window == 3 for p in ps)
    assert len({p.stack_key for p in ps}) == 1
    other = mex.prepare_partial(compile_query(sqls[0], segments[0].schema),
                                segments[3:6], segments)
    assert other.stack_key != ps[0].stack_key       # another start, no stack
    (outs, finish, idxs, recorded), = mex.dispatch_prepared(ps)
    assert idxs == [0, 1, 2] and recorded[qstats.SCANNED_SLOTS] == 3
    for p, host, sql in zip(ps, finish(jax.device_get(outs)), sqls):
        want = ServerQueryExecutor().execute(routed, sql)
        assert p.decode(host).num_docs_scanned == sum(r[1] for r in want.rows)
