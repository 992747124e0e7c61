"""A GROUP BY past the dense key space (`KernelCaps.dense_keys`), answered from
its sorted groups (`kernels._grouped_sparse`), and the ORDER BY ... LIMIT cut
on the device where the partial is the whole answer (`KernelSpec.trim`).

The bound is lowered through `set_caps`, so that small tables reach the
regime: against the host executor (numpy) over aligned and merged sets, MIN
and MAX cells and keys past 2^24 exact, the trimmed answer of one server equal
to the untrimmed answers of two after the broker's reduce (through the wire),
ties at the cut in the SQL's order, and the host's answer where more rows pass
than the launch holds. Every plan under the bound lowers to the text it
lowered to before the regime existed.
"""

import hashlib

import numpy as np
import pytest

from pinot_tpu.engine import kernels
from pinot_tpu.engine.caps import KernelCaps, get_caps, set_caps
from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
from pinot_tpu.query import stats as qstats
from pinot_tpu.query.context import SOLE_SERVER, compile_query
from pinot_tpu.query.executor import ServerQueryExecutor
from pinot_tpu.query.reduce import merge_segment_results, reduce_to_result
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.segment.writer import (SegmentGeneratorConfig,
                                      build_aligned_segments)

SEGMENTS, ROWS = 4, 4096
KEYS_A_SEGMENT = 2000           # 8,000 keys in all: past a dense bound of 4,096
BIG = 67_000_000                # keys and dates past 2^24: no float holds them
SMALL_CAPS = KernelCaps(chunk_cap=1024, dense_keys=4096)

SCHEMA = Schema("o", [dimension("k", DataType.INT),
                      dimension("seg", DataType.STRING),
                      dimension("day", DataType.INT),
                      metric("price", DataType.INT),
                      metric("disc", DataType.INT)])

Q3ISH = ("SELECT k, SUM(price * (100 - disc)), MIN(day), COUNT(*) FROM o "
         "WHERE seg = 'B' AND price < {p} GROUP BY k ORDER BY "
         "SUM(price * (100 - disc)) DESC, MIN(day), k LIMIT 10")
QUERIES = [
    Q3ISH.format(p=400),
    "SELECT k, MAX(day), COUNT(*) FROM o WHERE price < 40 GROUP BY k "
    "ORDER BY MAX(day) DESC, k DESC LIMIT 7",
    "SELECT k, COUNT(*) FROM o WHERE price < 80 GROUP BY k "
    "ORDER BY COUNT(*) DESC, k LIMIT 5 OFFSET 2",
    "SELECT seg, k, SUM(price), MIN(disc) FROM o WHERE price < 30 "
    "GROUP BY seg, k ORDER BY seg DESC, SUM(price) DESC, k LIMIT 12",
    # no ORDER BY the device can cut: the groups stay whole
    "SELECT k, AVG(price) FROM o WHERE price < 30 GROUP BY k "
    "ORDER BY AVG(price) DESC, k LIMIT 4",
    "SELECT k, SUM(price) FROM o WHERE price < 30 GROUP BY k "
    "HAVING SUM(price) > 20 ORDER BY k LIMIT 6",
]


@pytest.fixture
def small_caps():
    prev = get_caps()
    set_caps(SMALL_CAPS)
    try:
        yield
    finally:
        set_caps(prev)


def _columns(rng, lo, n):
    """`n` rows over keys [lo, lo + KEYS_A_SEGMENT), dates past 2^24."""
    return {"k": (BIG + lo + rng.integers(0, KEYS_A_SEGMENT, n)).astype(
                np.int64),
            "seg": np.array(["A", "B", "C"])[rng.integers(0, 3, n)],
            "day": BIG + rng.integers(0, 500, n),
            "price": rng.integers(0, 1000, n),
            "disc": rng.integers(0, 11, n)}


@pytest.fixture(scope="module")
def merged_set(tmp_path_factory):
    """Per-segment dictionaries, the keys of two segments disjoint (a table
    pushed in key order): the merged id space."""
    out = tmp_path_factory.mktemp("sparse_merged")
    rng = np.random.default_rng(40)
    builder = SegmentBuilder(SCHEMA)
    return [load_segment(builder.build(
        _columns(rng, i * KEYS_A_SEGMENT, ROWS), str(out), f"o_{i}"))
        for i in range(SEGMENTS)]


@pytest.fixture(scope="module")
def aligned_set(tmp_path_factory):
    """One dictionary shared by every segment: the aligned id space."""
    out = tmp_path_factory.mktemp("sparse_aligned")
    cols = _columns(np.random.default_rng(41), 0, SEGMENTS * ROWS)
    cols["k"] = cols["k"] + np.random.default_rng(42).integers(
        0, SEGMENTS, SEGMENTS * ROWS) * KEYS_A_SEGMENT
    return [load_segment(p) for p in build_aligned_segments(
        SCHEMA, cols, str(out), "o", SEGMENTS)]


def _rows(result):
    return [list(r) for r in result.rows]


@pytest.mark.parametrize("which", ["merged", "aligned"])
@pytest.mark.parametrize("q", range(len(QUERIES)))
def test_sorted_groups_answer_as_the_host(small_caps, merged_set, aligned_set,
                                          which, q):
    segs = merged_set if which == "merged" else aligned_set
    sql = QUERIES[q]
    want = ServerQueryExecutor(use_device=False).execute(segs, sql)
    with qstats.collect_stats() as st:
        got = MeshQueryExecutor(default_mesh(1)).execute(segs, sql)
    assert _rows(got) == _rows(want)
    assert got.stats.get("numGroupsTotal") == want.stats.get("numGroupsTotal")
    # one launch, the sparse regime; cut on the device where it can be
    c = st.counters
    assert c.get(qstats.DEVICE_LAUNCHES) == 1
    assert c.get(qstats.SPARSE_GROUPBY_LAUNCHES) == 1
    cut = "AVG" not in sql and "HAVING" not in sql
    assert c.get(qstats.DEVICE_TRIMMED_LAUNCHES, 0) == int(cut)


def test_min_and_keys_past_2_24_are_exact(small_caps, merged_set):
    """The keys (67M and up) and the MIN and MAX of dates past 2^24 come back
    whole: no key and no extreme passes through a float32, whose step there
    is 4 and 8."""
    sql = ("SELECT k, MIN(day), MAX(day), COUNT(*) FROM o WHERE price < 20 "
           "GROUP BY k ORDER BY MIN(day), k LIMIT 50")
    got = MeshQueryExecutor(default_mesh(1)).execute(merged_set, sql)
    want = ServerQueryExecutor(use_device=False).execute(merged_set, sql)
    assert _rows(got) == _rows(want)
    for k, lo, hi, _ in got.rows:
        assert k > 1 << 24 and lo > 1 << 24 and float(lo) == int(lo)
    assert any(int(np.float32(r[1])) != r[1] for r in got.rows)


def _partial(ex, segs, sql, sole):
    ctx = compile_query(sql, segs[0].schema)
    if sole:
        ctx.options[SOLE_SERVER] = True
    p = ex.prepare_partial(ctx, segs, segs)
    assert p is not None and kernels.sparse(p.spec)
    assert bool(p.spec.trim) == sole
    (packed, finish, _, _), = ex.dispatch_prepared([p])
    return ctx, p.decode(finish(ex.fetch([packed])[0])[0])


@pytest.fixture
def smaller_caps():
    """A bound that half the set's 4,000 keys also pass."""
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=1024, dense_keys=2048))
    try:
        yield
    finally:
        set_caps(prev)


@pytest.mark.parametrize("q", [0, 1, 2, 3])
def test_one_servers_cut_is_two_servers_merge(smaller_caps, merged_set, q):
    """The trimmed partial of the one server the broker routed a query to,
    reduced, is the answer two servers' untrimmed partials give after the
    wire and the broker's merge and reduce."""
    from pinot_tpu.cluster.wire import (decode_segment_result,
                                        encode_segment_result)
    from pinot_tpu.query.aggregates import make_agg
    sql = QUERIES[q]
    ex = MeshQueryExecutor(default_mesh(1))
    ctx, whole = _partial(ex, merged_set, sql, sole=True)
    assert whole.sparse.trimmed and len(whole.sparse.counts) <= 12
    parts = [decode_segment_result(encode_segment_result(
        _partial(ex, half, sql, sole=False)[1]))
        for half in (merged_set[:2], merged_set[2:])]
    assert not any(p.sparse.trimmed for p in parts)
    aggs = [make_agg(f) for f in ctx.aggregations]
    group = list(ctx.group_by)
    one = reduce_to_result(ctx, merge_segment_results(
        [decode_segment_result(encode_segment_result(whole))], aggs), aggs,
        group)
    two = reduce_to_result(ctx, merge_segment_results(parts, aggs), aggs,
                           group)
    assert _rows(one) == _rows(two) == _rows(
        ServerQueryExecutor(use_device=False).execute(merged_set, sql))
    assert one.stats["numGroupsTotal"] == two.stats["numGroupsTotal"]
    with pytest.raises(ValueError):
        merge_segment_results([whole, parts[0]], aggs)


@pytest.fixture(scope="module")
def tied_set(tmp_path_factory):
    """30 keys of equal sum and day, 4 rows each, among 3,030: the cut falls
    inside the tie, which the SQL breaks by the last ORDER BY key, or where
    it names none by the broker's stable sort of groups in key order."""
    out = tmp_path_factory.mktemp("sparse_ties")
    keys = np.repeat(np.arange(30) * 1000 + BIG, 4)
    rest = np.arange(9000) % 3000 + BIG + 100_000
    n = len(keys) + len(rest)
    cols = {"k": np.concatenate([keys, rest]).astype(np.int64),
            "seg": np.array(["B"] * len(keys) + ["A"] * len(rest)),
            "day": np.full(n, BIG), "price": np.full(n, 7),
            "disc": np.zeros(n, dtype=np.int64)}
    return [load_segment(SegmentBuilder(SCHEMA).build(cols, str(out), "t_0"))]


@pytest.mark.parametrize("order,limit", [
    ("SUM(price) DESC, MIN(day), k", 10),
    ("SUM(price) DESC, MIN(day), k DESC", 10),
    ("SUM(price) DESC", 10),             # the tie broken as the broker's sort
    ("COUNT(*), k DESC", 13),
])
def test_ties_at_the_cut_fall_in_the_sqls_order(smaller_caps, tied_set, order,
                                               limit):
    sql = (f"SELECT k, SUM(price), MIN(day), COUNT(*) FROM o WHERE seg = 'B' "
           f"GROUP BY k ORDER BY {order} LIMIT {limit}")
    with qstats.collect_stats() as st:
        got = MeshQueryExecutor(default_mesh(1)).execute(tied_set, sql)
    assert st.counters.get(qstats.DEVICE_TRIMMED_LAUNCHES) == 1
    assert _rows(got) == _rows(
        ServerQueryExecutor(use_device=False).execute(tied_set, sql))
    assert len(got.rows) == limit


def test_more_rows_than_the_launch_holds_go_to_the_host(small_caps,
                                                         merged_set):
    """An unselective filter passes more rows than `sparse_cap`: the launch
    says so (-1 groups) and the host answers, exactly."""
    sql = ("SELECT k, SUM(price) FROM o GROUP BY k ORDER BY SUM(price) DESC, "
           "k LIMIT 5")
    rows = SEGMENTS * ROWS
    assert rows > kernels.sparse_cap(rows)
    got = MeshQueryExecutor(default_mesh(1)).execute(merged_set, sql)
    assert _rows(got) == _rows(
        ServerQueryExecutor(use_device=False).execute(merged_set, sql))


def test_a_mesh_of_four_leaves_it_to_the_host(small_caps, merged_set):
    """Merging several chips' sorted groups is not built: on a mesh of four
    the host answers, and nothing is prepared for the device."""
    sql = QUERIES[0]
    ex = MeshQueryExecutor(default_mesh(4))
    ctx = compile_query(sql, merged_set[0].schema)
    assert ex.prepare_partial(ctx, merged_set, merged_set) is None
    assert _rows(ex.execute(merged_set, sql)) == _rows(
        ServerQueryExecutor(use_device=False).execute(merged_set, sql))


def test_single_segment_executor_answers_from_sorted_groups(merged_set):
    """One segment's 2,000 keys past a bound of 1,024: each segment's
    partial is its sorted groups, never cut (a segment is not the answer)."""
    sql = QUERIES[0].replace("LIMIT 10", "LIMIT 400")
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=512, dense_keys=1024))
    try:
        with qstats.collect_stats() as st:
            got = ServerQueryExecutor().execute(merged_set, sql)
    finally:
        set_caps(prev)
    assert st.counters.get(qstats.SPARSE_GROUPBY_LAUNCHES) == SEGMENTS
    assert not st.counters.get(qstats.DEVICE_TRIMMED_LAUNCHES)
    assert _rows(got) == _rows(
        ServerQueryExecutor(use_device=False).execute(merged_set, sql))


@pytest.mark.parametrize("keys,sparse", [
    (4096, False), (4097, True), (8000, True), (1 << 21, True)])
def test_the_plan_is_sparse_past_the_dense_bound(small_caps, keys, sparse):
    """The planner and the kernel's ladder read the bound alike: the padded
    key space against `dense_keys`."""
    from pinot_tpu.query.planner import pad_keys
    from pinot_tpu.query.predicate import FilterProgram
    assert (pad_keys(keys) > get_caps().dense_keys) == sparse
    spec = kernels.KernelSpec(FilterProgram(), ("k",), pad_keys(keys), (), {},
                              ROWS)
    assert kernels.sparse(spec) == sparse
    assert not kernels.masked(spec) and not kernels.trimmed(spec)


def test_a_key_space_past_int32_plans_on_the_host(tmp_path):
    """Three columns of 2,000 keys each: 8e9 ids, past the device's int32
    key. The host answers, with the reason."""
    from pinot_tpu.query.planner import plan_segment
    n = 2000
    cols = {"k": np.arange(n), "seg": np.array([f"s{i}" for i in range(n)]),
            "day": np.arange(n) + 7, "price": np.ones(n, dtype=np.int64),
            "disc": np.zeros(n, dtype=np.int64)}
    seg = load_segment(SegmentBuilder(SCHEMA, SegmentGeneratorConfig(
        raw_cardinality_fraction=2.0)).build(cols, str(tmp_path), "w_0"))
    plan = plan_segment(compile_query(
        "SELECT k, seg, day, COUNT(*) FROM o GROUP BY k, seg, day", SCHEMA),
        seg)
    assert plan.kind == "host" and "int32" in plan.fallback_reason


def test_trim_spec_reads_the_order_by(merged_set, small_caps):
    from pinot_tpu.query.executor import sparse_trim_spec
    from pinot_tpu.query.planner import plan_segment

    def spec(sql):
        ctx = compile_query(sql, SCHEMA)
        return sparse_trim_spec(ctx, plan_segment(ctx, merged_set[0]))
    base = "SELECT k, SUM(price), MIN(day), COUNT(*) FROM o GROUP BY k "
    assert spec(base + "ORDER BY SUM(price) DESC, MIN(day), k LIMIT 10") == (
        10, ((("out", "0.sum"), True), (("out", "1.min"), False),
             (("key", 0), False)))
    assert spec(base + "ORDER BY COUNT(*) DESC LIMIT 3 OFFSET 4") == (
        7, ((("out", "count"), True),))
    for tail in ("ORDER BY k LIMIT 70000", "ORDER BY k NULLS LAST LIMIT 5",
                 "ORDER BY SUM(price) + 1 LIMIT 5",
                 "HAVING COUNT(*) > 1 ORDER BY k LIMIT 5"):
        assert spec(base + tail) == (), tail


# -- every plan under the bound is the program it was ---------------------------

# sha256 of each scan's lowered text (no debug info) on the tree before the
# sparse regime existed, by the same helper: TPC-H Q1's masked rung, SSB
# Q4.1 in four slabs, the chunked regime, SSB Q4.3's wide key through the
# compacted sort, the sort regime at the dense bound itself, a MIN past the
# broadcast cap and a grouped distinct through the sort regime
PARENT_TEXT = [
    ("COUNT(*), SUM(v)", 8, 1 << 26, None,
     "14ddd68ef0d8f7ee2274d8dc6c47b3a7ef4085aa16bedb60ed19f24bab27ba08"),
    # the one-hot and chunk64 rungs, re-pinned where they gained the
    # compaction of the rows that passed in front of them
    # (`kernels._compacted_sums`); the other five are the texts they were
    ("COUNT(*), SUM(v)", 256, 1 << 26, None,
     "e64c83e3a31e14ca41edb73c94b8a06e245e8762d5fd86daca3412409e0c2c24"),
    ("COUNT(*), SUM(v)", 8192, 1 << 24, None,
     "712addd9f1f0875b027273321530625e6265473d0285ed816c0763bdad88348b"),
    ("COUNT(*), SUM(v)", 1_753_088, 1 << 26, None,
     "8cb982fa81fa3284aec016e9380338b8b2d7f94c83897856b0a9f81ebcaa5491"),
    ("COUNT(*), SUM(v)", 1 << 21, 16_384, None,
     "bc508d3dac034e540f156b5855ac90b4883a1950df35ef6a79721c0708a1bbe7"),
    ("MIN(v)", 131_072, 16_384, None,
     "d9f0fe1842afb3ef0d8d14458bfbf0c629452d4ff022a18cd6f4a0fe046a8ebf"),
    ("DISTINCTCOUNT(d)", 16, 16_384, 8192,
     "dae4aec09f894ff35f27b5d6fbe983cb20c59eb7b747159b60550c566813a930"),
]


@pytest.mark.parametrize("aggs,keys,rows,ids,digest", PARENT_TEXT)
def test_plans_under_the_bound_lower_to_the_parents_text(aggs, keys, rows, ids,
                                                         digest):
    import jax
    from test_dense_groupby import _abstract_scan
    assert get_caps() == KernelCaps()
    body, args = _abstract_scan(aggs, keys, rows, ids)
    text = jax.jit(body).lower(*args).as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == digest
    assert "pinot.groupby.sparse" not in jax.jit(body).lower(*args).as_text(
        debug_info=True)


def test_past_the_bound_the_program_is_the_sparse_regime():
    import jax
    from test_dense_groupby import _abstract_scan
    body, args = _abstract_scan("COUNT(*), SUM(v), MIN(v)",
                                (1 << 21) + 4096, 16_384)
    text = jax.jit(body).lower(*args).as_text(debug_info=True)
    for scope in ("pinot.groupby.sparse", "pinot.groupby.sparse.presort",
                  "pinot.groupby.sparse.groups"):
        assert scope in text, scope
    assert "pinot.groupby.partitioned" not in text
    assert "pinot.trim" not in text
