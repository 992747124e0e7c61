"""The sort regime's dense table past `chunk_cap` keys (`kernels.
_grouped_partitioned`): its MINs and MAXs taken from the rows it sorted
(`kernels._sorted_extremes` and the compact decode's), and, where the partial
is the whole answer, the key space answered from its sorted groups and cut
to the ORDER BY ... LIMIT on the device (`kernels._grouped_sparse`,
`every_row`).

The cut against numpy's ORDER BY ... LIMIT over the same rows (ties at the
k-th row broken by the key, DESC and ASC, several ORDER BY items, k past the
groups that occur, few rows passing and many); the extremes against `segment_min` / `segment_max` on
both decodes of the sorted rows; and the cut through the served executor
against the host, on one device and, uncut, on four.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.engine import kernels
from pinot_tpu.engine.caps import KernelCaps, get_caps, set_caps
from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
from pinot_tpu.query import stats as qstats
from pinot_tpu.query.context import SOLE_SERVER, compile_query
from pinot_tpu.query.executor import ServerQueryExecutor, sort_trim_spec
from pinot_tpu.query.planner import plan_segment
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.segment.writer import SegmentGeneratorConfig

# -- the cut of a dense key space ------------------------------------------------

NSEG, N = 5001, 1 << 16         # 5,000 keys and the overflow key, rows


def _rows_for(rng, passing, ties):
    """A key a row (the overflow key where it did not pass), a SUM's row and
    a MIN's; with `ties` the values take few levels, so sums and MINs tie at
    the cut."""
    key = np.where(rng.random(N) < passing, rng.integers(0, NSEG - 1, N),
                   NSEG - 1).astype(np.int32)
    if ties:
        s = (rng.integers(0, 2, N) * 0.5).astype(np.float32)
        v = rng.integers(0, 2, N).astype(np.int32)
    else:
        s = rng.integers(-50, 50, N).astype(np.float32)
        v = rng.integers(0, 1000, N).astype(np.int32)
    return key, np.where(key < NSEG - 1, s, 0).astype(np.float32), v


def _host_cut(key, s, v, order, k):
    """numpy's ORDER BY ... LIMIT over the keys that occur, the key last:
    [keys, counts, sums, mins] of the k groups, in key order."""
    live = key < NSEG - 1
    keys = np.unique(key[live])
    counts = np.bincount(key[live], minlength=NSEG)[keys]
    sums = np.bincount(key[live], weights=s[live], minlength=NSEG)[keys]
    mins = np.full(NSEG, np.iinfo(np.int32).max)
    np.minimum.at(mins, key[live], v[live])
    cols = {("key", 0): keys.astype(np.float64), ("col", 0): counts,
            ("col", 1): sums, ("col", 2): mins[keys]}
    sort_keys = [keys]
    for src, desc in reversed(order):
        sort_keys.append(-cols[src] if desc else cols[src])
    pick = np.sort(np.lexsort(sort_keys)[:k])
    return [keys[pick], counts[pick], sums[pick], mins[keys][pick]]


CUTS = [
    # (ORDER BY, k, share of rows that pass, ties)
    pytest.param(((("col", 1), True),), 10, 0.6, False, id="sum-desc"),
    pytest.param(((("col", 1), False),), 10, 0.6, False, id="sum-asc"),
    pytest.param(((("col", 1), True),), 25, 0.6, True, id="tie-at-kth"),
    pytest.param(((("col", 1), True), (("col", 2), False)), 40, 0.6, True,
                 id="sum-desc-min-asc"),
    pytest.param(((("col", 2), True), (("col", 1), False), (("key", 0), True)),
                 17, 0.6, True, id="three-items-key-desc"),
    pytest.param(((("col", 0), True),), 100, 0.0008, False,
                 id="k-past-groups"),
    pytest.param(((("col", 1), True),), 30, 0.005, True,
                 id="few-pass-compacted"),
    pytest.param(((("key", 0), False),), 7, 0.6, False, id="by-key"),
]


@pytest.mark.parametrize("order,k,passing,ties", CUTS)
def test_dense_cut_is_the_host_order_by_limit(order, k, passing, ties):
    """The sort regime's cut from its sorted groups (`every_row`): over the
    compacted prefix where few rows pass, over every sorted row where many
    do, against numpy's ORDER BY ... LIMIT."""
    rng = np.random.default_rng(k + int(passing * 1000))
    key, s, v = _rows_for(rng, passing, ties)
    strides = jnp.asarray([1], jnp.int32)

    def cut(key, s, v):
        return kernels._grouped_sparse(key, NSEG, [s], [(v, True)], None,
                                       (k, order), strides, 1, every_row=True,
                                       name="pinot.groupby.partitioned")
    (keys, *cols), groups, rows = jax.jit(cut)(key, s, v)
    want = _host_cut(key, s, v, order, k)
    n = len(want[0])
    assert n == min(k, len(np.unique(key[key < NSEG - 1])))
    assert int(groups) == len(np.unique(key[key < NSEG - 1]))
    assert int(rows) == int((key < NSEG - 1).sum())
    # the k slots hold the cut's groups in key order, then key 0 and zeros
    assert np.array_equal(np.asarray(keys[:n]), want[0])
    assert not np.asarray(keys[n:]).any()
    assert np.array_equal(np.asarray(cols[0][:n]), want[1])
    assert np.allclose(np.asarray(cols[1][:n]), want[2], atol=1e-3)
    assert np.array_equal(np.asarray(cols[2][:n]), want[3])


# -- the extremes from the sorted rows ------------------------------------------

@pytest.mark.parametrize("passing,dtype", [
    (0.005, np.int32),      # under compact_cap: the compacted sort's prefix
    (0.005, np.float32),
    (0.6, np.int32),        # the full sort and its per-key decode
    (0.6, np.float32),
])
def test_sorted_extremes_are_segment_min_and_max(passing, dtype):
    n, nseg, block = 1 << 16, 5001, 4096
    rng = np.random.default_rng(int(passing * 1000))
    key = np.where(rng.random(n) < passing, rng.integers(0, nseg - 1, n),
                   nseg - 1).astype(np.int32)
    v = (rng.integers(-1000, 1000, n) * (1 if dtype == np.int32 else 0.25)
         ).astype(dtype)
    s = rng.random(n).astype(np.float32)
    took = []

    def regime(key, v, s):
        return kernels._grouped_partitioned(key, nseg, [s * (key < nseg - 1)],
                                            block, took,
                                            [(v, True), (v, False)])
    counts, sums, lo, hi = jax.jit(regime)(key, v, s)
    held = np.bincount(key, minlength=nseg)[:-1] > 0
    want_lo = jax.ops.segment_min(v, key, num_segments=nseg)
    want_hi = jax.ops.segment_max(v, key, num_segments=nseg)
    assert np.array_equal(np.asarray(lo)[:-1][held],
                          np.asarray(want_lo)[:-1][held])
    assert np.array_equal(np.asarray(hi)[:-1][held],
                          np.asarray(want_hi)[:-1][held])
    assert np.asarray(lo).dtype == dtype
    assert np.array_equal(np.asarray(counts)[:-1],
                          np.bincount(key, minlength=nseg)[:-1])
    assert np.allclose(np.asarray(sums)[:-1], np.bincount(
        key, weights=s, minlength=nseg)[:-1], rtol=1e-5, atol=1e-4)
    # the few rows that passed take the compacted sort, the many the full
    assert (passing < 0.01) == (int((key < nseg - 1).sum())
                                <= kernels.compact_cap(n, nseg, block))


# -- served: the cut on one device, none on four ----------------------------------

SCHEMA = Schema("p", [dimension("part", DataType.INT),
                      dimension("day", DataType.INT),
                      metric("qty", DataType.INT),
                      metric("disc", DataType.INT)])
SEGMENTS, ROWS, PARTS = 4, 8192, 6000
CAPS = KernelCaps(chunk_cap=1024, dense_keys=1 << 14)

TOPN = [
    "SELECT part, SUM(qty) FROM p WHERE day < {d} GROUP BY part "
    "ORDER BY SUM(qty) DESC, part LIMIT 100",
    "SELECT part, SUM(qty), MIN(disc), MAX(disc), COUNT(*) FROM p "
    "WHERE day < {d} GROUP BY part ORDER BY SUM(qty) DESC, part LIMIT 100",
    "SELECT part, MAX(disc), COUNT(*) FROM p WHERE day < {d} GROUP BY part "
    "ORDER BY MAX(disc), COUNT(*) DESC, part DESC LIMIT 30 OFFSET 5",
    "SELECT part, SUM(qty) FROM p GROUP BY part ORDER BY part LIMIT 256",
    # past PICK_REDUCE_MAX, an AVG, a HAVING: the table is fetched whole
    "SELECT part, SUM(qty) FROM p GROUP BY part ORDER BY part LIMIT 257",
    "SELECT part, AVG(qty) FROM p WHERE day < {d} GROUP BY part "
    "ORDER BY AVG(qty) DESC, part LIMIT 10",
    "SELECT part, SUM(qty) FROM p GROUP BY part HAVING SUM(qty) > 40 "
    "ORDER BY part LIMIT 10",
]


@pytest.fixture(scope="module")
def parts_set(tmp_path_factory):
    """Per-segment part dictionaries (a merged id space of 6,000 keys, past
    a chunk_cap of 1,024); quantities tie often at the 100th row."""
    out = tmp_path_factory.mktemp("dense_cut")
    rng = np.random.default_rng(43)
    segs = []
    for i in range(SEGMENTS):
        cols = {"part": rng.integers(1, PARTS + 1, ROWS).astype(np.int64),
                "day": rng.integers(0, 100, ROWS).astype(np.int64),
                "qty": rng.integers(1, 51, ROWS).astype(np.int64),
                "disc": rng.integers(0, 11, ROWS).astype(np.int64)}
        segs.append(load_segment(SegmentBuilder(SCHEMA, SegmentGeneratorConfig(
            raw_cardinality_fraction=2.0)).build(cols, str(out), f"p_{i}")))
    return segs


@pytest.fixture
def caps():
    prev = get_caps()
    set_caps(CAPS)
    try:
        yield
    finally:
        set_caps(prev)


def _rows(result):
    return [list(r) for r in result.rows]


@pytest.mark.parametrize("q", range(len(TOPN)))
def test_served_topn_is_the_hosts(caps, parts_set, q):
    sql = TOPN[q].format(d=40)
    ctx = compile_query(sql, SCHEMA)
    plan = plan_segment(ctx, parts_set[0])
    assert not plan.sparse
    want = ServerQueryExecutor(use_device=False).execute(parts_set, sql)
    with qstats.collect_stats() as st:
        got = MeshQueryExecutor(default_mesh(1)).execute(parts_set, sql)
    assert _rows(got) == _rows(want)
    c = st.counters
    assert c.get(qstats.DEVICE_LAUNCHES) == 1
    assert not c.get(qstats.SPARSE_GROUPBY_LAUNCHES)
    cut = q < 4
    assert c.get(qstats.DENSE_TRIMMED_LAUNCHES, 0) == int(cut)
    assert not c.get(qstats.DEVICE_TRIMMED_LAUNCHES)
    assert c.get(qstats.SORTED_MINMAX_LAUNCHES, 0) == int("MIN(" in sql
                                                          or "MAX(" in sql)


def test_sole_servers_partial_is_the_cut_groups(caps, parts_set):
    """A partial the broker routed here alone ships the k groups in the
    sorted groups' form, trimmed; one that is not whole ships the table."""
    sql = TOPN[1].format(d=40)
    ex = MeshQueryExecutor(default_mesh(1))
    for sole in (True, False):
        ctx = compile_query(sql, SCHEMA)
        if sole:
            ctx.options[SOLE_SERVER] = True
        p = ex.prepare_partial(ctx, parts_set, parts_set)
        assert kernels.dense_trimmed(p.spec) == sole
        assert kernels.sorted_minmax(p.spec)
        (packed, finish, _, _), = ex.dispatch_prepared([p])
        res = p.decode(finish(ex.fetch([packed])[0])[0])
        if sole:
            assert res.sparse.trimmed and len(res.sparse.counts) == 100
            assert res.dense is None
        else:
            assert res.sparse is None


def test_a_mesh_of_four_adds_the_tables_uncut(caps, parts_set):
    """Four chips' tables are added after the kernel: no chip cuts its own,
    and the answer is the host's."""
    sql = TOPN[1].format(d=40)
    ctx = compile_query(sql, SCHEMA)
    assert sort_trim_spec(ctx, plan_segment(ctx, parts_set[0]), 4) == ()
    with qstats.collect_stats() as st:
        got = MeshQueryExecutor(default_mesh(4)).execute(parts_set, sql)
    assert not st.counters.get(qstats.DENSE_TRIMMED_LAUNCHES)
    assert st.counters.get(qstats.SORTED_MINMAX_LAUNCHES)
    assert _rows(got) == _rows(
        ServerQueryExecutor(use_device=False).execute(parts_set, sql))


def test_a_stacked_burst_cuts_each_query(caps, parts_set):
    """Three same-shape Top-Ns that differ in a literal ride ONE stacked
    launch (the server's default): each query's table is cut inside the
    batch's scan, and each answer is the host's."""
    from pinot_tpu.query.aggregates import make_agg
    from pinot_tpu.query.reduce import merge_segment_results, reduce_to_result
    sqls = [TOPN[1].format(d=d) for d in (20, 45, 80)]
    ex = MeshQueryExecutor(default_mesh(1))
    ctxs = [compile_query(sql, SCHEMA) for sql in sqls]
    for ctx in ctxs:
        ctx.options[SOLE_SERVER] = True
    preps = [ex.prepare_partial(ctx, parts_set, parts_set) for ctx in ctxs]
    with qstats.collect_stats() as st:
        launches = ex.dispatch_prepared(preps)
        assert len(launches) == 1, "same-signature burst must stack"
        outs_dev, finish, idxs, _ = launches[0]
        outs_list = finish(ex.fetch([outs_dev])[0])
    assert int(st.counters.get(qstats.DEVICE_LAUNCHES, 0)) == 1
    host = ServerQueryExecutor(use_device=False)
    for pos, i in enumerate(idxs):
        part = preps[i].decode(outs_list[pos])
        assert part.sparse.trimmed and len(part.sparse.counts) == 100
        aggs = [make_agg(f) for f in ctxs[i].aggregations]
        got = reduce_to_result(ctxs[i], merge_segment_results([part], aggs),
                               aggs, list(ctxs[i].group_by))
        assert _rows(got) == _rows(host.execute(parts_set, sqls[i])), sqls[i]
