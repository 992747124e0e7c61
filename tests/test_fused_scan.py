"""Differential suite for fused single-launch execution on compressed forms.

Three-way differential per query shape: the FUSED plan (single launch,
in-register dict/FOR decode), the STAGED plan (mask launch + aggregate launch
over decoded columns), and the host f64 oracle. Fused and staged run the same
f32 kernel regimes over the same row order, so their results must be
BYTE-IDENTICAL — any drift means the compressed-form decode changed a value.
The host comparison carries the usual f32-accumulation tolerance.

Covers the routing matrix: bitmap-only / mixed / NOT filter trees, null-heavy
columns, MV columns (value-column MV forces the staged rung; MV *filters*
stay fused), FOR-int and dict-encoded projections, and the stacked-burst
case where same-signature fused queries share one persistent launch.
"""

import numpy as np
import pytest

from pinot_tpu.engine import kernels
from pinot_tpu.engine.datablock import block_for, release_block
from pinot_tpu.query import stats as qstats
from pinot_tpu.query.executor import ServerQueryExecutor
from pinot_tpu.schema import (DataType, FieldRole, FieldSpec, Schema,
                              dimension, metric)
from pinot_tpu.segment.reader import load_segment
from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig

N = 2400
RNG = np.random.default_rng(20260807)

SCHEMA = Schema("fused", [
    dimension("dim_a"), dimension("dim_b"),
    dimension("dim_i", DataType.INT),
    FieldSpec("tags", DataType.STRING, FieldRole.DIMENSION,
              single_value=False),
    metric("num_for", DataType.INT), metric("num_wide", DataType.INT),
    metric("val_x", DataType.DOUBLE), metric("val_null", DataType.DOUBLE),
])

COLS = {
    "dim_a": [f"a{i}" for i in RNG.integers(0, 8, N)],
    "dim_b": [f"b{i}" for i in RNG.integers(0, 5, N)],
    # dict-encoded int: the fused "dict" value form (in-register LUT gather)
    "dim_i": RNG.integers(0, 40, N).astype(np.int32) * 7,
    "tags": [[f"t{j}" for j in RNG.integers(0, 6, RNG.integers(1, 4))]
             for _ in range(N)],
    # range 200 < 2^8: uint8 FOR deltas vs int16 narrowed raw -> FOR form
    "num_for": RNG.integers(1000, 1200, N).astype(np.int32),
    # range >= 2^16: FOR declined -> raw passthrough stays fused
    "num_wide": RNG.integers(-(1 << 20), 1 << 20, N).astype(np.int32),
    "val_x": np.round(RNG.uniform(-100, 100, N), 3),
    # null-heavy: ~40% nulls through the writer's null bitmap
    "val_null": [None if RNG.random() < 0.4 else
                 round(float(RNG.uniform(0, 50)), 3) for _ in range(N)],
}


@pytest.fixture(scope="module")
def seg(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fused")
    builder = SegmentBuilder(SCHEMA, SegmentGeneratorConfig(
        no_dictionary_columns=["num_for", "num_wide", "val_x", "val_null"]))
    return load_segment(builder.build(
        {k: (v.copy() if isinstance(v, np.ndarray) else list(v))
         for k, v in COLS.items()}, str(tmp), "fused_0"))


QUERIES = [
    # bitmap-only tree (dict IN/EQ leaves), FOR-int + raw projections
    ("SELECT dim_b, COUNT(*), SUM(num_for), MIN(num_wide) FROM fused "
     "WHERE dim_a IN ('a1', 'a2', 'a3') GROUP BY dim_b"),
    # mixed tree: dict leaf AND numeric compare (CmpLeaf value column)
    ("SELECT COUNT(*), SUM(val_x), MAX(num_for) FROM fused "
     "WHERE dim_a = 'a1' AND num_wide > 0"),
    # NOT over a compare, OR with a dict leaf
    ("SELECT dim_a, COUNT(*), SUM(num_for) FROM fused "
     "WHERE NOT num_for < 1100 OR dim_b = 'b2' GROUP BY dim_a"),
    # null-heavy value column: null rows drop out of SUM/COUNT identically
    ("SELECT dim_b, COUNT(val_null), SUM(val_null) FROM fused "
     "WHERE dim_a <> 'a0' GROUP BY dim_b"),
    # dict-encoded INT projection: the "dict" fused form feeds the aggregate
    ("SELECT dim_a, SUM(dim_i), MAX(dim_i) FROM fused "
     "WHERE num_for BETWEEN 1050 AND 1150 GROUP BY dim_a"),
    # MV filter (stacked id matrix) + SV aggregate: fused handles MV LUT
    # leaves — only MV *value* columns force the staged rung
    ("SELECT COUNT(*), SUM(num_for) FROM fused WHERE tags = 't1'"),
    # match-all: staged collapses to one launch, fused still one
    "SELECT SUM(num_wide), AVG(val_x) FROM fused",
]


def _rows(res):
    return sorted([tuple(r) for r in res.rows], key=lambda r: str(r))


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_fused_vs_staged_byte_identical_vs_host(seg, qi):
    sql = QUERIES[qi]
    fused = ServerQueryExecutor(fused_enabled=True).execute([seg], sql)
    staged = ServerQueryExecutor(fused_enabled=False).execute([seg], sql)
    host = ServerQueryExecutor(use_device=False).execute([seg], sql)
    fr, sr, hr = _rows(fused), _rows(staged), _rows(host)
    assert fr == sr, f"fused != staged (byte-identical contract)\n{sql}"
    assert len(fr) == len(hr), sql
    for frow, hrow in zip(fr, hr):
        for fv, hv in zip(frow, hrow):
            if isinstance(fv, float) and isinstance(hv, float):
                assert fv == pytest.approx(hv, rel=1e-5, abs=0.05), sql
            else:
                assert fv == hv, sql


def test_fused_launch_count_halves_staged(seg):
    """Filter+aggregate: fused = 1 device launch, staged = 2 (mask +
    aggregate) — the >=2x launch-count reduction the issue pins."""
    sql = ("SELECT dim_b, COUNT(*), SUM(num_for) FROM fused "
           "WHERE dim_a = 'a1' AND num_wide > 0 GROUP BY dim_b")
    ServerQueryExecutor(fused_enabled=True).execute([seg], sql)   # warm jit
    ServerQueryExecutor(fused_enabled=False).execute([seg], sql)
    with qstats.collect_stats() as st_f:
        ServerQueryExecutor(fused_enabled=True).execute([seg], sql)
    with qstats.collect_stats() as st_s:
        ServerQueryExecutor(fused_enabled=False).execute([seg], sql)
    f_launches = int(st_f.counters.get(qstats.DEVICE_LAUNCHES, 0))
    s_launches = int(st_s.counters.get(qstats.DEVICE_LAUNCHES, 0))
    assert f_launches == 1, st_f.counters
    assert s_launches == 2, st_s.counters
    assert int(st_f.counters.get(qstats.FUSED_LAUNCHES, 0)) == 1
    assert int(st_s.counters.get(qstats.STAGED_LAUNCHES, 0)) == 2


def test_mv_value_column_degrades_to_staged(seg):
    """An MV aggregate argument cannot ride the fused forms; the plan must
    take the staged rung (or host), never a wrong fused answer."""
    sql = "SELECT COUNT(tags) FROM fused WHERE dim_a = 'a1'"
    ex = ServerQueryExecutor(fused_enabled=True)
    host = ServerQueryExecutor(use_device=False)
    got = ex.execute([seg], sql)
    want = host.execute([seg], sql)
    assert _rows(got) == _rows(want)


@pytest.mark.parametrize("kind", ["server", "mesh"])
def test_table_over_fused_lut_cap_is_not_fused(seg, kind):
    """The other input only the staged path runs, decided from the plan alone:
    a dict value column whose padded decode table (64 entries for `dim_i`'s
    40) is over `fused_lut_cap` is not routed fused — the server executor
    takes the two staged launches, the mesh keeps the decoded column — and
    the answer is the fused one's, byte for byte."""
    from dataclasses import replace

    from pinot_tpu.engine.caps import get_caps, set_caps
    from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
    from pinot_tpu.query.context import compile_query
    sql = ("SELECT dim_a, SUM(dim_i), COUNT(*) FROM fused "
           "WHERE num_for BETWEEN 1050 AND 1150 GROUP BY dim_a")
    make = (ServerQueryExecutor if kind == "server"
            else lambda: MeshQueryExecutor(default_mesh(1)))

    def run():
        """(rows, the launch counters, the mesh spec's fused columns)."""
        ex = make()
        with qstats.collect_stats() as st:
            rows = _rows(ex.execute([seg], sql))
        routed = (ex.prepare_partial(compile_query(sql, seg.schema),
                                     [seg]).spec.fused_cols
                  if kind == "mesh" else None)
        return rows, {k: int(st.counters.get(k, 0)) for k in (
            qstats.FUSED_LAUNCHES, qstats.STAGED_LAUNCHES)}, routed

    fused, launches_f, routed_f = run()
    prev = get_caps()
    set_caps(replace(prev, fused_lut_cap=32))
    try:
        over, launches_s, routed_s = run()
    finally:
        set_caps(prev)
        release_block(seg)
    assert over == fused
    if kind == "server":
        assert launches_f == {"fusedLaunches": 1, "stagedLaunches": 0}
        assert launches_s == {"fusedLaunches": 0, "stagedLaunches": 2}
    else:
        assert routed_f == (("dim_i", "dict"),) and routed_s == ()


def test_for_form_eligibility(seg):
    """num_for (range 200, int16 raw) carries a FOR form; num_wide (range
    2^21) and the doubles do not."""
    block = block_for(seg)
    try:
        ff = block.for_form("num_for")
        assert ff is not None
        base, deltas = ff
        assert base == int(np.min(COLS["num_for"]))
        assert np.asarray(deltas).dtype == np.uint8
        assert block.for_form("num_wide") is None
        assert block.for_form("val_x") is None
    finally:
        release_block(seg)


def test_fused_spec_routes_expected_forms(seg):
    """The executor's routing decision itself: dict-SV value cols -> "dict",
    FOR-eligible raw ints -> "for", wide raw ints -> passthrough."""
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.planner import plan_segment
    ex = ServerQueryExecutor(fused_enabled=True)
    ctx = compile_query(
        "SELECT SUM(dim_i), SUM(num_for), SUM(num_wide) FROM fused "
        "WHERE val_x > 0", seg.schema)
    plan = plan_segment(ctx, seg)
    assert plan.kind == "device"
    block = block_for(seg)
    try:
        routed = dict(ex._fused_cols(plan, seg, block))
        assert routed.get("dim_i") == "dict"
        assert routed.get("num_for") == "for"
        assert "num_wide" not in routed      # raw passthrough
        assert "val_x" not in routed         # raw float passthrough
    finally:
        release_block(seg)


def test_stacked_burst_one_launch_byte_identical(seg):
    """A burst of same-signature fused queries (different scalars) rides ONE
    stacked persistent launch; each answer matches its solo staged execution
    and the burst uses strictly fewer device launches than staged (which
    needs two per query)."""
    from pinot_tpu.parallel.combine import MeshQueryExecutor
    from pinot_tpu.query.aggregates import make_agg
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.reduce import (merge_segment_results,
                                        reduce_to_result)
    thresholds = (0, 100_000, -250_000, 500_000)
    # dim_i is dict-encoded: SUM(dim_i) rides the mesh fused "dict" form
    sqls = [("SELECT COUNT(*), SUM(dim_i) FROM fused "
             f"WHERE num_wide > {t}") for t in thresholds]
    mex = MeshQueryExecutor()
    ctxs = [compile_query(sql, seg.schema) for sql in sqls]
    preps = [mex.prepare_partial(ctx, [seg]) for ctx in ctxs]
    assert all(p is not None for p in preps)
    assert any(p.spec.fused_cols for p in preps), \
        "burst should ride the fused compressed forms"
    # same signature + same block -> one stack key -> ONE batched launch
    with qstats.collect_stats() as st:
        launches = mex.dispatch_prepared(preps)
        assert len(launches) == 1, "same-signature burst must stack"
        outs_dev, finish, idxs, _ = launches[0]
        assert sorted(idxs) == list(range(len(sqls)))
        outs_list = finish(mex.fetch([outs_dev])[0])
    burst_launches = int(st.counters.get(qstats.DEVICE_LAUNCHES, 0))
    assert burst_launches == 1, st.counters
    assert int(st.counters.get(qstats.FUSED_LAUNCHES, 0)) == 1

    staged = ServerQueryExecutor(fused_enabled=False)
    for pos, i in enumerate(idxs):
        partial = preps[i].decode(outs_list[pos])
        aggs = [make_agg(f) for f in ctxs[i].aggregations]
        got = reduce_to_result(
            ctxs[i], merge_segment_results([partial], aggs), aggs, []).rows
        want = staged.execute([seg], sqls[i])
        assert sorted(map(tuple, got)) == _rows(want), sqls[i]


def test_staged_spec_reuses_match_all_single_launch(seg):
    """A match-all filter needs no mask launch: staged executes in ONE
    launch and records stagedLaunches=1."""
    sql = "SELECT SUM(num_for), COUNT(*) FROM fused"
    ex = ServerQueryExecutor(fused_enabled=False)
    ex.execute([seg], sql)                     # warm
    with qstats.collect_stats() as st:
        ex.execute([seg], sql)
    assert int(st.counters.get(qstats.DEVICE_LAUNCHES, 0)) == 1
    assert int(st.counters.get(qstats.STAGED_LAUNCHES, 0)) == 1


def test_fused_signature_distinct_from_staged(seg):
    """fused_cols participates in KernelSpec.signature(): fused and staged
    plans must never share a jit cache entry."""
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.planner import plan_segment
    ctx = compile_query(
        "SELECT SUM(num_for) FROM fused WHERE dim_a = 'a1'", seg.schema)
    plan = plan_segment(ctx, seg)
    block = block_for(seg)
    try:
        ex = ServerQueryExecutor(fused_enabled=True)
        fused_cols = ex._fused_cols(plan, seg, block)
        assert fused_cols  # num_for routes as ("num_for", "for")
        spec_fused = kernels.KernelSpec(
            plan.filter_prog, (), 1, (), {}, block.padded,
            fused_cols=fused_cols)
        spec_staged = kernels.KernelSpec(
            plan.filter_prog, (), 1, (), {}, block.padded)
        assert spec_fused.signature() != spec_staged.signature()
    finally:
        release_block(seg)


# -- small decode tables: selects, not a gather (PR 27) ---------------------

CAP = kernels.SELECT_DECODE_CAP
DECODE_WIDTHS = [16, 64, CAP, 2 * CAP]
#: value ranges a dictionary's entries span once narrowed for the device
#: (negatives in each); the SUM of 640 rows stays exact in f32 for the first two
VALUE_RANGES = {"int8": (-128, 127), "int16": (-20000, 20000),
                "int32": (-(1 << 31), (1 << 31) - 1)}
DEC_ROWS = 640


def _card_for(width: int) -> int:
    """A cardinality whose padded decode table (`lut_size`) is `width` wide."""
    from pinot_tpu.engine.datablock import lut_size
    card = width // 2
    assert lut_size(card) == width
    return card


def _decode_segments(tmp, tag, width, values, n_segs):
    """`n_segs` segments of one dict-encoded INT column `d` (each segment its
    OWN dictionary of `_card_for(width)` values drawn from the range, so the
    stacked per-segment tables differ), a filter column and a group column
    whose dictionaries agree across segments."""
    lo, hi = VALUE_RANGES[values]
    card = _card_for(width)
    schema = Schema("dec", [dimension("g"), dimension("d", DataType.INT),
                            metric("w", DataType.INT)])
    builder = SegmentBuilder(schema, SegmentGeneratorConfig(
        no_dictionary_columns=["w"]))
    segs = []
    for i in range(n_segs):
        rng = np.random.default_rng([27, width, i, len(values)])
        dvals = rng.choice(np.arange(lo, hi + 1, max(1, (hi - lo) // 4096),
                                     dtype=np.int64),
                           size=card, replace=False).astype(np.int32)
        d = dvals[np.arange(DEC_ROWS) % card]          # every entry is read
        rng.shuffle(d)
        cols = {"g": [f"g{j % 3}" for j in range(DEC_ROWS)], "d": d,
                "w": rng.integers(-50, 50, DEC_ROWS).astype(np.int32)}
        segs.append(load_segment(builder.build(
            {k: (v.copy() if isinstance(v, np.ndarray) else list(v))
             for k, v in cols.items()}, str(tmp), f"{tag}_{i}")))
    return segs


def _clear_kernel_caches():
    from pinot_tpu.parallel import combine
    kernels._KERNEL_CACHE.clear()
    combine._SHARD_KERNEL_CACHE.clear()


@pytest.fixture
def decode_cap(monkeypatch):
    """Set `SELECT_DECODE_CAP` for a block of the test: the cap is read at
    trace time and is not part of any kernel cache key (it is a constant of
    the program), so the caches are emptied around each change."""
    def set_cap(cap):
        _clear_kernel_caches()
        monkeypatch.setattr(kernels, "SELECT_DECODE_CAP", cap)
    yield set_cap
    _clear_kernel_caches()


def _served_rows(mex, segs, sqls):
    """The served path of the mesh executor (what the device pipeline
    drives): prepare each query, launch them together, fetch, decode and
    reduce. Returns (rows per query, the launches)."""
    from pinot_tpu.query.aggregates import make_agg
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.reduce import (merge_segment_results,
                                        reduce_to_result)
    ctxs = [compile_query(sql, segs[0].schema) for sql in sqls]
    preps = [mex.prepare_partial(ctx, segs) for ctx in ctxs]
    assert all(p is not None for p in preps)
    launches = mex.dispatch_prepared(preps)
    rows = [None] * len(sqls)
    for outs_dev, finish, idxs, _ in launches:
        outs = finish(mex.fetch([outs_dev])[0])
        for pos, i in enumerate(idxs):
            aggs = [make_agg(f) for f in ctxs[i].aggregations]
            partial = preps[i].decode(outs[pos])
            rows[i] = _rows(reduce_to_result(
                ctxs[i], merge_segment_results([partial], aggs), aggs,
                list(ctxs[i].group_by)))
    return rows, launches, preps


def _execute(form, segs, sql, **kw):
    if form == "1d":
        return _rows(ServerQueryExecutor(**kw).execute(segs, sql))
    from pinot_tpu.parallel.combine import MeshQueryExecutor
    return _served_rows(MeshQueryExecutor(**kw), segs, [sql])[0][0]


@pytest.mark.parametrize("values", list(VALUE_RANGES))
@pytest.mark.parametrize("form", ["1d", "stacked"])
@pytest.mark.parametrize("width", DECODE_WIDTHS)
def test_small_table_decode_select_gather_staged_host_identical(
        tmp_path, decode_cap, width, form, values):
    """A fused dict column decoded by selects, by the gather, staged (decoded
    in HBM) and on the host gives the same rows, at each table width, for the
    direct executor's 1-D table and the mesh path's per-segment tables."""
    segs = _decode_segments(tmp_path, f"{form}{width}{values}", width,
                               values, 1 if form == "1d" else 3)
    sqls = ["SELECT COUNT(*), MIN(d), MAX(d), SUM(d) FROM dec WHERE w > -20",
            "SELECT g, COUNT(*), MIN(d), MAX(d), SUM(d) FROM dec "
            "WHERE w < 30 GROUP BY g"]
    small = width <= CAP
    for sql in sqls:
        decode_cap(CAP)                     # the program's own choice
        with qstats.collect_stats() as st_rule:
            by_rule = _execute(form, segs, sql, fused_enabled=True)
        decode_cap(0 if small else 1 << 20)  # the decode it did not choose
        with qstats.collect_stats() as st_other:
            by_other = _execute(form, segs, sql, fused_enabled=True)
        staged = _execute(form, segs, sql, fused_enabled=False)
        host = _rows(ServerQueryExecutor(use_device=False).execute(segs, sql))
        assert by_rule == by_other == staged, (sql, width)
        # the counts and the int32 MIN / MAX are exact on every path; SUM is
        # f32 on the device, exact while every partial sum is under 2^24
        assert len(by_rule) == len(host)
        for drow, hrow in zip(by_rule, host):
            assert drow[:-1] == hrow[:-1], (sql, width)
            if values == "int32":
                assert drow[-1] == pytest.approx(hrow[-1], rel=1e-5)
            else:
                assert drow[-1] == hrow[-1], (sql, width)
        # the count says which decode a launch rode
        for st, by_select in ((st_rule, small), (st_other, not small)):
            assert int(st.counters.get(qstats.FUSED_LAUNCHES, 0)) == 1
            assert int(st.counters.get(qstats.GATHER_FREE_LAUNCHES, 0)) == \
                int(by_select), (st.counters, width)


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32, np.float32])
@pytest.mark.parametrize("stacked", [False, True], ids=["1d", "stacked"])
@pytest.mark.parametrize("width", DECODE_WIDTHS)
def test_decode_select_is_the_gather_value_for_value(width, stacked, dtype):
    """`_decode_select` against `_decode_gather` and numpy: same values, same
    dtype, the fill id (= cardinality, which reads the table's padded zero)
    and every other id of the table included."""
    import jax.numpy as jnp
    rng = np.random.default_rng([width, stacked, np.dtype(dtype).itemsize])
    card = _card_for(width)
    shape = (3, width) if stacked else (width,)
    if np.dtype(dtype).kind == "f":
        lut = rng.normal(0, 1e6, shape).astype(dtype)
    else:
        info = np.iinfo(dtype)
        lut = rng.integers(info.min, info.max, shape, dtype=dtype,
                           endpoint=True)
    lut[..., card:] = 0                      # the padding the fill id reads
    rows = 4 * width
    ids = np.concatenate([np.arange(width), np.full(width, card),
                          rng.integers(0, card + 1, rows - 2 * width)]
                         ).astype(np.int32)
    if stacked:
        ids = np.stack([rng.permutation(ids) for _ in range(3)])
        want = np.take_along_axis(lut, ids, axis=1)
    else:
        want = lut[ids]
    got_s = kernels._decode_select(jnp.asarray(lut), jnp.asarray(ids))
    got_g = kernels._decode_gather(jnp.asarray(lut), jnp.asarray(ids))
    assert got_s.dtype == got_g.dtype == want.dtype
    assert got_s.shape == got_g.shape == want.shape
    assert np.asarray(got_s).tobytes() == np.asarray(got_g).tobytes() \
        == want.tobytes()


@pytest.mark.parametrize("values", list(VALUE_RANGES))
def test_stacked_b2_launch_of_small_table_plan_agrees(tmp_path, decode_cap,
                                                      values):
    """Two same-shape queries over per-segment 16-entry tables ride ONE
    stacked (`_b2`) launch that decodes by selects; each answer is its solo
    gather-decoded answer, and the launch counts as gather-free once."""
    from pinot_tpu.parallel.combine import MeshQueryExecutor
    segs = _decode_segments(tmp_path, f"b2{values}", 16, values, 3)
    sqls = [f"SELECT COUNT(*), MIN(d), MAX(d), SUM(d) FROM dec WHERE w > {t}"
            for t in (-20, 10)]
    decode_cap(CAP)
    with qstats.collect_stats() as st:
        got, launches, preps = _served_rows(MeshQueryExecutor(), segs, sqls)
    assert all(p.spec.fused_cols for p in preps)
    assert preps[0].inputs["vals"]["d"].shape == (preps[0].s_pad, 16)
    assert len(launches) == 1 and sorted(launches[0][2]) == [0, 1]
    assert int(st.counters.get(qstats.DEVICE_LAUNCHES, 0)) == 1
    assert int(st.counters.get(qstats.GATHER_FREE_LAUNCHES, 0)) == 1
    assert launches[0][3].get(qstats.GATHER_FREE_LAUNCHES) == 1
    decode_cap(0)
    for sql, rows in zip(sqls, got):
        assert rows == _execute("stacked", segs, sql), sql
