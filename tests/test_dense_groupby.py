"""High-cardinality group-by: the chunked 64x64 kernel path + dense decode.

Covers the r5 redesign (VERDICT r4 #2): cardinalities ABOVE the skinny
matmul cap take `_grouped_chunk64` (engine/kernels.py), and full results on
the mesh path decode through the vectorized `query/dense_reduce.py` instead
of the per-group state loop. Differentials pin both against the host
(numpy) engine. Reference behavior:
DictionaryBasedGroupKeyGenerator.java:62 + GroupByDataTableReducer.java.
"""

import numpy as np
import pytest

from pinot_tpu.engine.kernels import CHUNK_KEY_CAP, MATMUL_KEY_CAP
from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
from pinot_tpu.query.executor import ServerQueryExecutor
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.segment import load_segment
from pinot_tpu.segment.writer import (SegmentGeneratorConfig,
                                      build_aligned_segments)

N_KEYS = 2500  # > MATMUL_KEY_CAP -> the chunked kernel branch
ROWS = 60_000


@pytest.fixture(scope="module")
def hc_schema():
    return Schema("hc", [
        dimension("k", DataType.INT),
        dimension("tag", DataType.STRING),
        metric("v", DataType.DOUBLE),
        metric("q", DataType.INT),
    ])


@pytest.fixture(scope="module")
def hc_cols():
    rng = np.random.default_rng(42)
    return {
        "k": rng.integers(0, N_KEYS, ROWS).astype(np.int32),
        "tag": [f"t{i}" for i in rng.integers(0, 7, ROWS)],
        "v": np.round(rng.uniform(-1000.0, 60_000.0, ROWS), 2),
        "q": rng.integers(1, 100, ROWS).astype(np.int32),
    }


@pytest.fixture(scope="module")
def hc_segments(tmp_path_factory, hc_schema, hc_cols):
    out = tmp_path_factory.mktemp("hc_aligned")
    paths = build_aligned_segments(hc_schema, hc_cols, str(out), "hc", 4)
    return [load_segment(p) for p in paths]


@pytest.fixture(scope="module")
def mesh_exec():
    return MeshQueryExecutor(default_mesh(4))


def test_bf16_split_is_exact_and_survives_the_tpu_compiler():
    """The 3-part bf16 split carries full f32 precision, and rounds with
    `reduce_precision`: on the v5e a convert round trip is kept in excess
    precision inside the fusion and the residual parts vanish (PR 22: 20k-key
    sums off by up to 5e-4 relative on the chip, exact on the CPU)."""
    import jax
    import jax.numpy as jnp
    from pinot_tpu.engine.kernels import _bf16_parts
    v = jnp.asarray(np.round(np.random.default_rng(5).uniform(
        1.0, 60_000.0, 4096), 2).astype(np.float32))
    parts = _bf16_parts(v)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    total = sum(np.asarray(p, dtype=np.float64) for p in parts)
    assert np.max(np.abs(total - np.asarray(v, np.float64))
                  / np.asarray(v, np.float64)) <= 2.0 ** -23
    jaxpr = str(jax.make_jaxpr(_bf16_parts)(v))
    assert jaxpr.count("reduce_precision") == 3


@pytest.mark.parametrize("n", [3 * 4096, 1 << 17, 5 * (1 << 16)])
def test_onehot_sums_matches_numpy(n):
    """The skinny one-hot matmul in its three-part bf16 form (on the v5e one
    f32 HIGHEST contraction over 16Mi rows came back 6e-4 low, PR 22): counts
    exact, sums to f32, and no HIGHEST-precision dot in the program."""
    import jax.numpy as jnp
    from pinot_tpu.engine.kernels import _onehot_sums
    rng = np.random.default_rng(n)
    key = rng.integers(0, 7, n).astype(np.int32)
    val = np.round(rng.uniform(1.0, 60_000.0, n), 2).astype(np.float32)
    got = np.asarray(_onehot_sums(jnp.asarray(key), 7,
                                  [jnp.ones(n, jnp.float32),
                                   jnp.asarray(val)]), dtype=np.float64)
    assert got.shape == (2, 7)
    import jax
    jaxpr = str(jax.make_jaxpr(lambda k, v: _onehot_sums(k, 7, [v]))(
        jnp.asarray(key), jnp.asarray(val)))
    assert "HIGHEST" not in jaxpr and jaxpr.count("dot_general") == 3
    np.testing.assert_array_equal(got[0], np.bincount(key, minlength=7))
    np.testing.assert_allclose(
        got[1], np.bincount(key, weights=val.astype(np.float64), minlength=7),
        rtol=1e-6)


def test_cap_structure():
    assert MATMUL_KEY_CAP < N_KEYS + 1 <= CHUNK_KEY_CAP


HC_QUERIES = [
    # the BASELINE config-5 shape: high-card key, SUM + COUNT
    "SELECT k, SUM(v), COUNT(*) FROM hc GROUP BY k LIMIT 100000",
    # filter + avg/min/max riding the same chunked kernel
    "SELECT k, AVG(v), MIN(q), MAX(q) FROM hc WHERE q < 50 GROUP BY k "
    "ORDER BY k LIMIT 100000",
    # ORDER BY an aggregation, desc, with offset
    "SELECT k, SUM(v) FROM hc GROUP BY k ORDER BY SUM(v) DESC LIMIT 50",
    # variance family over the chunked power sums
    "SELECT k, VARPOP(q), STDDEVPOP(q) FROM hc GROUP BY k ORDER BY k "
    "LIMIT 100000",
]


@pytest.mark.parametrize("sql", HC_QUERIES)
def test_chunked_kernel_matches_host(hc_segments, mesh_exec, sql):
    dev = mesh_exec.execute(hc_segments, sql)
    host = ServerQueryExecutor(use_device=False).execute(hc_segments, sql)
    assert len(dev.rows) == len(host.rows)
    dev_rows, host_rows = dev.rows, host.rows
    if "ORDER BY" not in sql:
        # without ORDER BY row order is unspecified (host: first-seen merge
        # order; dense decode: key order) — compare as sets keyed on col 0
        dev_rows = sorted(dev_rows, key=lambda r: r[0])
        host_rows = sorted(host_rows, key=lambda r: r[0])
    for dr, hr in zip(dev_rows, host_rows):
        assert len(dr) == len(hr)
        for dv, hv in zip(dr, hr):
            if isinstance(dv, float) and isinstance(hv, float):
                assert abs(dv - hv) <= 2e-3 * max(1.0, abs(hv)), (dr, hr)
            else:
                assert dv == hv, (dr, hr)


def test_dense_decode_is_used(hc_segments, mesh_exec):
    res = mesh_exec.execute(hc_segments,
                            "SELECT k, SUM(v), COUNT(*) FROM hc GROUP BY k "
                            "LIMIT 100000")
    assert res.stats.get("denseReduce") is True
    assert res.stats["numGroups"] == N_KEYS
    # exact differential against raw numpy
    got = {r[0]: (r[1], r[2]) for r in res.rows}
    assert sum(c for _, c in got.values()) == ROWS


def test_dense_decode_order_and_limit(hc_segments, mesh_exec, hc_cols):
    res = mesh_exec.execute(hc_segments,
                            "SELECT k, SUM(v) FROM hc GROUP BY k "
                            "ORDER BY SUM(v) DESC LIMIT 7")
    assert len(res.rows) == 7
    sums = np.zeros(N_KEYS)
    np.add.at(sums, hc_cols["k"], hc_cols["v"])
    want = np.argsort(-sums)[:7]
    got = [r[0] for r in res.rows]
    assert got == [int(w) for w in want]
    for r in res.rows:
        assert abs(r[1] - sums[r[0]]) < 2e-3 * max(1.0, abs(sums[r[0]]))


def test_dense_decode_string_group_order(hc_segments, mesh_exec):
    """ORDER BY a string group column: dict-id sort must equal value sort."""
    res = mesh_exec.execute(hc_segments,
                            "SELECT tag, COUNT(*) FROM hc GROUP BY tag "
                            "ORDER BY tag DESC LIMIT 10")
    tags = [r[0] for r in res.rows]
    assert tags == sorted(tags, reverse=True)


def test_dense_orderby_null_ranking_matches_host(tmp_path_factory, mesh_exec):
    """Differential lock on ORDER BY null ranking: groups whose aggregation is
    null (every input cell null) must land in the same positions on the dense
    decode as on the classic host reduce, for every desc/nulls combination —
    the dense lexsort ranks NaN-as-null exactly like reduce._sort_key."""
    rng = np.random.default_rng(7)
    rows, card = 4000, 60
    schema = Schema("nul", [dimension("k", DataType.INT),
                            metric("v", DataType.DOUBLE)])
    k = rng.integers(0, card, rows).astype(np.int64)
    v = np.round(rng.uniform(-100, 100, rows), 3).astype(object)
    v[k < 6] = None            # six all-null groups -> null SUM(v)
    out = tmp_path_factory.mktemp("nulorder")
    cfg = SegmentGeneratorConfig(raw_cardinality_fraction=4.0,
                                 no_dictionary_columns=["v"])
    paths = build_aligned_segments(schema, {"k": k, "v": v}, str(out),
                                   "nul", 4, config=cfg)
    segs = [load_segment(p) for p in paths]
    host = ServerQueryExecutor(use_device=False)
    for suffix in ("", " DESC", " NULLS FIRST", " NULLS LAST",
                   " DESC NULLS FIRST", " DESC NULLS LAST"):
        sql = (f"SELECT k, SUM(v) FROM nul GROUP BY k "
               f"ORDER BY SUM(v){suffix}, k LIMIT 100")
        dev = mesh_exec.execute(segs, sql)
        want = host.execute(segs, sql)
        assert dev.stats.get("denseReduce") is True, sql
        assert [r[0] for r in dev.rows] == [r[0] for r in want.rows], sql
        for dr, wr in zip(dev.rows, want.rows):
            if wr[1] is None:
                assert dr[1] is None, sql
            else:
                assert abs(dr[1] - wr[1]) <= 2e-3 * max(1.0, abs(wr[1])), sql


def test_grouped_distinct_chunked(hc_segments, mesh_exec, hc_cols):
    """Grouped DISTINCTCOUNT: the presence matrix rides _grouped_chunk64 when
    the (groups x ids) product space fits the chunk cap."""
    res = mesh_exec.execute(hc_segments,
                            "SELECT tag, DISTINCTCOUNT(q) FROM hc "
                            "GROUP BY tag ORDER BY tag LIMIT 10")
    ks = np.asarray(hc_cols["tag"])
    qs = np.asarray(hc_cols["q"])
    for tag, got in res.rows:
        assert got == len(np.unique(qs[ks == tag]))


def _norm(rows):
    out = []
    for r in rows:
        vals = []
        for v in r:
            if isinstance(v, float):
                vals.append(float(f"{v:.5g}"))
            else:
                vals.append(v)
        out.append(tuple(vals))
    return out


def _assert_rows_match(dev_rows, host_rows, ctxmsg):
    assert len(dev_rows) == len(host_rows), ctxmsg
    for dr, hr in zip(dev_rows, host_rows):
        assert len(dr) == len(hr), (ctxmsg, dr, hr)
        for dv, hv in zip(dr, hr):
            if isinstance(dv, float) and isinstance(hv, float):
                assert abs(dv - hv) <= 2e-3 * max(1.0, abs(hv)),                     (ctxmsg, dr, hr)
            else:
                assert dv == hv, (ctxmsg, dr, hr)


# one card per kernel regime: skinny matmul (<=512), chunked 64x64 (two
# points), and — via the g*k combined key space — past the chunk cap
@pytest.mark.parametrize("card", [300, 700, 5000, 40_000])
def test_groupby_fuzz_across_cap_regimes(tmp_path_factory, mesh_exec, card):
    """Seeded fuzz of GROUP BY across the three kernel regimes, with
    filters, agg mixes, and order/limit shapes — differential against the
    host engine."""
    seed = card % 97
    rng = np.random.default_rng(1000 + seed)
    rows = 30_000
    schema = Schema(f"fz{seed}", [
        dimension("k", DataType.INT),
        dimension("g", DataType.STRING),
        metric("v", DataType.DOUBLE),
        metric("q", DataType.INT),
    ])
    cols = {
        "k": rng.integers(0, card, rows).astype(np.int32),
        "g": [f"g{i}" for i in rng.integers(0, 6, rows)],
        "v": np.round(rng.uniform(-500, 500, rows), 3),
        "q": rng.integers(0, 1000, rows).astype(np.int32),
    }
    out = tmp_path_factory.mktemp(f"fz{seed}")
    paths = build_aligned_segments(schema, cols, str(out), f"fz{seed}", 4)
    segs = [load_segment(p) for p in paths]
    host = ServerQueryExecutor(use_device=False)
    shapes = [
        f"SELECT k, COUNT(*), SUM(v) FROM fz{seed} GROUP BY k "
        f"ORDER BY k LIMIT 100000",
        f"SELECT k, AVG(v), MIN(q), MAX(q) FROM fz{seed} WHERE q < 500 "
        f"GROUP BY k ORDER BY k LIMIT 100000",
        # multi-column group: the combined key space k*6 can cross caps
        f"SELECT g, k, SUM(v) FROM fz{seed} WHERE q >= 250 GROUP BY g, k "
        f"ORDER BY g, k LIMIT 100000",
        # the k tiebreak pins rank order when adjacent sums differ by
        # less than cross-engine float error
        f"SELECT k, SUM(v) FROM fz{seed} GROUP BY k "
        f"ORDER BY SUM(v) DESC, k LIMIT 13",
        f"SELECT g, VARPOP(v), COUNT(*) FROM fz{seed} GROUP BY g "
        f"ORDER BY g LIMIT 10",
    ]
    for sql in shapes:
        dev = mesh_exec.execute(segs, sql)
        want = host.execute(segs, sql)
        _assert_rows_match(dev.rows, want.rows, sql)


# ---------------------------------------------------------------------------
# very-high-cardinality regimes: radix-partitioned + sort kernels (PR: the
# segment_sum scatter fallback replacement) — differential vs the host engine
# ---------------------------------------------------------------------------

from pinot_tpu.engine.calibrate import KernelCaps, get_caps, set_caps  # noqa: E402


@pytest.fixture(scope="module")
def vhc_segments(tmp_path_factory):
    """6000-key set: padded key space 8192 crosses a FORCED chunk_cap of 4096,
    so the sort-based regimes exercise cheaply in tier-1."""
    rng = np.random.default_rng(7)
    rows = 40_000
    schema = Schema("vhc", [
        dimension("k", DataType.INT),
        metric("v", DataType.DOUBLE),
        metric("q", DataType.INT),
    ])
    cols = {
        "k": rng.integers(0, 6000, rows).astype(np.int32),
        "v": np.round(rng.uniform(-500, 500, rows), 3),
        # group sums cross int32 (the overflow differential)
        "q": rng.integers(0, 1 << 30, rows).astype(np.int32),
    }
    out = tmp_path_factory.mktemp("vhc")
    paths = build_aligned_segments(schema, cols, str(out), "vhc", 4)
    return [load_segment(p) for p in paths]


def _assert_rows_close(dev_rows, host_rows, ctxmsg, rtol=1e-3):
    """Row-for-row match; numerics compare with relative tolerance (device
    sums accumulate in f32 via bf16 splits — int sums come back as floats)."""
    assert len(dev_rows) == len(host_rows), ctxmsg
    for dr, hr in zip(dev_rows, host_rows):
        assert len(dr) == len(hr), (ctxmsg, dr, hr)
        for dv, hv in zip(dr, hr):
            if isinstance(dv, bool) or isinstance(hv, bool) \
                    or not isinstance(dv, (int, float)) \
                    or not isinstance(hv, (int, float)):
                assert dv == hv, (ctxmsg, dr, hr)
            else:
                assert abs(dv - hv) <= rtol * max(1.0, abs(hv)), \
                    (ctxmsg, dr, hr)


VHC_QUERIES = [
    "SELECT k, COUNT(*), SUM(v) FROM vhc GROUP BY k ORDER BY k LIMIT 3000000",
    "SELECT k, SUM(q) FROM vhc GROUP BY k ORDER BY k LIMIT 3000000",
    "SELECT k, AVG(v), MIN(q), MAX(q) FROM vhc WHERE q < 900000000 GROUP BY k "
    "ORDER BY k LIMIT 3000000",
    "SELECT k, SUM(v) FROM vhc GROUP BY k ORDER BY SUM(v) DESC, k LIMIT 17",
]


@pytest.mark.parametrize("regime", ["partitioned", "sorted"])
def test_forced_high_card_regime_matches_host(vhc_segments, mesh_exec, regime):
    """Force chunk_cap below the padded key space so BOTH new sort-based
    kernels run through the full mesh stack, differentially vs the host."""
    host = ServerQueryExecutor(use_device=False)
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096, high_card_regime=regime))
    try:
        for sql in VHC_QUERIES:
            dev = mesh_exec.execute(vhc_segments, sql)
            want = host.execute(vhc_segments, sql)
            _assert_rows_close(dev.rows, want.rows, (regime, sql))
    finally:
        set_caps(prev)


def test_scatter_escape_hatch_matches_host(vhc_segments, mesh_exec):
    """high_card_regime='scatter' keeps the legacy segment_sum path alive."""
    host = ServerQueryExecutor(use_device=False)
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096, high_card_regime="scatter"))
    try:
        sql = VHC_QUERIES[0]
        dev = mesh_exec.execute(vhc_segments, sql)
        want = host.execute(vhc_segments, sql)
        _assert_rows_close(dev.rows, want.rows, ("scatter", sql))
    finally:
        set_caps(prev)


def _guaranteed_card_keys(rng, card, rows):
    """Exactly min(card, rows) distinct keys: one pass of every key, the rest
    random repeats. Pure random draws top out far below the nominal card
    (20k draws from 140k keys hit ~19k uniques) and would silently test the
    WRONG dispatch regime."""
    base = min(card, rows)
    k = np.concatenate([np.arange(base, dtype=np.int64),
                        rng.integers(0, base, rows - base)])
    rng.shuffle(k)
    return k.astype(np.int32)


def _very_high_card_case(tmp_path_factory, card, rows, with_nulls):
    rng = np.random.default_rng(card % 9973)
    schema = Schema("vh", [
        dimension("k", DataType.INT),
        metric("v", DataType.DOUBLE),
        metric("q", DataType.INT),
    ])
    v = np.round(rng.uniform(-500, 500, rows), 3)
    cols = {
        "k": _guaranteed_card_keys(rng, card, rows),
        "v": v,
        "q": rng.integers(0, 1 << 30, rows).astype(np.int32),
    }
    if with_nulls:
        vo = v.astype(object)
        vo[rng.random(rows) < 0.02] = None  # null cells -> NaN-aware aggs
        cols["v"] = vo
    out = tmp_path_factory.mktemp(f"vh{card}")
    # keep k dictionary-encoded even at cardinality ~= rows: the device
    # group-by only rides dict columns, and raw-encoding would demote every
    # query here to the host path (vacuously green differential). Metrics
    # stay raw — the fixed-dict encoder can't represent None cells.
    cfg = SegmentGeneratorConfig(raw_cardinality_fraction=4.0,
                                 no_dictionary_columns=["v", "q"])
    paths = build_aligned_segments(schema, cols, str(out), f"vh{card}", 4,
                                   config=cfg)
    segs = [load_segment(p) for p in paths]
    assert segs[0].column("k").dictionary is not None
    return segs


def _run_very_high_card(tmp_path_factory, mesh_exec, card, rows,
                        with_nulls=False):
    segs = _very_high_card_case(tmp_path_factory, card, rows, with_nulls)
    host = ServerQueryExecutor(use_device=False)
    shapes = [
        f"SELECT k, COUNT(*), SUM(v) FROM vh GROUP BY k "
        f"ORDER BY k LIMIT 3000000",
        f"SELECT k, SUM(q) FROM vh GROUP BY k ORDER BY k LIMIT 3000000",
        f"SELECT k, SUM(v) FROM vh GROUP BY k ORDER BY SUM(v) DESC, k "
        f"LIMIT 23",
    ]
    for sql in shapes:
        dev = mesh_exec.execute(segs, sql)
        want = host.execute(segs, sql)
        _assert_rows_close(dev.rows, want.rows, (card, sql))


def test_partitioned_regime_128k_groups(tmp_path_factory, mesh_exec):
    """Tier-1 anchor of the sweep: 140k REAL groups is past the default
    chunk_cap (131072), so the radix-partitioned kernel is the regime
    actually dispatched."""
    assert get_caps().high_card_regime == "partitioned"
    _run_very_high_card(tmp_path_factory, mesh_exec, 140_000, 160_000)


@pytest.mark.slow
@pytest.mark.parametrize("card,rows", [(500_000, 650_000),
                                       (2_000_000, 2_050_000)])
def test_very_high_card_fuzz_sweep(tmp_path_factory, mesh_exec, card, rows):
    _run_very_high_card(tmp_path_factory, mesh_exec, card, rows)


@pytest.mark.slow
def test_very_high_card_with_nulls(tmp_path_factory, mesh_exec):
    _run_very_high_card(tmp_path_factory, mesh_exec, 140_000, 160_000,
                        with_nulls=True)


def test_dense_partial_roundtrip(vhc_segments, mesh_exec):
    """Server partial at >=4096 groups ships the ARRAY form (DensePartial):
    wire roundtrip + elementwise merge + vectorized broker reduce must equal
    the classic end-to-end result."""
    import jax

    from pinot_tpu.cluster.wire import (decode_segment_result,
                                        encode_segment_result)
    from pinot_tpu.query.aggregates import make_agg
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.reduce import (merge_segment_results,
                                        reduce_to_result)

    sql = ("SELECT k, COUNT(*), SUM(v) FROM vhc GROUP BY k "
           "ORDER BY k LIMIT 3000000")
    schema = vhc_segments[0].schema
    ctx = compile_query(sql, schema)
    halves = [vhc_segments[:2], vhc_segments[2:]]
    partials = []
    for half in halves:
        dp = mesh_exec.dispatch_partial(ctx, half)
        assert dp is not None, "device partial path refused the plan"
        outs_dev, decode = dp
        part = decode(jax.device_get(outs_dev))
        assert part.dense is not None, "expected the array-form partial"
        assert len(part.groups) == 0
        partials.append(part)
    # one partial crosses the wire (server -> broker), one stays local
    partials[0] = decode_segment_result(encode_segment_result(partials[0]))
    assert partials[0].dense is not None
    assert partials[0].dense.token == partials[1].dense.token
    aggs = [make_agg(f) for f in ctx.aggregations]
    merged = merge_segment_results(partials, aggs)
    assert merged.dense is not None, "aligned dense partials must merge dense"
    got = reduce_to_result(ctx, merged, aggs, list(ctx.group_by))
    want = ServerQueryExecutor(use_device=False).execute(vhc_segments, sql)
    _assert_rows_close(got.rows, want.rows, sql)


@pytest.mark.slow
def test_no_flat_scatter_at_high_card(tmp_path_factory):
    """Regression guard: the >=128k-group count+sum kernel must never lower
    through a flat scatter again (the 26.9M rows/s cliff this PR removes)."""
    import jax

    from pinot_tpu.engine import kernels
    from pinot_tpu.engine.datablock import block_for
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.planner import build_device_geometry, plan_segment

    rng = np.random.default_rng(3)
    rows = 150_000
    schema = Schema("sc", [
        dimension("k", DataType.INT),
        metric("v", DataType.DOUBLE),
    ])
    cols = {
        "k": _guaranteed_card_keys(rng, 140_000, rows),
        "v": rng.uniform(0, 10, rows),
    }
    out = tmp_path_factory.mktemp("sc")
    cfg = SegmentGeneratorConfig(raw_cardinality_fraction=4.0)
    paths = build_aligned_segments(schema, cols, str(out), "sc", 1, config=cfg)
    seg = load_segment(paths[0])
    ctx = compile_query("SELECT k, COUNT(*), SUM(v) FROM sc GROUP BY k "
                        "LIMIT 3000000", schema)
    plan = plan_segment(ctx, seg)
    assert plan.kind == "device"
    build_device_geometry(plan)
    assert plan.num_keys_pad > get_caps().chunk_cap
    block = block_for(seg)
    spec = kernels.KernelSpec(plan.filter_prog, plan.group_cols,
                              plan.num_keys_pad,
                              tuple((a, a.device_outputs) for a in plan.aggs),
                              {}, block.padded)
    inputs = ServerQueryExecutor()._kernel_inputs(plan, spec, block)
    body = kernels.make_kernel_body(spec)
    jaxpr = jax.make_jaxpr(body)(
        inputs.ids, inputs.vals, inputs.luts, inputs.iscal, inputs.fscal,
        inputs.nulls, inputs.valid, inputs.strides, inputs.agg_luts,
        inputs.docsets)
    assert "scatter" not in str(jaxpr), \
        ">=128k-group count+sum kernel dispatched through flat scatter"
