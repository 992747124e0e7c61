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

from pinot_tpu.engine.caps import KernelCaps, get_caps, set_caps
from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
from pinot_tpu.query.executor import ServerQueryExecutor
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.segment import load_segment
from pinot_tpu.segment.writer import (SegmentGeneratorConfig,
                                      build_aligned_segments)

N_KEYS = 2500  # > matmul_cap -> the chunked kernel branch
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
    assert KernelCaps().matmul_cap < N_KEYS + 1 <= KernelCaps().chunk_cap


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
# the very-high-cardinality regime: the radix-partitioned sort kernel —
# differential vs the host engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vhc_segments(tmp_path_factory):
    """6000-key set: padded key space 8192 crosses a FORCED chunk_cap of 4096,
    so the sort regime exercises cheaply in tier-1."""
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


def test_forced_sort_regime_matches_host(vhc_segments, mesh_exec):
    """Force chunk_cap below the padded key space so the sort regime runs
    through the full mesh stack, differentially vs the host."""
    host = ServerQueryExecutor(use_device=False)
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096))
    try:
        for sql in VHC_QUERIES:
            dev = mesh_exec.execute(vhc_segments, sql)
            want = host.execute(vhc_segments, sql)
            _assert_rows_close(dev.rows, want.rows, sql)
    finally:
        set_caps(prev)


# --- the compact decode of the sort regime (PR 29) ---------------------------
# 6000 keys pad to 8192 (nseg 8193) and cross a forced chunk_cap of 4096;
# `pos` numbers the rows, so `WHERE pos < m` lets exactly m rows pass.
COMPACT_ROWS = 12_000      # pads to 16,384 rows on one device


@pytest.fixture(scope="module")
def compact_segment(tmp_path_factory):
    rng = np.random.default_rng(29)
    rows = COMPACT_ROWS
    schema = Schema("cd", [
        dimension("k", DataType.INT),
        metric("pos", DataType.INT),
        metric("v", DataType.DOUBLE),
        metric("q", DataType.INT),
    ])
    v = np.round(rng.uniform(-500, 500, rows), 3).astype(object)
    v[rng.random(rows) < 0.02] = None          # a table with nulls
    cols = {
        # runs of ~2 rows a key, so the prefix's last row ends a real group
        "k": rng.integers(0, 6000, rows).astype(np.int32),
        "pos": np.arange(rows, dtype=np.int32),
        "v": v,
        "q": rng.integers(0, 1 << 30, rows).astype(np.int32),
    }
    out = tmp_path_factory.mktemp("cd")
    cfg = SegmentGeneratorConfig(no_dictionary_columns=["pos", "v", "q"])
    paths = build_aligned_segments(schema, cols, str(out), "cd", 1, config=cfg)
    return load_segment(paths[0])


def _compact_sql(seg, m, aggs="COUNT(*), SUM(v), SUM(q)"):
    """Exactly m rows pass. For none, a range the segment's min/max cannot
    prune: the first row alone, less itself by its own `q`."""
    where = f"pos < {m}" if m else \
        f"pos < 1 AND q > {int(np.asarray(seg.column('q').values())[0])}"
    return (f"SELECT k, {aggs} FROM cd "
            f"WHERE {where} GROUP BY k ORDER BY k LIMIT 3000000")


DECODE_KEYS = ("compactDecodeLaunches", "denseDecodeLaunches")
SORT_KEYS = ("presortCompactLaunches", "fullSortLaunches")      # PR 33


def _executed(mex, segs, sql, keys=DECODE_KEYS):
    """(rows, the counters of `keys` the launch recorded)."""
    from pinot_tpu.query import stats as qstats
    with qstats.collect_stats() as st:
        rows = mex.execute(segs, sql).rows
    return rows, {k: int(st.counters.get(k, 0)) for k in keys}


def _dense_only(monkeypatch):
    """Programs built from here on hold today's decode alone: no branch."""
    from pinot_tpu.engine import kernels
    from pinot_tpu.parallel import combine
    monkeypatch.setattr(kernels, "compact_cap", lambda n, nseg, block: 0)
    monkeypatch.setattr(combine, "_SHARD_KERNEL_CACHE", {})


@pytest.mark.parametrize("block", [256, 320], ids=["aligned", "ragged"])
@pytest.mark.parametrize("passing", ["0", "1", "cap-1", "cap", "cap+1", "all"])
@pytest.mark.parametrize("aggs", ["COUNT(*)", "COUNT(*), SUM(v), SUM(q)"],
                         ids=["count", "sums"])
def test_compact_decode_matches_dense_and_host(compact_segment, monkeypatch,
                                               aggs, passing, block):
    """Both branches of the sort regime's decode, against the host executor
    and against each other, around the cap: the sorted prefix of rows that
    passed answers up to `cap` rows (the last of them ends its group on the
    prefix's last row), the per-key decode above. 16,384 padded rows are a
    multiple of a 256-row block and 64 short of one of 320 (the sort pads
    them). With COUNT(*) alone the sort carries no value rows: the decode a
    grouped DISTINCTCOUNT past `chunk_cap` runs."""
    from pinot_tpu.engine import kernels
    n = 16_384 + (-16_384) % block
    cap = kernels.compact_cap(n, 8193, block)
    assert cap == n // 64
    m = {"0": 0, "1": 1, "cap-1": cap - 1, "cap": cap, "cap+1": cap + 1,
         "all": COMPACT_ROWS}[passing]
    sql = _compact_sql(compact_segment, m, aggs)
    mex = MeshQueryExecutor(default_mesh(1))
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096, partition_block=block))
    try:
        got, took = _executed(mex, [compact_segment], sql)
        want = ServerQueryExecutor(use_device=False).execute(
            [compact_segment], sql).rows
        _dense_only(monkeypatch)
        dense, neither = _executed(mex, [compact_segment], sql)
    finally:
        set_caps(prev)
    assert took == {"compactDecodeLaunches": int(m <= cap),
                    "denseDecodeLaunches": int(m > cap)}
    assert neither == {"compactDecodeLaunches": 0, "denseDecodeLaunches": 0}
    assert len(want) == len(np.unique(
        np.asarray(compact_segment.column("k").values())[:m]))
    _assert_rows_close(got, want, (aggs, passing, block))
    _assert_rows_close(dense, want, (aggs, passing, block, "dense"))
    # against each other: groups and counts exactly, sums within tolerance
    assert [r[:2] for r in got] == [r[:2] for r in dense]
    _assert_rows_close(got, dense, (aggs, passing, block, "branches"))


@pytest.mark.parametrize("passing", [50, 12_000], ids=["few", "many"])
def test_grouped_distinct_past_chunk_cap_matches_host(tmp_path_factory,
                                                      passing):
    """A grouped DISTINCTCOUNT whose (group, id) product is wider than
    `chunk_cap` counts presence by the sort regime with no value rows. Its
    masked rows ride an overflow BAND of `ids` keys, not the one overflow key,
    so the decode's count of rows that passed holds them too and the per-key
    decode answers however few pass (ROADMAP S1)."""
    rng = np.random.default_rng(30)
    rows = COMPACT_ROWS
    schema = Schema("dd", [dimension("g", DataType.INT),
                           dimension("d", DataType.INT),
                           metric("pos", DataType.INT)])
    cols = {"g": rng.integers(0, 20, rows).astype(np.int32),
            "d": rng.integers(0, 300, rows).astype(np.int32),
            "pos": np.arange(rows, dtype=np.int32)}
    cfg = SegmentGeneratorConfig(no_dictionary_columns=["pos"])
    seg = load_segment(build_aligned_segments(
        schema, cols, str(tmp_path_factory.mktemp("dd")), "dd", 1,
        config=cfg)[0])
    sql = ("SELECT g, DISTINCTCOUNT(d), COUNT(*) FROM dd "
           f"WHERE pos < {passing} GROUP BY g ORDER BY g LIMIT 100")
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096))     # 33 groups x 512 ids = 16,896
    try:
        got, took = _executed(MeshQueryExecutor(default_mesh(1)), [seg], sql)
    finally:
        set_caps(prev)
    want = ServerQueryExecutor(use_device=False).execute([seg], sql).rows
    assert got == want and sum(r[2] for r in got) == passing
    assert took == {"compactDecodeLaunches": 0, "denseDecodeLaunches": 1}


@pytest.mark.parametrize("length,p_head", [
    (256, 0.3), (1024, 0.3), (4096, 0.01), (8192, 0.0005), (2048, 1.0),
    (2048, 0.0)])
@pytest.mark.parametrize("rows", [2, 0], ids=["sums", "lengths-only"])
def test_run_totals_match_a_row_by_row_walk(length, p_head, rows):
    """Running lengths exactly and running sums to f32 rounding, for runs
    inside one 256-row block, across many, one row long, and one run in all."""
    import jax
    from pinot_tpu.engine.kernels import _run_totals
    rng = np.random.default_rng(length)
    head = rng.random(length) < p_head
    head[0] = True
    v = rng.uniform(-500, 500, (rows, length)).astype(np.float32)
    lengths, sums = jax.jit(_run_totals)(head, v)
    want_len = np.zeros(length, np.int64)
    want = np.zeros((rows, length))
    acc, run = np.zeros(rows), 0
    for i in range(length):
        acc = v[:, i].astype(np.float64) if head[i] else acc + v[:, i]
        run = 1 if head[i] else run + 1
        want[:, i], want_len[i] = acc, run
    assert np.array_equal(np.asarray(lengths), want_len)
    assert lengths.dtype == np.int32 and sums.shape == (rows, length)
    np.testing.assert_allclose(np.asarray(sums), want, rtol=2e-6, atol=1e-2)


def test_compact_ladder_takes_the_shortest_prefix_that_fits(monkeypatch):
    """A ladder of prefixes (16 and 64 rows here, then the cap's 256) chosen
    by the same count: each side of every rung answers as numpy does, with
    three keys so that a run spans many blocks of the run-totals scan."""
    import jax
    from pinot_tpu.engine import kernels
    monkeypatch.setattr(kernels, "COMPACT_RUNGS", (16, 64))
    fn = kernels._grouped_partitioned
    n, nseg, block = 16_384, 8193, 256
    took = []
    run = jax.jit(lambda k, v: (fn(k, nseg, [v], block, took), took[-1]))
    jaxpr = str(jax.make_jaxpr(lambda k, v: fn(k, nseg, [v], block))(
        np.zeros(n, np.int32), np.zeros(n, np.float32)))
    # one conditional on what the tiles hold, the compacted sort or the full
    # one; the step of slots inside the first; a ladder each side
    assert jaxpr.count("branches=") == 4
    rng = np.random.default_rng(9)
    for m in (0, 1, 16, 17, 64, 65, 256, 257, n):
        key = np.full(n, nseg - 1, np.int32)
        key[rng.choice(n, m, replace=False)] = rng.integers(0, 3, m) * 100
        v = np.where(key < nseg - 1, rng.uniform(-500, 500, n),
                     0).astype(np.float32)
        (counts, sums), flags = run(key, v)
        in_a_tile = (key < nseg - 1).reshape(-1, kernels.PRESORT_TILE).sum(1)
        assert bool(flags["decode.presort"]) == (
            m <= 256 and in_a_tile.max() <= 64), m
        assert bool(flags["decode.compact"]) == (m <= 256), m
        assert np.array_equal(np.asarray(counts),
                              np.bincount(key, minlength=nseg)), m
        np.testing.assert_allclose(
            np.asarray(sums), np.bincount(key, weights=v.astype(np.float64),
                                          minlength=nseg),
            rtol=1e-5, atol=1e-3, err_msg=str(m))


def one_full_quarter(name):
    """(schema, columns) of 4 x 8,000 rows: `w < 100` passes every row of the
    SECOND quarter and 10 rows of each other one, `w < 10` passes 40 rows of
    the second alone. (Not the first: a segment set is planned on its first
    segment, whose min/max must not fold the predicate.)"""
    rng = np.random.default_rng(31)
    per = 8000
    w = rng.integers(500, 1000, 4 * per).astype(np.int32)
    w[per:2 * per] = rng.integers(10, 100, per)
    w[per:per + 40] = 5
    for s in (0, 2, 3):
        w[s * per:s * per + 10] = 50
    schema = Schema(name, [dimension("k", DataType.INT),
                           metric("w", DataType.INT),
                           metric("v", DataType.DOUBLE)])
    return schema, {"k": rng.integers(0, 6000, 4 * per).astype(np.int32),
                    "w": w,
                    "v": np.round(rng.uniform(-500, 500, 4 * per), 3)}


def test_one_chip_dense_three_compact_on_the_mesh(tmp_path_factory):
    """Four devices, a segment each: every row of one segment passes (that
    chip's prefix does not fit: `dense`) and 10 rows of each other one
    (`compact`). Each chip takes its own branch from its own count, the
    answer equals the host's, and the launch counts as dense: compact only if
    every chip took it."""
    schema, cols = one_full_quarter("cm")
    cfg = SegmentGeneratorConfig(no_dictionary_columns=["w", "v"])
    segs = [load_segment(p) for p in build_aligned_segments(
        schema, cols, str(tmp_path_factory.mktemp("cm")), "cm", 4,
        config=cfg)]
    mex = MeshQueryExecutor(default_mesh(4))
    host = ServerQueryExecutor(use_device=False)
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096))
    try:
        for bound, passing, compact in ((100, 8030, 0), (10, 40, 1)):
            sql = ("SELECT k, COUNT(*), SUM(v) FROM cm "
                   f"WHERE w < {bound} GROUP BY k ORDER BY k LIMIT 3000000")
            got, took = _executed(mex, segs, sql)
            assert sum(r[1] for r in got) == passing
            _assert_rows_close(got, host.execute(segs, sql).rows, bound)
            assert took == {"compactDecodeLaunches": compact,
                            "denseDecodeLaunches": 1 - compact}, bound
    finally:
        set_caps(prev)


# --- the compacted sort in front of the sort regime (PR 33) ------------------
# One segment of 12,000 rows (16,384 padded on one device); `pos` numbers the
# rows and `s` is pos % 64, so `s = 0` passes one row in 64: exactly the slots
# a tile keeps, in every tile, whatever the tile's length.


def _presort_cols(rng, rows, per_segment):
    v = np.round(rng.uniform(-500, 500, rows), 3).astype(object)
    v[rng.random(rows) < 0.02] = None          # a table with nulls
    pos = np.arange(rows, dtype=np.int32)
    return {"k": rng.integers(0, 6000, rows).astype(np.int32), "pos": pos,
            "s": (pos % per_segment % 64).astype(np.int32), "v": v,
            "q": rng.integers(0, 1 << 30, rows).astype(np.int32)}


def _presort_segments(tmp_path_factory, name, rows, segments):
    schema = Schema(name, [dimension("k", DataType.INT),
                           metric("pos", DataType.INT),
                           metric("s", DataType.INT),
                           metric("v", DataType.DOUBLE),
                           metric("q", DataType.INT)])
    cols = _presort_cols(np.random.default_rng(33), rows, rows // segments)
    cfg = SegmentGeneratorConfig(no_dictionary_columns=["pos", "s", "v", "q"])
    return [load_segment(p) for p in build_aligned_segments(
        schema, cols, str(tmp_path_factory.mktemp(name)), name, segments,
        config=cfg)]


@pytest.fixture(scope="module")
def presort_segment(tmp_path_factory):
    return _presort_segments(tmp_path_factory, "ps", COMPACT_ROWS, 1)[0]


def _patch_presort_tile(monkeypatch, tile, slots=None):
    """Programs built from here on compact tiles of `tile` rows into `slots`
    slots (a 64th of the tile, then a 16th, as the constants that ship)."""
    from pinot_tpu.engine import kernels
    from pinot_tpu.parallel import combine
    monkeypatch.setattr(kernels, "PRESORT_TILE", tile)
    monkeypatch.setattr(kernels, "PRESORT_SLOTS",
                        slots or (tile // 64, tile // 16))
    monkeypatch.setattr(kernels, "_KERNEL_CACHE", {})
    monkeypatch.setattr(combine, "_SHARD_KERNEL_CACHE", {})


# passing -> (WHERE, rows that pass, the tiles hold them in their slots)
PRESORT_CASES = {
    "0": (None, 0, True),
    "1": ("pos < 1", 1, True),
    "slots-a-tile": ("s = 0", COMPACT_ROWS // 64 + 1, True),
    "slots+1-in-one-tile": ("s = 0 OR pos = 1", COMPACT_ROWS // 64 + 2, True),
    "past-the-last-step": ("s = 0 OR pos < 24", COMPACT_ROWS // 64 + 24, False),
    "all-in-one-tile": ("pos < 200", 200, False),
    "all": (f"pos < {COMPACT_ROWS}", COMPACT_ROWS, False),
}


@pytest.mark.parametrize("tile", [256, 320], ids=["aligned", "ragged"])
@pytest.mark.parametrize("passing", sorted(PRESORT_CASES))
@pytest.mark.parametrize("aggs", ["COUNT(*)", "COUNT(*), SUM(v), SUM(q)"],
                         ids=["count", "sums"])
def test_presorted_rows_answer_as_the_full_sort_and_the_host(
        presort_segment, monkeypatch, aggs, passing, tile):
    """The compacted sort against the host executor and against a build with
    the full sort alone, each side of what a tile's slots hold: no row, one,
    exactly the first step's slots in every tile, one row more in one tile
    (the second step's slots hold it), more in one tile than the last step's
    (falls back), every passing row in one tile (falls back), every row
    (falls back, and decodes per key). 16,384 padded rows are 64 tiles of 256
    rows and 64 rows short of 52 tiles of 320 (the count pads them); a 320-row
    tile keeps 5 slots, then 20, a 256-row one 4, then 16. With COUNT(*) alone
    no value row is moved."""
    from pinot_tpu.engine import kernels
    where, m, fits = PRESORT_CASES[passing]
    where = where or ("pos < 1 AND q > %d" % int(
        np.asarray(presort_segment.column("q").values())[0]))
    sql = (f"SELECT k, {aggs} FROM ps WHERE {where} "
           "GROUP BY k ORDER BY k LIMIT 3000000")
    cap = kernels.compact_cap(16_384, 8193, 4096)
    assert cap == 256
    mex = MeshQueryExecutor(default_mesh(1))
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096))
    try:
        _patch_presort_tile(monkeypatch, tile)
        got, took = _executed(mex, [presort_segment], sql,
                              DECODE_KEYS + SORT_KEYS)
        want = ServerQueryExecutor(use_device=False).execute(
            [presort_segment], sql).rows
        _dense_only(monkeypatch)
        full, neither = _executed(mex, [presort_segment], sql,
                                  DECODE_KEYS + SORT_KEYS)
    finally:
        set_caps(prev)
    assert sum(r[1] for r in want) == m
    assert took == {"presortCompactLaunches": int(fits),
                    "fullSortLaunches": int(not fits),
                    "compactDecodeLaunches": int(m <= cap),
                    "denseDecodeLaunches": int(m > cap)}
    assert not any(neither.values())
    _assert_rows_close(got, want, (aggs, passing, tile))
    assert [r[:2] for r in got] == [r[:2] for r in full]
    _assert_rows_close(got, full, (aggs, passing, tile, "full sort"))


def test_presorted_sums_are_the_full_sorts_to_the_bit(monkeypatch):
    """Contiguous tiles and slots in row order keep the passing rows' order,
    and the sort is stable: the compacted sort hands the compact decode the
    rows the full sort would, so the f32 sums are equal bit for bit. A
    64-row tile keeps one slot, so the same rows fall back there."""
    import jax
    from pinot_tpu.engine import kernels
    n, nseg, block = 16_384, 8193, 4096
    rng = np.random.default_rng(33)
    key = np.full(n, nseg - 1, np.int32)
    rows = rng.choice(n, 60, replace=False)     # about 4 a 1,024-row tile
    key[rows] = rng.integers(0, 12, 60) * 100
    v = np.where(key < nseg - 1, rng.uniform(-500, 500, n), 0).astype(np.float32)
    answers = {}
    for tile in (1024, 64):
        monkeypatch.setattr(kernels, "PRESORT_TILE", tile)
        monkeypatch.setattr(kernels, "PRESORT_SLOTS", (tile // 64,))
        took = []
        outs, flags = jax.jit(lambda k, x: (kernels._grouped_partitioned(
            k, nseg, [x], block, took), took[-1]))(key, v)
        in_a_tile = (key < nseg - 1).reshape(-1, tile).sum(1).max()
        assert (tile == 1024) == (in_a_tile <= tile // 64)
        assert {k: bool(f) for k, f in flags.items()} == {
            "decode.compact": True, "decode.presort": tile == 1024}
        answers[tile] = [np.asarray(o) for o in outs]
    assert np.array_equal(answers[1024][0], np.bincount(key, minlength=nseg))
    assert answers[1024][1].tobytes() == answers[64][1].tobytes()


@pytest.mark.parametrize("where,m,fits", [
    ("s = 0", 6 * 32, True), ("pos >= 2048 AND pos < 2148", 100, False)],
    ids=["slots-a-tile", "all-in-one-tile"])
def test_presort_over_a_routed_window_of_six_slots(tmp_path_factory,
                                                   monkeypatch, where, m, fits):
    """Eight resident segments of 2,048 rows, six routed: on a mesh of one the
    launch reads a window of 6 slots, 12,288 rows that are 48 tiles of 256
    rows; the tile view holds for any slot count, and the answer is the six
    segments' alone."""
    import jax
    from pinot_tpu.query import stats as qstats
    from pinot_tpu.query.aggregates import make_agg
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.reduce import merge_segment_results, reduce_to_result
    segs = _presort_segments(tmp_path_factory, "pw", 8 * 2048, 8)
    routed = segs[1:7]
    sql = (f"SELECT k, COUNT(*), SUM(v) FROM pw WHERE {where} "
           "GROUP BY k ORDER BY k LIMIT 3000000")
    ctx = compile_query(sql, segs[0].schema)
    mex = MeshQueryExecutor(default_mesh(1))
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096))
    try:
        _patch_presort_tile(monkeypatch, 256)
        p = mex.prepare_partial(ctx, routed, segs)
        assert p is not None and p.window == 6
        (outs, finish, _, recorded), = mex.dispatch_prepared([p])
        fetched = finish(jax.device_get(outs))[0]
    finally:
        set_caps(prev)
    assert recorded[qstats.SCANNED_SLOTS] == 6
    assert set(qstats.decode_branch(fetched)) == {
        "compactDecodeLaunches",
        "presortCompactLaunches" if fits else "fullSortLaunches"}
    aggs = [make_agg(f) for f in ctx.aggregations]
    got = reduce_to_result(ctx, merge_segment_results([p.decode(fetched)], aggs),
                           aggs, list(ctx.group_by)).rows
    want = ServerQueryExecutor(use_device=False).execute(routed, sql).rows
    assert sum(r[1] for r in want) == m
    _assert_rows_close(got, want, where)


@pytest.mark.parametrize("where,compact,presorted", [
    ("w < 100", 0, 0),    # one chip sorts every row and decodes per key
    ("w < 10", 1, 0),     # 40 rows in one tile of one chip: it falls back
    ("w = 500", 1, 1),    # about 16 rows of three chips, none of the fourth
], ids=["one-dense", "one-clustered", "all-compact"])
def test_one_chip_sorts_every_row_three_compact_on_the_mesh(
        tmp_path_factory, monkeypatch, where, compact, presorted):
    """Four devices, a segment each of 8,000 rows (8 tiles of 1,024 rows and
    16 slots, then 32 here). Each chip takes its own branch from its own tiles' counts, the
    answer equals the host's, and the launch counts under the compacted sort
    only if every chip took it."""
    schema, cols = one_full_quarter("pm")
    cfg = SegmentGeneratorConfig(no_dictionary_columns=["w", "v"])
    segs = [load_segment(p) for p in build_aligned_segments(
        schema, cols, str(tmp_path_factory.mktemp("pm")), "pm", 4,
        config=cfg)]
    sql = (f"SELECT k, COUNT(*), SUM(v) FROM pm WHERE {where} "
           "GROUP BY k ORDER BY k LIMIT 3000000")
    prev = get_caps()
    set_caps(KernelCaps(chunk_cap=4096))
    try:
        _patch_presort_tile(monkeypatch, 1024, (16, 32))
        got, took = _executed(MeshQueryExecutor(default_mesh(4)), segs, sql,
                              DECODE_KEYS + SORT_KEYS)
    finally:
        set_caps(prev)
    w = cols["w"].reshape(4, -1)
    passed = {"w < 100": w < 100, "w < 10": w < 10, "w = 500": w == 500}[where]
    in_a_tile = np.pad(passed, ((0, 0), (0, 192))).reshape(4, 8, 1024).sum(-1)
    assert list((in_a_tile <= 32).all(axis=1)) == {
        "w < 100": [True, False, True, True],
        "w < 10": [True, False, True, True]}.get(where, [True] * 4)
    assert sum(r[1] for r in got) == passed.sum() > 0
    _assert_rows_close(got, ServerQueryExecutor(use_device=False).execute(
        segs, sql).rows, where)
    assert took == {"compactDecodeLaunches": compact,
                    "denseDecodeLaunches": 1 - compact,
                    "presortCompactLaunches": presorted,
                    "fullSortLaunches": 1 - presorted}, where


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


def _nested(eqn):
    """The jaxprs an equation holds: conditional branches, loop bodies."""
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else (v,)):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _scatter_update_rows(jaxpr):
    """Rows of the updates operand of every scatter in a jaxpr, conditional
    branches and other nested jaxprs included."""
    sizes = []
    for eqn in jaxpr.eqns:
        if "scatter" in eqn.primitive.name:
            sizes.append(int(np.prod(eqn.invars[2].aval.shape, dtype=np.int64)))
        for sub in _nested(eqn):
            sizes.extend(_scatter_update_rows(sub))
    return sizes


def _flat_scans(jaxpr):
    """(primitive, elements along the scanned axis) of every cumulative
    primitive in a jaxpr, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("cum"):
            aval = eqn.invars[0].aval
            axis = eqn.params.get("axis", 0)
            found.append((eqn.primitive.name, int(aval.shape[axis])))
        for sub in _nested(eqn):
            found.extend(_flat_scans(sub))
    return found


def _presort_branch(jaxpr):
    """The jaxpr of the compacted sort's branch: the true side of the
    outermost conditional of the sort regime."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            return eqn.params["branches"][1].jaxpr
        for sub in _nested(eqn):
            found = _presort_branch(sub)
            if found is not None:
                return found
    return None


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
    # an n-row scatter is still forbidden; the compact decode's scatter-adds
    # of the sorted prefix (`kernels.compact_cap` rows, n / 64) are not
    n = block.padded
    cap = kernels.compact_cap(n, plan.num_keys_pad + 1,
                              get_caps().partition_block)
    sizes = _scatter_update_rows(jaxpr.jaxpr)
    assert sizes and set(sizes) == {cap, 1}, sizes   # 1: the overflow bucket
    assert cap <= n // 64
    # the compacted sort's branch (PR 33) moves the rows that passed with no
    # n-row scatter and no flat scan: nothing cumulative over n rows or over
    # its n / PRESORT_TILE tiles (the full sort's per-key decode, the other
    # side of the conditional, keeps its `cumsum` of run heads over n)
    short = _presort_branch(jaxpr.jaxpr)
    assert short is not None and str(short).count("sort[") == len(
        kernels.PRESORT_SLOTS)          # a step of slots is a move and a sort
    assert set(_scatter_update_rows(short)) == {cap, 1}
    assert not [s for s in _flat_scans(short)
                if s[1] >= n // kernels.PRESORT_TILE], _flat_scans(short)
    assert ("cumsum", n) in _flat_scans(jaxpr.jaxpr)


# --- one place chooses the kernel: the ladder at the shipped constants -------

def _abstract_scan(aggs_sql, num_keys_pad, rows, distinct_size=None):
    """(the scan body of a GROUP BY k, its abstract arguments): nothing is
    staged, compiled or run."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.engine import kernels
    from pinot_tpu.query.aggregates import make_agg
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.predicate import FilterProgram
    schema = Schema("t", [dimension("k", DataType.INT),
                          dimension("d", DataType.INT),
                          metric("v", DataType.DOUBLE)])
    ctx = compile_query(f"SELECT k, {aggs_sql} FROM t GROUP BY k", schema)
    aggs = [make_agg(f) for f in ctx.aggregations]
    spec = kernels.KernelSpec(
        FilterProgram(), ("k",), num_keys_pad,
        tuple((a, a.device_outputs) for a in aggs),
        {i: distinct_size for i, a in enumerate(aggs)
         if "distinct" in a.device_outputs}, rows)
    shape = jax.ShapeDtypeStruct
    i32, f32 = shape((rows,), jnp.int32), shape((rows,), jnp.float32)
    return kernels.make_kernel_body(spec), (
        {"k": i32, "d": i32}, {"v": f32}, (), shape((0,), jnp.int32),
        shape((0,), jnp.float32), {}, shape((rows,), jnp.bool_),
        shape((1,), jnp.int32), {}, ())


def _lowered_scan(aggs_sql, num_keys_pad, rows, distinct_size=None,
                  scopes=True):
    """The scan's lowered text, with its scope names (and the source
    locations of whoever called) or bare."""
    import jax
    body, args = _abstract_scan(aggs_sql, num_keys_pad, rows, distinct_size)
    return jax.jit(body).lower(*args).as_text(debug_info=scopes)


_SUMS, _MINMAX, _DISTINCT = "COUNT(*), SUM(v)", "MIN(v)", "DISTINCTCOUNT(d)"
_2_24 = 1 << 24
# (aggregates, padded keys, rows a device, ids of the distinct column,
#  the scope the program must hold, scopes it must not)
LADDER = [
    # padded keys + 1 (the overflow bucket) against masked_cap = 65
    pytest.param(_SUMS, 64, 16_384, None, "pinot.groupby.masked",
                 ("onehot", "chunk64", "partitioned"),
                 id="keys-at-masked_cap"),
    pytest.param(_SUMS, 65, 16_384, None, "pinot.groupby.onehot",
                 ("masked", "chunk64", "partitioned"),
                 id="keys-past-masked_cap"),
    pytest.param(_SUMS, 128, 16_384, None, "pinot.groupby.onehot",
                 ("masked", "chunk64", "partitioned"),
                 id="next-padded-keys-past-masked_cap"),
    # ... at any row count: an int32 count needs no slab, so no loop and no
    # contraction under its scope (TPC-H Q1's 9 key cells at the cell's 2^26)
    pytest.param(_SUMS, 8, _2_24 + 4096, None, "pinot.groupby.masked",
                 ("onehot", "pinot.groupby.masked/while",
                  "pinot.groupby.masked/dot_general"),
                 id="masked-past-2^24-rows"),
    pytest.param(_SUMS, 8, 1 << 26, None, "pinot.groupby.masked",
                 ("onehot", "pinot.groupby.masked/while",
                  "pinot.groupby.masked/dot_general"),
                 id="masked-at-2^26-rows"),
    # ... against matmul_cap = 512
    pytest.param(_SUMS, 511, 16_384, None, "pinot.groupby.onehot",
                 ("masked", "chunk64", "partitioned"),
                 id="keys-at-matmul_cap"),
    pytest.param(_SUMS, 512, 16_384, None, "pinot.groupby.chunk64",
                 ("onehot", "partitioned"), id="keys-past-matmul_cap"),
    # ... against chunk_cap = 131,072
    pytest.param(_SUMS, 131_071, 16_384, None, "pinot.groupby.chunk64",
                 ("onehot", "partitioned"), id="keys-at-chunk_cap"),
    pytest.param(_SUMS, 131_072, 16_384, None, "pinot.groupby.partitioned",
                 ("onehot", "chunk64"), id="keys-past-chunk_cap"),
    # the row count chooses no regime: one block past 2^24 rows, and at the
    # full cell's 2^26, the matmul regimes stay (slab by slab)
    pytest.param(_SUMS, 256, _2_24, None, "pinot.groupby.onehot",
                 ("chunk64", "partitioned"), id="onehot-at-2^24-rows"),
    pytest.param(_SUMS, 256, _2_24 + 4096, None, "pinot.groupby.onehot",
                 ("chunk64", "partitioned"), id="onehot-past-2^24-rows"),
    pytest.param(_SUMS, 256, 1 << 26, None, "pinot.groupby.onehot",
                 ("chunk64", "partitioned"), id="onehot-at-2^26-rows"),
    pytest.param(_SUMS, 8192, _2_24, None, "pinot.groupby.chunk64",
                 ("onehot", "partitioned"), id="chunk64-at-2^24-rows"),
    pytest.param(_SUMS, 8192, _2_24 + 4096, None, "pinot.groupby.chunk64",
                 ("onehot", "partitioned"), id="chunk64-past-2^24-rows"),
    pytest.param(_SUMS, 8192, 1 << 26, None, "pinot.groupby.chunk64",
                 ("onehot", "partitioned"), id="chunk64-at-2^26-rows"),
    pytest.param(_SUMS, 131_072, 1 << 26, None, "pinot.groupby.partitioned",
                 ("onehot", "chunk64"), id="keys-past-chunk_cap-at-2^26-rows"),
    # ... against dense_keys = 2^21: at it the dense table of the
    # sort regime, past it the sorted groups alone, the MINs with them
    pytest.param(_SUMS, 1 << 21, 16_384, None, "pinot.groupby.partitioned",
                 ("sparse", "chunk64"), id="keys-at-dense_keys"),
    pytest.param(_SUMS, (1 << 21) + 4096, 16_384, None,
                 "pinot.groupby.sparse", ("partitioned", "chunk64"),
                 id="keys-past-dense_keys"),
    pytest.param(_MINMAX, (1 << 21) + 4096, 16_384, None,
                 "pinot.groupby.sparse.groups", ("pinot.groupby.minmax",),
                 id="minmax-past-dense_keys"),
    # min/max: the broadcast-reduce up to minmax_bcast_cap = 1,024 keys + 1
    pytest.param(_MINMAX, 1023, 16_384, None,
                 "pinot.groupby.minmax/reduce_min", ("minmax/scatter-min",),
                 id="minmax-at-bcast_cap"),
    pytest.param(_MINMAX, 1024, 16_384, None,
                 "pinot.groupby.minmax/scatter-min", ("minmax/reduce_min",),
                 id="minmax-past-bcast_cap"),
    # grouped distinct: (groups + 1) x ids against chunk_cap
    pytest.param(_DISTINCT, 15, 16_384, 8192, "pinot.distinct/dot_general",
                 ("partitioned",), id="distinct-product-at-chunk_cap"),
    pytest.param(_DISTINCT, 16, 16_384, 8192,
                 # the full sort, beside the compacted one (PR 33)
                 "pinot.distinct/cond/branch_0_fun/"
                 "pinot.groupby.partitioned.sort",
                 ("pinot.distinct/dot_general",),
                 id="distinct-product-past-chunk_cap"),
    # ... at any row count (an f32 presence cell counts one slab's rows)
    pytest.param(_DISTINCT, 15, _2_24, 64, "pinot.distinct/dot_general",
                 ("partitioned",), id="distinct-at-2^24-rows"),
    pytest.param(_DISTINCT, 15, _2_24 + 4096, 64,
                 "pinot.distinct/while/body/closed_call",
                 ("partitioned", "pinot.distinct/dot_general"),
                 id="distinct-past-2^24-rows"),
]


@pytest.mark.parametrize("aggs,keys,rows,ids,holds,not_these", LADDER)
def test_regime_ladder_boundaries(aggs, keys, rows, ids, holds, not_these):
    """Which kernel a plan's shape gets, at the constants of `engine/caps.py`
    as they ship (no `set_caps`): each crossover of the ladder from both
    sides, read from the scope names of the lowered program."""
    assert get_caps() == KernelCaps()
    text = _lowered_scan(aggs, keys, rows, ids)
    assert holds in text
    for scope in not_these:
        assert (scope if "/" in scope else f"pinot.groupby.{scope}") \
            not in text, scope


# --- a handful of key cells: the masked VPU reduce (PR 37) -------------------

@pytest.mark.parametrize("aggs,keys,ids,same", [
    # SSB's GROUP BYs: Q4.1's 257 cells, the 8,193 of Q2.x-Q3.1 and Q4.2, the
    # wide four past chunk_cap; a grouped distinct past the rung
    (_SUMS, 256, None, True), (_SUMS, 8192, None, True),
    (_SUMS, 131_072, None, True), (_MINMAX, 256, None, True),
    (_DISTINCT, 256, 64, True), (_SUMS, 65, None, True),
    # at and under the cap the program is another
    (_SUMS, 64, None, False), (_SUMS, 8, None, False),
])
def test_past_masked_cap_the_program_is_the_parents_text(aggs, keys, ids,
                                                         same):
    """With `masked_cap` 0 the ladder is the one PR 37's parent had. Past the
    cap the lowered scan is that ladder's text, letter for letter (the four
    SSB cells' programs do not move); at or under it, it is not."""
    from dataclasses import replace
    ours = _lowered_scan(aggs, keys, 16_384, ids, scopes=False)
    prev = get_caps()
    set_caps(replace(prev, masked_cap=0))
    try:
        parents = _lowered_scan(aggs, keys, 16_384, ids, scopes=False)
        assert "pinot.groupby.masked" not in _lowered_scan(aggs, keys, 16_384,
                                                           ids)
    finally:
        set_caps(prev)
    assert "stablehlo" in ours and (ours == parents) == same

@pytest.mark.parametrize("keys,distinct,rows,is_masked,is_slabbed", [
    (8, None, 1 << 26, True, False),        # TPC-H Q1 in the lineitem cell
    (64, None, 1 << 26, True, False),       # at the cap: 65 cells
    (64, None, 16_384, True, False),
    (128, None, 1 << 26, False, True),      # past it: the one-hot regime
    (256, None, 1 << 26, False, True),      # SSB Q4.1's 257 cells, four slabs
    (256, None, 1 << 24, False, False),     # ... and one
    (131_072, None, 1 << 26, False, False),  # the sort regime has no slabs
    # a grouped distinct under the cap: the presence product's chunked matmul
    # still goes slab by slab; past chunk_cap it sorts
    (15, 64, 1 << 26, True, True),
    (16, 8192, 1 << 26, True, False),
])
def test_masked_and_slabbed_read_the_static_plan(keys, distinct, rows,
                                                 is_masked, is_slabbed):
    """What `maskedGroupByLaunches` and `slabbedLaunches` count, from the
    padded key count against `engine/caps.py` and the rows a device holds."""
    from pinot_tpu.engine import kernels
    from pinot_tpu.query.predicate import FilterProgram
    assert get_caps() == KernelCaps() and get_caps().masked_cap == 65
    spec = kernels.KernelSpec(
        FilterProgram(), ("k",), keys, (),
        {} if distinct is None else {0: distinct}, rows)
    assert kernels.masked(spec) == is_masked
    assert kernels.slabbed(spec, rows) == is_slabbed
    scalar = kernels.KernelSpec(FilterProgram(), (), 1, (), {}, rows)
    assert not kernels.masked(scalar) and not kernels.slabbed(scalar, rows)


@pytest.mark.parametrize("nseg", [2, 9, 65])
@pytest.mark.parametrize("n", [4096, 5000])
def test_masked_sums_match_numpy_and_int64(nseg, n):
    """The rung's helper alone: int32 counts equal to numpy's, each sum
    within 1e-6 of the int64 sum of the same integers, the overflow cell (the
    masked-out rows') the zero the other regimes fill it with, an empty key
    cell 0."""
    import jax
    from pinot_tpu.engine import kernels
    rng = np.random.default_rng(nseg * n)
    real = nseg - 1
    key = rng.integers(0, real, n).astype(np.int32)
    empty = real // 2
    key[key == empty] = real                        # nobody lands in `empty`
    key[rng.random(n) < 0.3] = real                 # masked out
    live = key < real
    q = rng.integers(1, 60_000, n).astype(np.int64)
    fm = live.astype(np.float32)
    rows = [fm, q.astype(np.float32) * fm, (q % 97).astype(np.float32) * fm]
    got = jax.jit(lambda k, r: kernels._masked_sums(k, nseg, r))(key, rows)
    assert len(got) == 3 and got[0].dtype == np.int32
    assert all(g.shape == (nseg,) for g in got)
    np.testing.assert_array_equal(
        np.asarray(got[0]), np.bincount(key[live], minlength=nseg))
    assert int(got[0][empty]) == 0 and int(got[0][-1]) == 0
    for g, r in zip(got[1:], (q, q % 97)):
        want = np.zeros(nseg, np.int64)
        np.add.at(want, key[live], r[live])
        assert g.dtype == np.float32 and float(g[-1]) == 0.0
        np.testing.assert_allclose(np.asarray(g, np.float64), want, rtol=1e-6)


@pytest.fixture(scope="module")
def masked_table(tmp_path_factory):
    """4 x 3,000 rows, 7 dictionary keys of `g`: `w < 900` passes nine rows in
    ten and NO row of key 3 (a key cell of the dictionary left empty)."""
    rng = np.random.default_rng(37)
    rows = 12_000
    g = rng.integers(0, 7, rows).astype(np.int32)
    w = rng.integers(0, 1000, rows).astype(np.int32)
    w[g == 3] = 999
    schema = Schema("mk", [dimension("g", DataType.INT),
                           metric("w", DataType.INT),
                           metric("q", DataType.INT),
                           metric("v", DataType.DOUBLE)])
    cols = {"g": g, "w": w,
            "q": rng.integers(1, 60_000, rows).astype(np.int32),
            "v": np.round(rng.uniform(-500.0, 60_000.0, rows), 2)}
    cfg = SegmentGeneratorConfig(no_dictionary_columns=["w", "q", "v"])
    segs = [load_segment(p) for p in build_aligned_segments(
        schema, cols, str(tmp_path_factory.mktemp("mk")), "mk", 4,
        config=cfg)]
    return segs, cols


@pytest.mark.parametrize("path", ["one-device", "mesh-of-four", "direct",
                                  "staged"])
def test_masked_rung_answers_as_numpy_and_counts_its_launches(masked_table,
                                                              path):
    """A GROUP BY of 7 keys (8 padded, 9 cells) through the served executor on
    one device, under `shard_map` on four, and through `run_kernel` and
    `run_kernel_staged` (a mask launch, then the aggregate): groups and
    counts numpy's, SUM of an INT column within 1e-6 of its int64 sum,
    the empty key cell no group; every launch counts in
    `maskedGroupByLaunches` and none in `slabbedLaunches`."""
    from pinot_tpu.query import stats as qstats
    segs, cols = masked_table
    sql = ("SELECT g, COUNT(*), SUM(q), SUM(v), MAX(q) FROM mk "
           "WHERE w < 900 GROUP BY g ORDER BY g LIMIT 100")
    ex = {"one-device": lambda: MeshQueryExecutor(default_mesh(1)),
          "mesh-of-four": lambda: MeshQueryExecutor(default_mesh(4)),
          "direct": ServerQueryExecutor,
          "staged": lambda: ServerQueryExecutor(fused_enabled=False)}[path]()
    with qstats.collect_stats() as st:
        rows = ex.execute(segs, sql).rows
    live = cols["w"] < 900
    keys = np.unique(cols["g"][live])
    assert 3 not in keys and [r[0] for r in rows] == keys.tolist()
    assert [r[1] for r in rows] == np.bincount(cols["g"][live])[keys].tolist()
    want = np.zeros(7, np.int64)
    np.add.at(want, cols["g"][live], cols["q"][live].astype(np.int64))
    np.testing.assert_allclose([r[2] for r in rows], want[keys], rtol=1e-6)
    np.testing.assert_allclose(
        [r[3] for r in rows],
        np.bincount(cols["g"][live], weights=cols["v"][live])[keys],
        rtol=1e-6)
    assert [r[4] for r in rows] == [
        int(cols["q"][live & (cols["g"] == k)].max()) for k in keys]
    # a launch a segment through the per-segment executor, two where staged
    rung = len(segs) if path in ("direct", "staged") else 1
    assert int(st.counters.get(qstats.DEVICE_LAUNCHES, 0)) == rung * (
        2 if path == "staged" else 1)
    assert int(st.counters.get(qstats.MASKED_GROUPBY_LAUNCHES, 0)) == rung
    assert int(st.counters.get(qstats.SLABBED_LAUNCHES, 0)) == 0


def test_masked_rung_is_not_taken_past_the_cap(slab_table):
    """200 keys (256 padded, 257 cells: SSB Q4.1's shape) keep the one-hot
    regime and count no masked launch."""
    from pinot_tpu.query import stats as qstats
    seg, _ = slab_table
    with qstats.collect_stats() as st:
        MeshQueryExecutor(default_mesh(1)).execute(
            [seg], "SELECT g, COUNT(*), SUM(v) FROM sl GROUP BY g LIMIT 999")
    assert int(st.counters.get(qstats.DEVICE_LAUNCHES, 0)) == 1
    assert int(st.counters.get(qstats.MASKED_GROUPBY_LAUNCHES, 0)) == 0


# --- past 2^24 rows a device: the matmul regimes slab by slab (PR 31) --------

def _eqns(jaxpr, *names):
    """Every equation of these primitives, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, *names)


def _contracted(eqn):
    """The lengths a dot_general contracts over (its lhs dimensions)."""
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    return [eqn.invars[0].aval.shape[d] for d in lhs_c]


@pytest.mark.parametrize("aggs,keys,ids,carries,dots,moved", [
    # one-hot: three bf16 parts, the rows stacked
    (_SUMS, 256, None, [[("int32", 257), ("float32", 257)]], 3, 3),
    # two chunks x (the count's one contraction + the sum's three parts)
    (_SUMS, 8192, None, [[("int32", 8193), ("float32", 8193)]], 8, 8),
    # 16 x 64 presence cells: one chunk, count only; the group-by's own count
    # (16 cells: the masked reduce) builds no loop and no contraction
    (_DISTINCT, 15, 64, [[("int32", 1024)]], 1, 0),
    # 257 groups x 64 ids: four chunks over the 256 real groups' presence
    # cells; then the group-by's own count (one-hot, 257 cells)
    (_DISTINCT, 256, 64, [[("int32", 257 * 64)], [("int32", 257)]], 4 + 3, 3),
], ids=["onehot-257", "chunk64-8193", "distinct", "distinct-257-groups"])
def test_at_2_26_rows_the_matmul_regimes_contract_one_slab_at_a_time(
        aggs, keys, ids, carries, dots, moved):
    """The full cell's shape, 2^26 rows a device: every loop over all the rows
    is one of four steps whose body holds every contraction of its regime,
    none of them over more than 2^24 rows (an f32 cell's exact range); the
    count leaves each slab as an integer (rounded and converted inside the
    body, added to an int32 carry) and the sums are carried in f32. The
    group-by's own one-hot or chunk64 rung sits in one conditional: its
    branch for each step of the compaction (16 and 64 slots a 1,024-row
    tile, one tile in 256 taken whole) contracts over T x S + T / 256 x
    1,024 rows in slabs of COMPACT_SLAB_ROWS, 5 and 17 of them, with the
    same carries, besides the two triangles over a tile; the fall-back holds
    the loop over all the rows. A grouped distinct's presence product keeps
    its loop. No sort anywhere."""
    import jax
    from pinot_tpu.engine import kernels
    body, args = _abstract_scan(aggs, keys, 1 << 26, ids)
    jaxpr = jax.make_jaxpr(body)(*args).jaxpr
    loops = list(_eqns(jaxpr, "scan", "while"))
    assert {e.primitive.name for e in loops} == {"scan"}
    full = [e for e in loops if e.params["length"] == 4]
    assert len(full) == len(carries)
    tile, steps = kernels.PRESORT_TILE, kernels.PRESORT_SLOTS
    tiles, slab = (1 << 26) // tile, kernels.COMPACT_SLAB_ROWS

    def carried_by(loop):
        avals = [v.aval for v in loop.outvars[:loop.params["num_carry"]]]
        return [(a.dtype.name, a.shape[0]) for a in avals if a.shape]

    looped = []
    for loop, carried in zip(full, carries):
        inner = loop.params["jaxpr"].jaxpr
        looped += list(_eqns(inner, "dot_general"))
        assert carried_by(loop) == carried
        # the count: round -> int32 -> added to the int32 carry, in the body
        assert list(_eqns(inner, "round"))
        assert any((e.outvars[0].aval.dtype.name, e.outvars[0].aval.shape)
                   == ("int32", (carried[0][1],))
                   for e in _eqns(inner, "add"))
    assert len(looped) == dots              # traced once, not four times
    for eqn in looped:
        assert _contracted(eqn) == [1 << 24], eqn
    compacted = [e for e in loops if e.params["length"] != 4]
    assert sorted(e.params["length"] for e in compacted) == sorted(
        kernels.slab_count(tiles * s + tiles // 256 * tile, slab)
        for s in steps if moved)
    in_loops = {id(e) for e in looped}
    for loop in compacted:
        assert carried_by(loop) == carries[-1]      # the rung's own
        inner = list(_eqns(loop.params["jaxpr"].jaxpr, "dot_general"))
        assert len(inner) == moved
        assert all(_contracted(e) == [slab] for e in inner)
        in_loops |= {id(e) for e in inner}
    outside = [tuple(_contracted(e)) for e in _eqns(jaxpr, "dot_general")
               if id(e) not in in_loops]
    assert outside == [(tile,)] * (2 * len(steps) if moved else 0)
    switches = [e for e in _eqns(jaxpr, "cond")
                if len(e.params["branches"]) == len(steps) + 1]
    assert len(switches) == int(bool(moved))
    for switch in switches:
        *moving, last = switch.params["branches"]
        assert all(e.params["length"] != 4
                   for b in moving for e in _eqns(b.jaxpr, "scan"))
        assert [e.params["length"] for e in _eqns(last.jaxpr, "scan")] == [4]
    assert not list(_eqns(jaxpr, "sort"))


@pytest.mark.parametrize("aggs,keys,ids,rows", [
    (_SUMS, 256, None, _2_24), (_SUMS, 8192, None, _2_24),
    (_DISTINCT, 15, 64, _2_24), (_SUMS, 8192, None, 16_384),
], ids=["onehot", "chunk64", "distinct", "chunk64-16k-rows"])
def test_at_most_2_24_rows_build_no_loop(aggs, keys, ids, rows):
    """One slab of all the rows is the program as it was: no loop over them,
    one contraction a pass over the rows. The one-hot and chunk64 rungs'
    compacted branches contract over T x S + T / 256 x 1,024 rows (T tiles
    of 1,024 rows, S slots a tile), in slabs of COMPACT_SLAB_ROWS where they
    are longer (a loop of that many steps), besides the triangles over a
    tile."""
    import jax
    from pinot_tpu.engine import kernels
    body, args = _abstract_scan(aggs, keys, rows, ids)
    jaxpr = jax.make_jaxpr(body)(*args).jaxpr
    tile, tiles = kernels.PRESORT_TILE, rows // kernels.PRESORT_TILE
    moved = [] if ids else [tiles * s + tiles // 256 * tile
                            for s in kernels.PRESORT_SLOTS]
    slabs = [kernels.slab_count(n, kernels.COMPACT_SLAB_ROWS) for n in moved]
    assert sorted(e.params["length"] for e in _eqns(jaxpr, "scan", "while")) \
        == sorted(k for k in slabs if k > 1)
    dots = {tuple(_contracted(e)) for e in _eqns(jaxpr, "dot_general")}
    assert dots == {(rows,)} | ({(tile,)} if moved else set()) | {
        (-(-n // k),) for n, k in zip(moved, slabs)}


@pytest.mark.parametrize("keys,passes", [
    (4095, 1), (4096, 1), (4097, 2), (8192, 2), (8193, 3), (12_288, 3)])
def test_chunk64_passes_cover_the_real_keys_alone(keys, passes):
    """`keys` padded keys + the overflow cell: one pass over the rows per 4,096
    REAL keys (count + three bf16 parts = four contractions a pass). 8,192
    keys are two passes, not a third whose only key is the overflow cell."""
    import jax
    body, args = _abstract_scan(_SUMS, keys, 16_384)
    jaxpr = jax.make_jaxpr(body)(*args).jaxpr
    # over every row: the fall-back of the compaction in front of the rung
    assert len([e for e in _eqns(jaxpr, "dot_general")
                if _contracted(e) == [16_384]]) == 4 * passes


@pytest.mark.parametrize("nseg", [257, 4097, 8193, 8200, 12_289])
def test_chunk64_overflow_cell_is_the_zero_it_would_sum_to(nseg):
    """Masked rows carry the overflow key and, by the contract, zeros: the
    cells no chunk covers read 0, as the pass that summed them did."""
    import jax.numpy as jnp
    from pinot_tpu.engine.kernels import _grouped_chunk64
    rng = np.random.default_rng(nseg)
    n = 6000
    key = rng.integers(0, nseg - 1, n).astype(np.int32)
    key[rng.random(n) < 0.5] = nseg - 1             # masked out
    fm = (key < nseg - 1).astype(np.float32)
    v = np.round(rng.uniform(1, 60_000, n), 2).astype(np.float32) * fm
    count, total = _grouped_chunk64(jnp.asarray(key), nseg,
                                    [jnp.asarray(fm)], [jnp.asarray(v)])
    assert count.shape == total.shape == (nseg,)
    assert float(count[-1]) == 0.0 and float(total[-1]) == 0.0
    live = key < nseg - 1
    np.testing.assert_array_equal(
        np.asarray(count), np.bincount(key[live], minlength=nseg))
    np.testing.assert_allclose(
        np.asarray(total, np.float64),
        np.bincount(key[live], weights=v[live].astype(np.float64),
                    minlength=nseg), rtol=1e-6)


def _patch_slab_rows(monkeypatch, rows):
    """Programs built from here on slab at `rows` (a module constant that no
    cache key holds: the caches are emptied, and restored with it)."""
    from pinot_tpu.engine import kernels
    from pinot_tpu.parallel import combine
    monkeypatch.setattr(kernels, "SLAB_ROWS", rows)
    monkeypatch.setattr(kernels, "_KERNEL_CACHE", {})
    monkeypatch.setattr(combine, "_SHARD_KERNEL_CACHE", {})


# rows, slab: one slab; two and five that divide the rows; two, three and
# five that do not (the tail is padded with the overflow key)
SLABS = [(4096, 4096), (8192, 4096), (10_240, 2048), (5000, 4096),
         (8193, 4096), (9999, 2048)]


@pytest.mark.parametrize("masked", [0.0, 0.97], ids=["all-pass", "few-pass"])
@pytest.mark.parametrize("n,slab", SLABS)
@pytest.mark.parametrize("regime", ["onehot", "chunk64", "distinct"])
def test_slab_sums_match_numpy(monkeypatch, regime, n, slab, masked):
    """The slab helper over each matmul regime: int32 counts equal to numpy's,
    sums within 1e-6 relative, whatever the slab count and the tail."""
    import jax
    from pinot_tpu.engine import kernels
    monkeypatch.setattr(kernels, "SLAB_ROWS", slab)
    assert kernels.slab_count(n) == -(-n // slab)
    nseg, real, fn = {
        "onehot": (257, 256, lambda k, r: kernels._onehot_sums(k, 257, r)),
        "chunk64": (8193, 8192, lambda k, r: kernels._grouped_chunk64(
            k, 8193, r[:1], r[1:])),
        # 33 groups x 64 ids: the masked rows' band is the last 64 keys
        "distinct": (2112, 2048, lambda k, r: kernels._grouped_chunk64(
            k, 2112, r, [], real=2048)),
    }[regime]
    rng = np.random.default_rng(n + slab)
    key = rng.integers(0, real, n).astype(np.int32)
    out = rng.random(n) < masked
    key[out] = rng.integers(real, nseg, int(out.sum()))
    fm = (key < real).astype(np.float32)
    rows = [fm] if regime == "distinct" else [
        fm, np.round(rng.uniform(1, 60_000, n), 2).astype(np.float32) * fm,
        rng.integers(1, 100, n).astype(np.float32) * fm]
    got = jax.jit(lambda k, r: kernels._slab_sums(k, nseg, r, fn))(key, rows)
    assert len(got) == len(rows)
    assert got[0].dtype == np.int32 and got[0].shape == (nseg,)
    np.testing.assert_array_equal(
        np.asarray(got[0]), np.bincount(key, weights=fm, minlength=nseg))
    for g, r in zip(got[1:], rows[1:]):
        assert g.dtype == np.float32
        np.testing.assert_allclose(
            np.asarray(g, np.float64),
            np.bincount(key, weights=r.astype(np.float64), minlength=nseg),
            rtol=1e-6)


SLAB_TABLE_ROWS = 12_000      # padded to 16,384 rows a device


@pytest.fixture(scope="module")
def slab_table(tmp_path_factory):
    rng = np.random.default_rng(31)
    rows = SLAB_TABLE_ROWS
    schema = Schema("sl", [dimension("g", DataType.INT),
                           dimension("k", DataType.INT),
                           dimension("d", DataType.INT),
                           metric("pos", DataType.INT),
                           metric("v", DataType.DOUBLE)])
    cols = {"g": rng.integers(0, 200, rows).astype(np.int32),
            "k": rng.integers(0, 6000, rows).astype(np.int32),
            "d": rng.integers(0, 40, rows).astype(np.int32),
            "pos": np.arange(rows, dtype=np.int32),
            "v": np.round(rng.uniform(1.0, 60_000.0, rows), 2)}
    cfg = SegmentGeneratorConfig(no_dictionary_columns=["pos", "v"])
    seg = load_segment(build_aligned_segments(
        schema, cols, str(tmp_path_factory.mktemp("sl")), "sl", 1,
        config=cfg)[0])
    return seg, cols


def _slab_answer(seg, sql):
    from pinot_tpu.query import stats as qstats
    with qstats.collect_stats() as st:
        rows = MeshQueryExecutor(default_mesh(1)).execute([seg], sql).rows
    return rows, int(st.counters.get(qstats.SLABBED_LAUNCHES, 0))


@pytest.mark.parametrize("passing", [SLAB_TABLE_ROWS, 300],
                         ids=["all-pass", "few-pass"])
@pytest.mark.parametrize("by,agg", [("g", "SUM(v)"), ("k", "SUM(v)"),
                                    ("g", "DISTINCTCOUNT(d)")],
                         ids=["onehot", "chunk64", "distinct"])
def test_slabbed_programs_answer_as_the_one_slab_program(
        slab_table, monkeypatch, by, agg, passing):
    """Through the served mesh executor with SLAB_ROWS patched small: 16,384
    padded rows in one slab (the program as it ships), two, and five whose
    tail is padded. Groups and counts equal to numpy's and to the one-slab
    program's, sums within 1e-6 relative of both; `slabbedLaunches` counts
    the launches of more than one slab and no other."""
    seg, cols = slab_table
    sql = (f"SELECT {by}, COUNT(*), {agg} FROM sl WHERE pos < {passing} "
           f"GROUP BY {by} ORDER BY {by} LIMIT 100000")
    live = cols["pos"] < passing
    keys = np.unique(cols[by][live])
    count = np.bincount(cols[by][live])[keys]
    if agg == "SUM(v)":
        want = np.bincount(cols[by][live], weights=cols["v"][live])[keys]
    else:
        want = np.array([len(np.unique(cols["d"][live & (cols[by] == k)]))
                         for k in keys])
    answers = {}
    for slab, slabs in ((16_384, 1), (8192, 2), (3300, 5)):
        _patch_slab_rows(monkeypatch, slab)
        rows, slabbed = _slab_answer(seg, sql)
        assert slabbed == int(slabs > 1), (slab, slabbed)
        assert [r[0] for r in rows] == keys.tolist(), slab
        assert [r[1] for r in rows] == count.tolist(), slab
        np.testing.assert_allclose([r[2] for r in rows], want, rtol=1e-6,
                                   err_msg=str(slab))
        answers[slabs] = rows
    for slabs in (2, 5):
        assert [r[:2] for r in answers[slabs]] == [r[:2] for r in answers[1]]
        np.testing.assert_allclose([r[2] for r in answers[slabs]],
                                   [r[2] for r in answers[1]], rtol=1e-6)


@pytest.mark.parametrize("slab,slabbed", [(16_384, 0), (4096, 1)])
def test_direct_executor_counts_its_slabbed_launch(slab_table, monkeypatch,
                                                   slab, slabbed):
    """`run_kernel` (the per-segment executor's launch) records the counter
    from the same rule, and answers as the host does."""
    from pinot_tpu.query import stats as qstats
    seg, _ = slab_table
    sql = "SELECT k, COUNT(*), SUM(v) FROM sl GROUP BY k ORDER BY k LIMIT 9999"
    _patch_slab_rows(monkeypatch, slab)
    with qstats.collect_stats() as st:
        got = ServerQueryExecutor().execute([seg], sql).rows
    assert int(st.counters.get(qstats.SLABBED_LAUNCHES, 0)) == slabbed
    want = ServerQueryExecutor(use_device=False).execute([seg], sql).rows
    assert [r[:2] for r in got] == [r[:2] for r in want]
    np.testing.assert_allclose([r[2] for r in got], [r[2] for r in want],
                               rtol=1e-6)


def test_a_stacked_burst_runs_its_slabs_inside_the_batch_scan(slab_table,
                                                              monkeypatch):
    """Three same-shape GROUP BYs that differ in a literal ride ONE stacked
    `_b4` launch: the slab loop inside the batch's `lax.scan` inside
    `shard_map`. One launch, one `slabbedLaunches`, each answer the host's."""
    from pinot_tpu.query import stats as qstats
    from pinot_tpu.query.aggregates import make_agg
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.reduce import (merge_segment_results,
                                        reduce_to_result)
    seg, _ = slab_table
    sqls = [f"SELECT k, COUNT(*), SUM(v) FROM sl WHERE pos < {t} GROUP BY k "
            "ORDER BY k LIMIT 100000" for t in (300, 7000, 11_000)]
    _patch_slab_rows(monkeypatch, 3300)         # five slabs, a padded tail
    mex = MeshQueryExecutor(default_mesh(1))
    ctxs = [compile_query(sql, seg.schema) for sql in sqls]
    preps = [mex.prepare_partial(ctx, [seg]) for ctx in ctxs]
    with qstats.collect_stats() as st:
        launches = mex.dispatch_prepared(preps)
        assert len(launches) == 1, "same-signature burst must stack"
        outs_dev, finish, idxs, _ = launches[0]
        outs_list = finish(mex.fetch([outs_dev])[0])
    assert int(st.counters.get(qstats.DEVICE_LAUNCHES, 0)) == 1
    assert int(st.counters.get(qstats.SLABBED_LAUNCHES, 0)) == 1
    host = ServerQueryExecutor(use_device=False)
    for pos, i in enumerate(idxs):
        aggs = [make_agg(f) for f in ctxs[i].aggregations]
        got = reduce_to_result(
            ctxs[i], merge_segment_results(
                [preps[i].decode(outs_list[pos])], aggs), aggs,
            list(ctxs[i].group_by)).rows
        want = host.execute([seg], sqls[i]).rows
        assert [r[:2] for r in got] == [r[:2] for r in want], sqls[i]
        np.testing.assert_allclose([r[2] for r in got],
                                   [r[2] for r in want], rtol=1e-6)


# --- the rows that passed, compacted in front of the matmul rungs ------------
# 16,384 rows in 64 tiles of 256: a tile keeps 4 slots, then 16, and one tile
# in 4 (16) may be taken whole beside them. Masked-out rows carry the overflow
# key and zeros, as `_make_body` hands them to the rungs.

COMPACT_N, COMPACT_TILE, COMPACT_STEPS, COMPACT_SPILL = 16_384, 256, (4, 16), 4


def _patch_compaction(monkeypatch, tile=COMPACT_TILE, slots=COMPACT_STEPS,
                      spill=COMPACT_SPILL, slab=None):
    """Programs built from here on compact a matmul rung's rows in tiles of
    `tile` rows into `slots` slots, one tile in `spill` taken whole, and
    contract them in slabs of `slab` rows (the constant that ships where
    None)."""
    from pinot_tpu.engine import kernels
    from pinot_tpu.parallel import combine
    if slab:
        monkeypatch.setattr(kernels, "COMPACT_SLAB_ROWS", slab)
    monkeypatch.setattr(kernels, "PRESORT_TILE", tile)
    monkeypatch.setattr(kernels, "PRESORT_SLOTS", slots)
    monkeypatch.setattr(kernels, "PRESORT_SPILL", spill)
    monkeypatch.setattr(kernels, "_KERNEL_CACHE", {})
    monkeypatch.setattr(combine, "_SHARD_KERNEL_CACHE", {})


def _layout(name):
    """Which of COMPACT_N rows pass, and whether the compaction holds them:
    a step whose slots leave at most COMPACT_N / COMPACT_TILE / COMPACT_SPILL
    = 16 tiles overfull."""
    live = np.zeros(COMPACT_N, bool)
    tiles = live.reshape(-1, COMPACT_TILE)             # a view
    sparse = np.arange(COMPACT_N) % 128 == 7           # 2 a tile
    if name == "every-tile-within-4":
        live[np.arange(COMPACT_N) % 64 == 3] = True    # 4 a tile: the slots
        return live, True
    if name == "unselective":
        live[np.random.default_rng(1).random(COMPACT_N) < 0.9] = True
        return live, False
    live |= sparse
    if name == "a-tile-at-4":
        tiles[5, :2] = True                            # 2 + 2 more
    elif name == "a-tile-at-5":
        tiles[5, 100:103] = True                       # 5: taken whole
    elif name == "a-whole-tile":
        tiles[9] = True                                # all 256 rows
    elif name in ("16-overfull-tiles", "17-overfull-tiles"):
        for t in range(16 if name.startswith("16") else 17):
            tiles[3 * t + 1, 30:50] = True             # 22 a tile
        return live, name.startswith("16")
    elif name == "walk":
        # every segment of 4,096 rows opens with runs of 50 rows that pass
        # every 150 rows: its first three tiles hold 102, 102 and 52
        for seg in range(COMPACT_N // 4096):
            for run in range(0, 750, 150):
                live[seg * 4096 + run:seg * 4096 + run + 50] = True
    return live, True


COMPACT_LAYOUTS = ["every-tile-within-4", "a-tile-at-4", "a-tile-at-5",
                   "a-whole-tile", "16-overfull-tiles", "17-overfull-tiles",
                   "walk", "unselective"]
_COMPACTED_FNS: dict = {}


def _compacted_fn(regime, n_vals):
    """The jitted rung (its counts and sums, and its flag) under the patched
    constants, built once for every layout."""
    import jax
    from pinot_tpu.engine import kernels
    from pinot_tpu.query import stats as qstats
    key = (regime, n_vals, kernels.PRESORT_TILE, kernels.PRESORT_SLOTS,
           kernels.PRESORT_SPILL, kernels.COMPACT_SLAB_ROWS)
    if key not in _COMPACTED_FNS:
        nseg, fn = {
            "onehot": (257, lambda k, r: kernels._onehot_sums(k, 257, r)),
            "chunk64": (8193, lambda k, r: kernels._grouped_chunk64(
                k, 8193, r[:1], r[1:])),
        }[regime]

        def run(k, rows):
            took = []
            return (kernels._compacted_sums(k, nseg, rows, fn, took),
                    took[0][qstats.MATMUL_FLAG])
        _COMPACTED_FNS[key] = (nseg, jax.jit(run))
    return _COMPACTED_FNS[key]


@pytest.mark.parametrize("slab", [None, 1500], ids=["one-slab", "slabs"])
@pytest.mark.parametrize("aggs", ["sums", "count"])
@pytest.mark.parametrize("layout", COMPACT_LAYOUTS)
@pytest.mark.parametrize("regime", ["onehot", "chunk64"])
def test_compacted_rung_counts_every_row_once(monkeypatch, regime, layout,
                                              aggs, slab):
    """Each matmul rung over the rows that passed, compacted or not: int32
    counts equal to numpy's (a row contracted twice, or not at all, would
    show), sums within 1e-6 of the int64 / float64 sums, the overflow cell
    0, and the flag 1 exactly where a step's slots leave at most one tile in
    four overfull: a tile at its slots, one past them and one whose every
    row passed are taken; 16 tiles past both steps are, 17 fall back, as
    an unselective filter does; the walk's runs of 50 fit. The compacted
    rows are contracted in one slab, or in slabs of 1,500 rows whose tail is
    padded (3 at the first step, 4 at the second)."""
    from pinot_tpu.engine import kernels
    _patch_compaction(monkeypatch, slab=slab)
    assert kernels.compacted_rows(COMPACT_N) == 64 * 16 + 16 * 256
    live, fits = _layout(layout)
    per_tile = live.reshape(-1, COMPACT_TILE).sum(1)
    assert fits == any((per_tile > s).sum() <= 16 for s in COMPACT_STEPS)
    rng = np.random.default_rng(COMPACT_LAYOUTS.index(layout))
    nseg, run = _compacted_fn(regime, 3 if aggs == "sums" else 1)
    key = np.where(live, rng.integers(0, nseg - 1, COMPACT_N),
                   nseg - 1).astype(np.int32)
    fm = live.astype(np.float32)
    q = rng.integers(1, 60_000, COMPACT_N)
    v = np.round(rng.uniform(-500, 60_000, COMPACT_N), 2)
    rows = [fm] if aggs == "count" else [
        fm, q.astype(np.float32) * fm, v.astype(np.float32) * fm]
    got, flag = run(key, rows)
    assert int(flag) == int(fits)
    assert len(got) == len(rows) and got[0].dtype == np.int32
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.bincount(key[live], minlength=nseg))
    for g, want in zip(got[1:], (q, v.astype(np.float32))):
        assert g.dtype == np.float32 and float(g[-1]) == 0.0
        np.testing.assert_allclose(
            np.asarray(g, np.float64),
            np.bincount(key[live], weights=want[live].astype(np.float64),
                        minlength=nseg), rtol=1e-6, atol=1e-6)


def test_spill_index_finds_the_flagged_tiles_in_order(monkeypatch):
    """The overfull tiles' indices, in tile order, across groups of
    PRESORT_TILE tiles (here 4): at most `spill` of them, the places left
    over marked as not taken."""
    import jax
    from pinot_tpu.engine import kernels
    monkeypatch.setattr(kernels, "PRESORT_TILE", 4)
    flagged = np.zeros(22, bool)
    flagged[[1, 2, 3, 7, 8, 21]] = True
    for spill, want in ((8, [1, 2, 3, 7, 8, 21]), (6, [1, 2, 3, 7, 8, 21]),
                        (3, [1, 2, 3])):
        at, taken = jax.jit(lambda f, s=spill: kernels._spill_index(f, s))(
            flagged)
        n = len(want)
        assert np.asarray(at)[:n].tolist() == want[:spill]
        assert np.asarray(taken).tolist() == [True] * min(n, spill) + [
            False] * (spill - min(n, spill))


MATMUL_KEYS = ("compactMatmulLaunches", "fullMatmulLaunches")


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("by", ["g", "k"], ids=["onehot", "chunk64"])
def test_one_chip_falls_back_three_compact_on_the_mesh(tmp_path_factory, by,
                                                       devices):
    """Four segments of 8,000 rows, at the constants that ship: `w < 100`
    passes every row of the second segment (its tiles overflow every step:
    the launch contracts every row, on a mesh of four the chip that holds
    it alone, and the launch counts as full: compacted only if every chip
    was), `w < 10` 40 rows of one tile (the second step), `w = 500` about
    16 scattered in each segment but the second. Answers equal to the
    host's, one launch counted in one of the two."""
    schema, cols = one_full_quarter(f"cmm{devices}")
    cols["g"] = np.random.default_rng(41).integers(
        0, 200, len(cols["k"])).astype(np.int32)
    schema = Schema(schema.name, [dimension("g", DataType.INT)]
                    + list(schema.fields))
    cfg = SegmentGeneratorConfig(no_dictionary_columns=["w", "v"])
    segs = [load_segment(p) for p in build_aligned_segments(
        schema, cols, str(tmp_path_factory.mktemp(f"cmm{devices}")),
        f"cmm{devices}", 4, config=cfg)]
    mex = MeshQueryExecutor(default_mesh(devices))
    host = ServerQueryExecutor(use_device=False)
    for where, compact in (("w < 100", 0), ("w < 10", 1), ("w = 500", 1)):
        sql = (f"SELECT {by}, COUNT(*), SUM(v) FROM {schema.name} "
               f"WHERE {where} GROUP BY {by} ORDER BY {by} LIMIT 100000")
        got, took = _executed(mex, segs, sql, MATMUL_KEYS + SORT_KEYS)
        _assert_rows_close(got, host.execute(segs, sql).rows, where)
        assert took == {"compactMatmulLaunches": compact,
                        "fullMatmulLaunches": 1 - compact,
                        "presortCompactLaunches": 0,
                        "fullSortLaunches": 0}, where
