"""Multichip differential suite: every mesh-merged shape on the 8-virtual-
device CPU mesh (conftest forces xla_force_host_platform_device_count=8),
compared against the 1-device mesh and the host-reducer answers.

Comparison contract: keys, counts, and every non-float cell must be
byte-equal across paths; float aggregates tolerate 1e-4 relative error
(f32 partials accumulate in different orders across 8 shards vs 1 vs the
host merge loop). Dense-partial ARRAYS (counts, occupancy) are compared
byte-for-byte — the psum of integer per-shard counts is exact.
"""

import numpy as np
import pytest

from pinot_tpu.parallel import MeshQueryExecutor, default_mesh
from pinot_tpu.parallel.mesh import pad_slots, placement_slots, skew_pct
from pinot_tpu.query import stats as qstats
from pinot_tpu.query.aggregates import make_agg
from pinot_tpu.query.context import compile_query
from pinot_tpu.query.executor import ServerQueryExecutor
from pinot_tpu.query.reduce import merge_segment_results, reduce_to_result
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.segment import load_segment
from pinot_tpu.segment.writer import SegmentBuilder, build_aligned_segments

N_KEYS = 5000   # >= executor.DENSE_PARTIAL_MIN_GROUPS: forces the dense path
N_ROWS = 8 * 8192

HC_QUERY = ("SELECT k, SUM(v), COUNT(*) FROM hcdiff GROUP BY k "
            f"LIMIT {2 * N_KEYS}")
DISTINCT_QUERY = ("SELECT DISTINCTCOUNT(region), DISTINCTCOUNTHLL(k), "
                  "DISTINCTCOUNTTHETASKETCH(k) FROM hcdiff "
                  "WHERE q < 40 LIMIT 5")
GROUPED_DISTINCT_QUERY = ("SELECT region, DISTINCTCOUNT(q), "
                          "DISTINCTCOUNTHLL(k) FROM hcdiff GROUP BY region "
                          "ORDER BY region LIMIT 10")
TOPK_QUERY = "SELECT k, v FROM hcdiff ORDER BY v DESC LIMIT 10"


def _schema():
    return Schema("hcdiff", [
        dimension("k", DataType.INT),
        dimension("region", DataType.STRING),
        metric("q", DataType.INT),
        metric("v", DataType.DOUBLE),
    ])


def _columns(rng, n):
    # one full pass of every key so each segment slice still spans the whole
    # dictionary; distinct v values keep the top-k order deterministic
    k = np.concatenate([np.arange(N_KEYS, dtype=np.int64),
                        rng.integers(0, N_KEYS, n - N_KEYS)])
    rng.shuffle(k)
    return {
        "k": k.astype(np.int32),
        "region": np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "ME"],
                           dtype=object)[rng.integers(0, 5, n)],
        "q": rng.integers(0, 100, n).astype(np.int32),
        "v": np.round(rng.uniform(0.0, 1000.0, n), 6),
    }


@pytest.fixture(scope="module")
def segments(tmp_path_factory):
    out = tmp_path_factory.mktemp("mc_aligned")
    paths = build_aligned_segments(_schema(), _columns(
        np.random.default_rng(29), N_ROWS), str(out), "hcdiff", 8)
    return [load_segment(p) for p in paths]


@pytest.fixture(scope="module")
def mesh8():
    return MeshQueryExecutor(default_mesh(8))


@pytest.fixture(scope="module")
def mesh1():
    return MeshQueryExecutor(default_mesh(1))


@pytest.fixture(scope="module")
def host():
    return ServerQueryExecutor(use_device=False)


def assert_rows_match(got, want, label, rel=1e-4):
    """Byte-equality for every non-float cell; `rel` tolerance for floats."""
    assert len(got) == len(want), \
        f"{label}: {len(got)} rows vs {len(want)}"
    for rg, rw in zip(got, want):
        assert len(rg) == len(rw), f"{label}: column count {rg} vs {rw}"
        for vg, vw in zip(rg, rw):
            if isinstance(vg, float) and isinstance(vw, float):
                assert abs(vg - vw) <= rel * max(1.0, abs(vw)), \
                    f"{label}: {vg} != {vw} in {rg} vs {rw}"
            else:
                assert vg == vw, f"{label}: {vg!r} != {vw!r}"


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple(str(v) for v in r))


def _leaf_partial(mesh_exec, segments, sql):
    """The server-level mesh partial: one sharded launch, one fetch."""
    ctx = compile_query(sql, segments[0].schema)
    disp = mesh_exec.dispatch_partial(ctx, segments)
    assert disp is not None, f"{sql!r} did not plan on the mesh"
    outs_dev, decode = disp
    return ctx, decode(mesh_exec.fetch([outs_dev])[0])


# -- placement unit behavior -------------------------------------------------

def test_pad_slots_quantization():
    # multi-device: per-device slots quantize to pow2 (compile-cache buckets)
    assert pad_slots(5, 8) == 8
    assert pad_slots(9, 8) == 16
    assert pad_slots(17, 8) == 8 * 4
    # single device keeps the exact count — no rectangularity to buy
    assert pad_slots(5, 1) == 5
    assert pad_slots(17, 1) == 17


def test_placement_slots_lpt_balances_uneven_docs():
    docs = [20000, 15000, 10000, 5000, 5000]
    slots, loads = placement_slots(docs, pad_slots(len(docs), 8), 8)
    assert sorted(slots) == slots or len(set(slots)) == len(slots)
    assert len(set(slots)) == len(docs)           # distinct slots
    assert max(slots) < pad_slots(len(docs), 8)   # bounded by the block
    assert sum(loads) == sum(docs)
    # LPT with capacity 1/device: each segment lands on its own device,
    # biggest first — the max device load is the biggest single segment
    assert max(loads) == 20000
    assert skew_pct(loads) > 0.0
    assert skew_pct([100, 100, 100, 100]) == 0.0
    assert skew_pct([]) == 0.0


# -- mesh-merged shapes vs 1-device and host reducers ------------------------

@pytest.mark.parametrize("sql,label", [
    (HC_QUERY, "dense_groupby"),
    (DISTINCT_QUERY, "distinct_sketches"),
    (GROUPED_DISTINCT_QUERY, "grouped_distinct"),
])
def test_mesh8_vs_mesh1_vs_host(segments, mesh8, mesh1, host, sql, label):
    with qstats.collect_stats() as st:
        r8 = mesh8.execute(segments, sql)
    r1 = mesh1.execute(segments, sql)
    rh = host.execute(segments, sql)
    assert int(st.counters.get(qstats.DEVICE_LAUNCHES, 0)) == 1, \
        f"{label}: expected ONE sharded launch on the 8-device mesh"
    assert_rows_match(_sorted(r8.rows), _sorted(r1.rows), f"{label} 8v1")
    assert_rows_match(_sorted(r8.rows), _sorted(rh.rows), f"{label} 8vHost")


def test_topk_prepared_mesh_vs_mesh1_vs_host(segments, mesh8, mesh1, host):
    """The fused top-k rides the PREPARED pipeline path (one stacked launch
    over all segments); its reduced selection must match both mesh widths
    and the host engine."""
    from pinot_tpu.cluster.device_server import DEVICE_FALLBACK
    ctx = compile_query(TOPK_QUERY, segments[0].schema)

    def run(me):
        p = me.prepare_partial(ctx, segments)
        assert p is not None and p.kind == "topk"
        launches = me.dispatch_prepared([p])
        assert len(launches) == 1, "topk must be ONE stacked launch"
        outs_dev, finish, _, _ = launches[0]
        outs_list = finish(me.fetch([outs_dev])[0])
        partial = p.decode(outs_list[0])
        assert partial is not DEVICE_FALLBACK
        return reduce_to_result(
            ctx, merge_segment_results([partial], []), [], []).rows

    r8, r1 = run(mesh8), run(mesh1)
    rh = host.execute(segments, TOPK_QUERY).rows
    assert_rows_match(r8, r1, "topk 8v1")
    assert_rows_match(r8, rh, "topk 8vHost")


def test_dense_partial_byte_equal_across_mesh_widths(segments, mesh8, mesh1):
    """The high-card leaf partial must come back as a DensePartial from BOTH
    mesh widths — zero host-side value merges — with byte-equal integer
    arrays (psum of per-shard int counts is exact)."""
    _, leaf8 = _leaf_partial(mesh8, segments, HC_QUERY)
    _, leaf1 = _leaf_partial(mesh1, segments, HC_QUERY)
    assert leaf8.dense is not None and leaf1.dense is not None
    assert leaf8.dense.token == leaf1.dense.token
    np.testing.assert_array_equal(leaf8.dense.counts, leaf1.dense.counts)
    assert leaf8.num_docs_scanned == leaf1.num_docs_scanned == N_ROWS
    for name in leaf8.dense.outs:
        np.testing.assert_allclose(leaf8.dense.outs[name],
                                   leaf1.dense.outs[name], rtol=1e-5)


def test_device_routed_exchange_preserves_dense(segments, mesh8):
    """P=1 — the partition count the device-routed coordinator collapses to
    when every stage worker shares the mesh — must carry the array-form
    partial through the REAL mailbox fabric untouched (byte-equal arrays,
    no densify)."""
    from pinot_tpu.multistage.shuffle import (_deliver_local, consume_mailbox,
                                              partition_groups_stable)
    ctx, leaf = _leaf_partial(mesh8, segments, HC_QUERY)
    aggs = [make_agg(f) for f in ctx.aggregations]
    assert leaf.dense is not None
    ref_counts = leaf.dense.counts.copy()
    ref_outs = {k: v.copy() for k, v in leaf.dense.outs.items()}

    parts = partition_groups_stable(leaf, 1)
    assert len(parts) == 1 and parts[0].dense is not None
    _deliver_local("mcq1", "A.0", parts[0], "partial", "s0")
    _, partials = consume_mailbox("mcq1", "A.0", 1)
    merged = merge_segment_results(partials, aggs)
    assert merged.dense is not None, "exchange densified the partial"
    np.testing.assert_array_equal(merged.dense.counts, ref_counts)
    for name, ref in ref_outs.items():
        np.testing.assert_array_equal(merged.dense.outs[name], ref)


def test_hash_exchange_matches_direct_reduce(segments, mesh8):
    """P=4 hash partition -> mailbox -> merge must reduce to the same table
    as reducing the leaf partial directly (keys are disjoint across
    partitions, so merged states are bit-identical)."""
    from pinot_tpu.multistage.shuffle import (_deliver_local, consume_mailbox,
                                              partition_groups_stable)
    ctx, leaf = _leaf_partial(mesh8, segments, HC_QUERY)
    aggs = [make_agg(f) for f in ctx.aggregations]
    direct = reduce_to_result(
        ctx, merge_segment_results([leaf], aggs), aggs, list(ctx.group_by))

    parts = partition_groups_stable(leaf, 4)     # materializes the dense form
    for i, part in enumerate(parts):
        _deliver_local("mcq4", f"A.{i}", part, "partial", "s0")
    got = []
    for i in range(4):
        _, partials = consume_mailbox("mcq4", f"A.{i}", 1)
        got.extend(partials)
    exchanged = reduce_to_result(
        ctx, merge_segment_results(got, aggs), aggs, list(ctx.group_by))
    assert_rows_match(_sorted(exchanged.rows), _sorted(direct.rows),
                      "hash_exchange", rel=1e-7)


def test_shuffle_join_matches_host_computation():
    """The multistage shuffle-join runtime (leaf scan -> hash exchange ->
    per-partition join -> reduce) against a direct numpy evaluation."""
    from pinot_tpu.multistage import execute_multistage
    from pinot_tpu.multistage.runtime import make_segment_scan

    rng = np.random.default_rng(61)
    n = 4000
    orders_schema = Schema("orders", [
        dimension("cust_id", DataType.INT),
        metric("amount", DataType.DOUBLE)])
    custs_schema = Schema("custs", [
        dimension("cust_id", DataType.INT),
        dimension("tier", DataType.STRING)])
    orders = {"cust_id": rng.integers(0, 500, n).astype(np.int32),
              "amount": np.round(rng.uniform(1.0, 50.0, n), 2)}
    tiers = np.array(["gold", "silver", "bronze"], dtype=object)
    custs = {"cust_id": np.arange(500, dtype=np.int32),
             "tier": tiers[rng.integers(0, 3, 500)]}
    import tempfile
    work = tempfile.mkdtemp(prefix="mc_join_")
    o_segs = [load_segment(p) for p in build_aligned_segments(
        orders_schema, orders, work, "orders", 4)]
    c_seg = load_segment(SegmentBuilder(custs_schema).build(
        custs, work, "custs_0"))
    res = execute_multistage(
        "SELECT c.tier, SUM(o.amount), COUNT(*) FROM orders o "
        "JOIN custs c ON o.cust_id = c.cust_id "
        "GROUP BY c.tier ORDER BY c.tier LIMIT 10",
        make_segment_scan({"orders": o_segs, "custs": [c_seg]}),
        {"orders": orders_schema, "custs": custs_schema}.get)

    cust_tier = dict(zip(custs["cust_id"].tolist(), custs["tier"].tolist()))
    want = {}
    for cid, amt in zip(orders["cust_id"].tolist(),
                        orders["amount"].tolist()):
        t = cust_tier[cid]
        s, c = want.get(t, (0.0, 0))
        want[t] = (s + amt, c + 1)
    want_rows = [[t, want[t][0], want[t][1]] for t in sorted(want)]
    assert_rows_match(res.rows, want_rows, "shuffle_join", rel=1e-9)


# -- uneven segment placement ------------------------------------------------

@pytest.fixture(scope="module")
def uneven_segments(tmp_path_factory):
    """5 ALIGNED segments with very different sizes over the 8-device mesh:
    exercises LPT placement (chip-aware slots), empty device slots, and the
    skew accounting — dictionaries are shared across segments exactly like
    build_aligned_segments so the dense path stays eligible."""
    from pinot_tpu.segment.dictionary import build_dictionary
    schema = _schema()
    rng = np.random.default_rng(47)
    sizes = (20000, 15000, 10000, 5000, 5000)
    union = _columns(rng, sum(sizes))
    fixed = {}
    for spec in schema.fields:
        fixed[spec.name], _ = build_dictionary(
            np.asarray(union[spec.name]) if spec.data_type.is_numeric
            else union[spec.name], spec.data_type)
    out = tmp_path_factory.mktemp("mc_uneven")
    builder = SegmentBuilder(schema)
    segs, lo = [], 0
    for i, sz in enumerate(sizes):
        part = {c: v[lo:lo + sz] for c, v in union.items()}
        segs.append(load_segment(builder.build(
            part, str(out), f"hcdiff_{i}", fixed_dictionaries=fixed)))
        lo += sz
    return segs


@pytest.mark.parametrize("sql,label", [
    (HC_QUERY, "uneven_dense_groupby"),
    ("SELECT region, SUM(v), COUNT(*), MAX(q) FROM hcdiff "
     "GROUP BY region ORDER BY region LIMIT 10", "uneven_lowcard_groupby"),
    ("SELECT SUM(v), COUNT(*) FROM hcdiff WHERE q < 30 LIMIT 5",
     "uneven_scalar"),
])
def test_uneven_segment_counts_match_host(uneven_segments, mesh8, host,
                                          sql, label):
    with qstats.collect_stats() as st:
        r8 = mesh8.execute(uneven_segments, sql)
    rh = host.execute(uneven_segments, sql)
    assert_rows_match(_sorted(r8.rows), _sorted(rh.rows), label)
    # 5 unequal segments on 8 devices: the LPT loads are necessarily skewed,
    # and the max-merged stat must surface that (not sum across launches)
    skew = float(st.counters.get(qstats.DEVICE_SKEW_PCT, 0.0))
    assert skew > 0.0, f"{label}: expected nonzero deviceSkewPct"
    assert int(st.counters.get(qstats.DEVICE_LAUNCHES, 0)) == 1


# -- unaligned (merged-view) sets on the mesh ---------------------------------

def test_merged_view_identity_remap_and_answers(tmp_path_factory, mesh8,
                                                host):
    """UNALIGNED segments ride the merged-dictionary path. A member whose
    dictionary already equals the global union must get remap None (its ids
    are global already — the stacker skips the gather); members with partial
    dictionaries get real translation tables. Either way the mesh answer
    matches the host engine."""
    from pinot_tpu.parallel.merged import MergedSegmentView
    schema = _schema()
    rng = np.random.default_rng(83)
    out = tmp_path_factory.mktemp("mc_unaligned")
    builder = SegmentBuilder(schema)
    regions = np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "ME"],
                       dtype=object)

    def make(name, keys, n):
        k = np.concatenate([keys, rng.choice(keys, n - len(keys))])
        rng.shuffle(k)
        cols = {"k": k.astype(np.int32),
                "region": regions[rng.integers(0, 5, n)],
                "q": rng.integers(0, 100, n).astype(np.int32),
                "v": np.round(rng.uniform(0.0, 1000.0, n), 6)}
        return load_segment(builder.build(cols, str(out), name))

    # seg0 spans every key (dict == union); seg1/seg2 see disjoint subsets
    segs = [make("full_0", np.arange(300, dtype=np.int64), 4000),
            make("low_1", np.arange(0, 100, dtype=np.int64), 3000),
            make("high_2", np.arange(200, 300, dtype=np.int64), 3000)]

    remaps = MergedSegmentView(segs).remap("k")
    assert remaps is not None
    assert remaps[0] is None, "full-union member should skip the remap gather"
    assert remaps[1] is not None and remaps[2] is not None
    np.testing.assert_array_equal(remaps[1], np.arange(100))
    np.testing.assert_array_equal(remaps[2], np.arange(200, 300))

    sql = ("SELECT k, SUM(v), COUNT(*) FROM hcdiff GROUP BY k "
           "ORDER BY k LIMIT 400")
    r8 = mesh8.execute(segs, sql)
    rh = host.execute(segs, sql)
    assert_rows_match(_sorted(r8.rows), _sorted(rh.rows), "merged_view")
