"""Device bitmap filter indexes (PR 12): packed-word bitwise kernels vs the
LUT-gather path, planner selectivity gating, and the one-snapshot fix for
`host_filter_mask` on consuming segments.

Every assertion here is differential: the bitmap path must be byte-identical
with the LUT path and with the host evaluator — the bitmap plane is a pure
performance representation, never a semantics change.
"""

import numpy as np
import pytest

from pinot_tpu.query.context import compile_query
from pinot_tpu.query.executor import ServerQueryExecutor, host_filter_mask
from pinot_tpu.query.planner import plan_segment, select_bitmap_leaves
from pinot_tpu.query.predicate import LutLeaf
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.segment.mutable import MutableSegment
from pinot_tpu.segment.reader import load_segment
from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig

N = 2500
RNG = np.random.default_rng(20260805)

SCHEMA = Schema("bm", [
    dimension("region"), dimension("cat"),
    dimension("tags", single_value=False),
    metric("v", DataType.LONG), metric("x", DataType.DOUBLE),
])

REGIONS = [f"r{i}" for i in range(8)]
CATS = [f"c{i}" for i in range(5)]


def _columns(n=N, rng=None):
    rng = rng if rng is not None else np.random.default_rng(20260805)
    return {
        "region": [REGIONS[i] for i in rng.integers(0, len(REGIONS), n)],
        "cat": [CATS[i] for i in rng.integers(0, len(CATS), n)],
        "tags": [[f"t{j}" for j in rng.choice(6, rng.integers(1, 4),
                                              replace=False)] for _ in range(n)],
        "v": rng.integers(0, 1000, n).astype(np.int64),
        "x": np.round(rng.uniform(-10, 10, n), 3),
    }


@pytest.fixture(scope="module")
def indexed_segment(tmp_path_factory):
    out = tmp_path_factory.mktemp("bm_idx")
    return load_segment(SegmentBuilder(SCHEMA, SegmentGeneratorConfig(
        inverted_index_columns=["region", "cat"])).build(
            _columns(), str(out), "bm_0"))


@pytest.fixture(scope="module")
def plain_segment(tmp_path_factory):
    """Same data, NO auxiliary indexes — the 'indexes off' differential arm."""
    out = tmp_path_factory.mktemp("bm_plain")
    return load_segment(SegmentBuilder(SCHEMA, SegmentGeneratorConfig()).build(
        _columns(), str(out), "bm_1"))


# -- packed-word construction -------------------------------------------------

def test_bitmap_words_match_forward_column(indexed_segment):
    from pinot_tpu.engine.datablock import block_for
    block = block_for(indexed_segment)
    words = np.asarray(block.bitmap_words("region"))
    reader = indexed_segment.column("region")
    ids = np.asarray(reader.fwd)
    assert words.shape == (reader.cardinality, block.padded // 32)
    for dict_id in range(reader.cardinality):
        unpacked = np.unpackbits(
            words[dict_id].view(np.uint8), bitorder="little")
        np.testing.assert_array_equal(
            unpacked[:indexed_segment.num_docs].astype(bool), ids == dict_id)
        # padding rows must stay zero — popcount counts them otherwise
        assert not unpacked[indexed_segment.num_docs:].any()


def test_bitmap_words_declined_for_high_card_and_mv(indexed_segment):
    from pinot_tpu.engine.datablock import block_for
    block = block_for(indexed_segment)
    assert block.bitmap_words("tags") is None      # multi-value
    assert block.bitmap_words("v") is None         # no dict / numeric raw


# -- fused word-domain kernels ------------------------------------------------

WHERE_TREES = [
    "region = 'r1'",
    "region = 'r1' AND cat = 'c2'",
    "region = 'r1' OR cat = 'c2'",
    "NOT region = 'r1'",
    "NOT (region IN ('r1', 'r2') OR cat = 'c0')",
    "region IN ('r0', 'r3', 'r7') AND NOT cat IN ('c1', 'c4')",
]


def _all_bitmap_spec(seg, sql):
    """KernelSpec with EVERY LutLeaf forced onto the bitmap path."""
    from pinot_tpu.engine import kernels
    from pinot_tpu.engine.datablock import block_for
    ctx = compile_query(sql, SCHEMA)
    plan = plan_segment(ctx, seg)
    block = block_for(seg)
    bm = tuple(i for i, leaf in enumerate(plan.filter_prog.leaves)
               if isinstance(leaf, LutLeaf)
               and block.bitmap_words(leaf.col) is not None)
    plan.bitmap_leaves = bm
    spec = kernels.KernelSpec(plan.filter_prog, (), 1, (), {}, block.padded,
                              bitmap_leaves=bm)
    ex = ServerQueryExecutor()
    inputs = ex._kernel_inputs(plan, spec, block)
    return plan, spec, inputs


@pytest.mark.parametrize("where", WHERE_TREES)
def test_word_domain_mask_matches_host(indexed_segment, where):
    from pinot_tpu.engine import kernels
    sql = f"SELECT COUNT(*) FROM bm WHERE {where}"
    plan, spec, inputs = _all_bitmap_spec(indexed_segment, sql)
    assert spec.bitmap_index, "no bitmap leaves selected — test is vacuous"
    mask = kernels.compute_mask(spec, inputs)[:indexed_segment.num_docs]
    host = host_filter_mask(plan, indexed_segment)
    np.testing.assert_array_equal(np.asarray(mask), host)


@pytest.mark.parametrize("where", WHERE_TREES)
def test_popcount_filter_count_matches_mask(indexed_segment, where):
    from pinot_tpu.engine import kernels
    sql = f"SELECT COUNT(*) FROM bm WHERE {where}"
    plan, spec, inputs = _all_bitmap_spec(indexed_segment, sql)
    count = kernels.compute_filter_count(spec, inputs)
    assert count is not None, "all-bitmap tree must take the popcount path"
    assert count == int(host_filter_mask(plan, indexed_segment).sum())


def test_filter_count_declines_mixed_trees(indexed_segment):
    """A tree with a non-bitmap leaf cannot run fully in the word domain."""
    from pinot_tpu.engine import kernels
    from pinot_tpu.engine.datablock import block_for
    ctx = compile_query(
        "SELECT COUNT(*) FROM bm WHERE region = 'r1' AND v > 500", SCHEMA)
    plan = plan_segment(ctx, indexed_segment)
    block = block_for(indexed_segment)
    # only the low-card region leaf is bitmap-eligible; v's 1000-card dict is
    # not — exactly the mixed tree the popcount path must decline
    bm = tuple(i for i, leaf in enumerate(plan.filter_prog.leaves)
               if isinstance(leaf, LutLeaf)
               and block.bitmap_words(leaf.col) is not None)
    assert bm == (0,)
    spec = kernels.KernelSpec(plan.filter_prog, (), 1, (), {}, block.padded,
                              bitmap_leaves=bm)
    plan.bitmap_leaves = bm
    inputs = ServerQueryExecutor()._kernel_inputs(plan, spec, block)
    assert kernels.compute_filter_count(spec, inputs) is None
    # ...but the per-leaf unpack inside the full mask still agrees
    mask = kernels.compute_mask(spec, inputs)[:indexed_segment.num_docs]
    np.testing.assert_array_equal(np.asarray(mask),
                                  host_filter_mask(plan, indexed_segment))


# -- planner gating -----------------------------------------------------------

def test_select_bitmap_leaves_honors_selectivity_cap(indexed_segment):
    ctx = compile_query("SELECT COUNT(*) FROM bm WHERE region = 'r1'", SCHEMA)
    plan = plan_segment(ctx, indexed_segment)
    from dataclasses import replace
    from pinot_tpu.engine import caps
    old = caps.get_caps()
    caps.set_caps(replace(old, bitmap_sel_cap=0.5))
    try:
        assert select_bitmap_leaves(plan, indexed_segment) == (0,)
        # a cap below the leaf's ~1/8 selectivity rejects it
        caps.set_caps(replace(old, bitmap_sel_cap=0.01))
        assert select_bitmap_leaves(plan, indexed_segment) == ()
    finally:
        caps.set_caps(old)


def test_select_bitmap_leaves_skips_mutable_segments():
    seg = MutableSegment("m", SCHEMA)
    for i in range(40):
        seg.index({"region": REGIONS[i % 8], "cat": CATS[i % 5],
                   "tags": ["t0"], "v": i, "x": 0.5})
    ctx = compile_query("SELECT COUNT(*) FROM bm WHERE region = 'r1'", SCHEMA)
    plan = plan_segment(ctx, seg)
    assert select_bitmap_leaves(plan, seg) == ()


# -- end-to-end differential: bitmap on/off/host, indexes on/off --------------

def _rand_where(rng):
    preds = []
    for _ in range(int(rng.integers(1, 4))):
        k = rng.integers(0, 5)
        if k == 0:
            preds.append(f"region = 'r{rng.integers(0, 10)}'")
        elif k == 1:
            vals = ", ".join(f"'c{rng.integers(0, 7)}'"
                             for _ in range(int(rng.integers(1, 4))))
            preds.append(f"cat IN ({vals})")
        elif k == 2:
            preds.append(f"v BETWEEN {rng.integers(0, 400)} "
                         f"AND {rng.integers(400, 1000)}")
        elif k == 3:
            preds.append(f"tags = 't{rng.integers(0, 7)}'")
        else:
            preds.append(f"NOT region IN ('r{rng.integers(0, 8)}', "
                         f"'r{rng.integers(0, 8)}')")
    glue = [" AND " if rng.random() < 0.6 else " OR "
            for _ in range(len(preds) - 1)]
    out = preds[0]
    for g, p in zip(glue, preds[1:]):
        out += g + p
    return out


def _sorted_rows(rows):
    return sorted(tuple(str(c) for c in r) for r in rows)


@pytest.mark.parametrize("seed", range(4))
def test_differential_bitmap_vs_lut_vs_host(indexed_segment, plain_segment,
                                            seed):
    rng = np.random.default_rng(4000 + seed)
    for qi in range(12):
        where = _rand_where(rng)
        sql = (f"SELECT region, COUNT(*), SUM(v) FROM bm WHERE {where} "
               f"GROUP BY region LIMIT 100000")
        want = None
        for seg in (indexed_segment, plain_segment):     # indexes on vs off
            for ex in (ServerQueryExecutor(bitmap_enabled=True),
                       ServerQueryExecutor(bitmap_enabled=False),
                       ServerQueryExecutor(use_device=False)):
                got = _sorted_rows(ex.execute([seg], sql).rows)
                if want is None:
                    want = got
                assert got == want, (
                    f"MISMATCH seed={seed} q={qi} bitmap={ex.bitmap_enabled} "
                    f"device={ex.use_device} "
                    f"indexed={seg is indexed_segment}\n{sql}")


def test_differential_consuming_segment(indexed_segment):
    """Consuming (mutable) segment answers match the committed form: bitmap
    selection is immutable-only, but the toggle must be inert, not wrong."""
    cols = _columns(600, np.random.default_rng(9))
    seg = MutableSegment("m", SCHEMA, inverted_index_columns=["region"])
    for i in range(600):
        seg.index({k: cols[k][i] for k in cols})
    rng = np.random.default_rng(55)
    for _ in range(8):
        sql = (f"SELECT cat, COUNT(*) FROM bm WHERE {_rand_where(rng)} "
               f"GROUP BY cat LIMIT 100000")
        want = None
        for ex in (ServerQueryExecutor(bitmap_enabled=True),
                   ServerQueryExecutor(bitmap_enabled=False),
                   ServerQueryExecutor(use_device=False)):
            got = _sorted_rows(ex.execute([seg], sql).rows)
            if want is None:
                want = got
            assert got == want, f"consuming mismatch: {sql}"


# -- host_filter_mask: one snapshot per leaf on consuming segments ------------

def test_host_filter_mask_survives_dict_id_remap():
    """Regression: the LUT is compiled against one dictionary snapshot; rows
    appended AFTER planning remap dict ids (the sorted dictionary inserts new
    values in the middle). host_filter_mask must bind the LUT, the inverted
    view, and the forward ids to ONE snapshot — mixing the stale compile-time
    LUT with fresh ids selects the wrong value."""
    seg = MutableSegment("m", SCHEMA, inverted_index_columns=["region"])
    for i in range(64):
        seg.index({"region": ["mm", "zz"][i % 2], "cat": "c0",
                   "tags": ["t0"], "v": i, "x": 0.0})
    ctx = compile_query("SELECT COUNT(*) FROM bm WHERE region = 'zz'", SCHEMA)
    plan = plan_segment(ctx, seg)   # LUT over dict ["mm", "zz"]: zz -> id 1
    # "aa" sorts FIRST: every existing id shifts (mm -> 1, zz -> 2)
    for i in range(32):
        seg.index({"region": "aa", "cat": "c0", "tags": ["t0"],
                   "v": 100 + i, "x": 0.0})
    mask = host_filter_mask(plan, seg)
    want = np.zeros(seg.num_docs, dtype=bool)
    want[1:64:2] = True             # the original zz rows, none of the aa rows
    np.testing.assert_array_equal(mask, want)
    # and the executor end-to-end agrees
    got = ServerQueryExecutor().execute([seg], ctx).rows
    assert got == [[32]]


def test_host_filter_mask_mv_snapshot_consistency():
    """Same remap hazard on the MV CSR arrays (flat ids + offsets)."""
    seg = MutableSegment("m", SCHEMA)
    for i in range(50):
        seg.index({"region": "r0", "cat": "c0",
                   "tags": ["mm"] if i % 2 else ["zz"], "v": i, "x": 0.0})
    ctx = compile_query("SELECT COUNT(*) FROM bm WHERE tags = 'zz'", SCHEMA)
    plan = plan_segment(ctx, seg)
    for i in range(30):
        seg.index({"region": "r0", "cat": "c0", "tags": ["aa"],
                   "v": 100 + i, "x": 0.0})
    mask = host_filter_mask(plan, seg)
    want = np.zeros(seg.num_docs, dtype=bool)
    want[0:50:2] = True
    np.testing.assert_array_equal(mask, want)


# -- the clusterConfig knob ---------------------------------------------------

def test_server_bitmap_knob_disables_executor_path(tmp_path):
    from pinot_tpu.cluster.catalog import Catalog
    from pinot_tpu.cluster.deepstore import LocalDeepStore
    from pinot_tpu.cluster.server import ServerNode
    catalog = Catalog()
    catalog.put_property("clusterConfig/server.index.bitmap.enabled", "false")
    deep = LocalDeepStore(str(tmp_path / "deep"))
    node = ServerNode("s0", catalog, deep, str(tmp_path / "s0"))
    assert node.executor.bitmap_enabled is False
    catalog2 = Catalog()
    node2 = ServerNode("s1", catalog2, deep, str(tmp_path / "s1"))
    assert node2.executor.bitmap_enabled is True
