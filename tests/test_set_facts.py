"""D13 (PR 32): the set-wide plan folds a leaf by the SET's facts. Four
row-ordered segments with a raw column `pos` (segment i holds [8000 i,
8000 (i + 1))): on the mesh path `WHERE pos < m` and `WHERE pos >= m` answer
as the host executor does, where a plan made on the first segment alone folds
the one to match-all and the other to empty (ROADMAP D13's two cases)."""

import numpy as np
import pytest

from pinot_tpu.parallel.combine import MeshQueryExecutor
from pinot_tpu.parallel.merged import SegmentSetFacts, set_facts
from pinot_tpu.parallel.mesh import default_mesh
from pinot_tpu.query.context import compile_query
from pinot_tpu.query.executor import ServerQueryExecutor
from pinot_tpu.query.planner import plan_segment
from pinot_tpu.schema import DataType, Schema, dimension, metric
from pinot_tpu.segment import (SegmentBuilder, SegmentGeneratorConfig,
                               load_segment)

ROWS = 8000


@pytest.fixture(scope="module")
def segments(tmp_path_factory):
    root = tmp_path_factory.mktemp("set_facts")
    schema = Schema("t", [dimension("k", DataType.STRING),
                          metric("pos", DataType.INT),
                          metric("v", DataType.INT),
                          metric("maybe", DataType.INT)])
    out = []
    for i in range(4):
        pos = np.arange(ROWS * i, ROWS * (i + 1), dtype=np.int32)
        cols = {"k": [f"k{j % 5}" for j in range(ROWS)], "pos": pos,
                "v": (pos % 7).astype(np.int32),
                # nulls in the LAST segment alone
                "maybe": [None if i == 3 and j % 2 else int(j)
                          for j in range(ROWS)]}
        path = SegmentBuilder(schema, SegmentGeneratorConfig(
            no_dictionary_columns=["pos", "v", "maybe"])).build(
            cols, str(root), f"t_{i}")
        out.append(load_segment(path))
    return out


CASES = {
    "lt_past_first_max": "pos < 8010",          # D13: folded to match-all
    "gte_at_first_max": "pos >= 8000",          # D13: folded to empty
    "lt_inside_first": "pos < 100",
    "gt_last": "pos > 31990",
    "between_across": "pos BETWEEN 7990 AND 24010",
    "eq_in_third": "pos = 20000",
    "in_second_and_fourth": "pos IN (9000, 31000)",
    "lte_all": "pos <= 40000",
    "lt_none": "pos < 0",
    "null_in_last_alone": "maybe IS NULL",
    "not_null": "maybe IS NOT NULL",
}


@pytest.mark.parametrize("devices", (1, 4))
@pytest.mark.parametrize("case", sorted(CASES))
def test_mesh_answers_as_the_host_over_row_ordered_segments(segments, case,
                                                            devices):
    sql = (f"SELECT k, COUNT(*), SUM(v) FROM t WHERE {CASES[case]} "
           "GROUP BY k ORDER BY k LIMIT 10")
    want = ServerQueryExecutor().execute(segments, sql)
    got = MeshQueryExecutor(default_mesh(devices)).execute(segments, sql)
    assert [tuple(r) for r in got.rows] == [tuple(r) for r in want.rows]
    count = f"SELECT COUNT(*) FROM t WHERE {CASES[case]}"
    assert MeshQueryExecutor(default_mesh(devices)).execute(
        segments, count).rows == ServerQueryExecutor().execute(
        segments, count).rows


@pytest.mark.parametrize("case", ("lt_past_first_max", "gte_at_first_max"))
def test_routed_subset_of_the_resident_set_folds_by_the_set(segments, case):
    """The two D13 cases through the served entry, routed to the last two of
    the four resident segments."""
    import jax
    from pinot_tpu.query.aggregates import make_agg
    from pinot_tpu.query.reduce import merge_segment_results, reduce_to_result
    sql = f"SELECT COUNT(*), SUM(v) FROM t WHERE {CASES[case]}"
    ctx = compile_query(sql, segments[0].schema)
    mex = MeshQueryExecutor(default_mesh(1))
    p = mex.prepare_partial(ctx, segments[2:], segments)
    (outs, finish, _, _), = mex.dispatch_prepared([p])
    part = p.decode(finish(jax.device_get(outs))[0])
    aggs = [make_agg(f) for f in ctx.aggregations]
    got = reduce_to_result(ctx, merge_segment_results([part], aggs), aggs, [])
    want = ServerQueryExecutor().execute(segments[2:], ctx)
    assert [tuple(r) for r in got.rows] == [tuple(r) for r in want.rows]


def test_set_facts_are_the_sets(segments):
    facts = set_facts(segments)
    assert isinstance(facts, SegmentSetFacts)
    assert set_facts(segments[:1]) is segments[0]
    pos = facts.column("pos")
    assert (pos.min_value, pos.max_value) == (0, 4 * ROWS - 1)
    assert segments[0].column("pos").max_value == ROWS - 1
    assert facts.num_docs == pos.num_docs == 4 * ROWS
    assert facts.column("maybe").meta["hasNulls"] is True
    assert not segments[0].column("maybe").meta.get("hasNulls", False)
    assert pos.bloom_filter is None
    assert facts.schema is segments[0].schema and facts.name == "t_0"
    # the first segment alone decides `pos >= 8000` (empty); the set does not
    ctx = compile_query("SELECT COUNT(*) FROM t WHERE pos >= 8000",
                        segments[0].schema)
    assert plan_segment(ctx, segments[0]).kind == "empty"
    assert plan_segment(ctx, facts, scan_docs=4 * ROWS).kind == "device"
