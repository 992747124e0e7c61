"""INT arithmetic that can leave int32 is widened, never wrapped (PR 36).

`engine/expr.py` is the one evaluator of the host paths (numpy: the host
executor, reduce, post-aggregation) and of the device kernels (jax.numpy).
Upstream's Addition / Subtraction / MultiplicationTransformFunction compute
in double; HEAD before PR 36 kept the operands' int32 and wrapped in silence
(`SUM(price * (100 - disc) * (100 + tax))` over 5,000 rows read -5.66e10 for
1.55e14). On numpy the operands go to int64 (float64 past 2^63); under
jax.numpy, which has no 64-bit type on the chip, to float32 where the plan's
ranges say the result can leave int32, and nowhere else: SSB's
`lo_extendedprice * lo_discount` is the int32 program it was."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.engine import expr as ex
from pinot_tpu.engine import kernels
from pinot_tpu.query.aggregates import make_agg
from pinot_tpu.query.predicate import FilterProgram
from pinot_tpu.sql.parser import parse_query

N = 4096
LINEITEM = {"p": (90_000, 10_494_950), "d": (0, 10), "t": (0, 8),
            "q": (1, 50)}
SSB = {"lo_extendedprice": (90_000, 10_000_000), "lo_discount": (0, 10)}


def _arg(sql_expr):
    return parse_query(f"SELECT SUM({sql_expr}) FROM x").select[0][0].args[0]


def _columns(ranges, seed=0):
    rng = np.random.default_rng(seed)
    cols = {c: rng.integers(lo, hi + 1, N).astype(np.int32)
            for c, (lo, hi) in ranges.items()}
    for c, (lo, hi) in ranges.items():      # the corners occur
        cols[c][0], cols[c][1] = lo, hi
    return cols


def _python(sql_expr, cols):
    """The expression over python's whole numbers, row by row."""
    names = sorted(cols)
    return [eval(sql_expr, {}, dict(zip(names, (int(cols[c][i])
                                                for c in names))))
            for i in range(N)]


BACKENDS = [pytest.param(np, id="numpy"), pytest.param(jnp, id="jnp")]
# (expression, the ranges of its columns): every result passes 2^31
LEAVES = [
    pytest.param("p * (100 - d) * (100 + t)", LINEITEM, id="times-q1-charge"),
    pytest.param("p * q * q", LINEITEM, id="times-three-columns"),
    pytest.param("a + b", {"a": (2_000_000_000, 2_147_483_647),
                           "b": (1_000_000_000, 2_147_483_647)}, id="plus"),
    pytest.param("a - b", {"a": (-2_147_483_648, -2_000_000_000),
                           "b": (1_000_000_000, 2_147_483_647)}, id="minus"),
    pytest.param("a * 3000", {"a": (1_000_000, 2_000_000)},
                 id="times-literal"),
]


@pytest.mark.parametrize("xp", BACKENDS)
@pytest.mark.parametrize("sql_expr,ranges", LEAVES)
def test_result_past_int32_agrees_with_whole_numbers(sql_expr, ranges, xp):
    cols = _columns(ranges)
    want = np.array(_python(sql_expr, cols), dtype=np.float64)
    assert np.abs(want).max() > 2 ** 31
    env = cols if xp is np else {c: jnp.asarray(v) for c, v in cols.items()}
    got = np.asarray(ex.eval_expr(_arg(sql_expr), env, xp, ranges))
    if xp is np:
        assert got.dtype == np.int64
        assert got.tolist() == _python(sql_expr, cols)      # exact
    else:
        assert got.dtype == np.float32
        assert np.abs(got.astype(np.float64) / want - 1).max() <= 2e-7


# every result stays inside int32
FITS = [
    pytest.param("lo_extendedprice * lo_discount", SSB, id="times-ssb-q1"),
    pytest.param("p * (100 - d)", LINEITEM, id="times-q1-disc-price"),
    pytest.param("p + q - d", LINEITEM, id="plus-minus"),
    pytest.param("100 - d", LINEITEM, id="minus-literal"),
]


@pytest.mark.parametrize("xp", BACKENDS)
@pytest.mark.parametrize("sql_expr,ranges", FITS)
def test_result_inside_int32_is_the_integers_it_was(sql_expr, ranges, xp):
    cols = _columns(ranges)
    env = cols if xp is np else {c: jnp.asarray(v) for c, v in cols.items()}
    got = np.asarray(ex.eval_expr(_arg(sql_expr), env, xp, ranges))
    assert got.dtype == (np.int64 if xp is np else np.int32)
    assert got.tolist() == _python(sql_expr, cols)
    assert not any(ex.widen_marks(_arg(sql_expr), ranges))


def test_ssb_q1_product_lowers_as_at_head():
    """`SUM(lo_extendedprice * lo_discount)`'s row: the int32 multiply and
    the one convert to float32 that HEAD's `l * r` gave, to the letter."""
    arg = _arg("lo_extendedprice * lo_discount")
    shapes = {c: jax.ShapeDtypeStruct((N,), jnp.int32) for c in SSB}

    def ours(cols):
        return ex.eval_expr(arg, cols, jnp, SSB).ravel().astype(jnp.float32)

    def heads(cols):
        return (cols["lo_extendedprice"] * cols["lo_discount"]
                ).ravel().astype(jnp.float32)
    text = jax.jit(ours).lower(shapes).as_text()
    assert text == jax.jit(heads).lower(shapes).as_text().replace(
        "jit_heads", "jit_ours")
    assert "stablehlo.multiply" in text and "xi32>" in text


def _spec(aggs_sql, ranges, keys=8):
    q = parse_query(f"SELECT {aggs_sql} FROM x")
    aggs = [make_agg(s[0]) for s in q.select]
    return kernels.KernelSpec(
        FilterProgram(), ("k",), keys,
        tuple((a, a.device_outputs) for a in aggs), {}, N, int_ranges=ranges)


def _lowered(spec, cols):
    shape = jax.ShapeDtypeStruct
    vals = {c: shape((N,), jnp.int32) for c in cols}
    args = ({"k": shape((N,), jnp.int32)}, vals, (), shape((0,), jnp.int32),
            shape((0,), jnp.float32), {}, shape((N,), jnp.bool_),
            shape((1,), jnp.int32), {}, ())
    return jax.jit(kernels.make_kernel_body(spec)).lower(*args).as_text()


def test_ssb_aggregate_kernel_is_heads_program(monkeypatch):
    """The whole GROUP BY scan of an SSB sum: the program HEAD built (no
    operator widened: `widens` patched to never) and the one built from
    SSB's ranges are one text, and the launch does not count as widened."""
    sql = "SUM(lo_extendedprice * lo_discount), COUNT(*)"
    spec = _spec(sql, SSB, keys=256)
    assert not kernels.widened(spec)
    ours = _lowered(spec, SSB)
    monkeypatch.setattr(ex, "widens", lambda e, ranges: False)
    assert ours == _lowered(_spec(sql, {}, keys=256), SSB)


def test_q1_charge_kernel_is_widened_and_says_so():
    spec = _spec("SUM(p * (100 - d) * (100 + t)), SUM(p * (100 - d)), "
                 "AVG(q), COUNT(*)", LINEITEM)
    assert kernels.widened(spec)
    marks = kernels._agg_widen_marks(spec)
    # the outer product alone: p * (100 - d) <= 1.05e9 fits
    assert marks[0] == (True, False, False, False)
    assert not any(marks[1]) and marks[2] == ()
    text = _lowered(spec, LINEITEM)
    assert "stablehlo.multiply" in text and "xf32>" in text


def test_signature_holds_the_choices_not_the_ranges():
    """Segments whose min/max differ share a program; a range under which
    another operator leaves int32 builds another."""
    sql = "SUM(p * (100 - d) * (100 + t))"
    a = _spec(sql, LINEITEM)
    b = _spec(sql, dict(LINEITEM, p=(90_100, 10_400_000)))
    c = _spec(sql, dict(LINEITEM, p=(0, 2_147_483_647)))
    assert a.signature() == b.signature()
    assert a.signature() != c.signature()
    assert kernels._agg_widen_marks(c)[0] == (True, True, False, False)


@pytest.mark.parametrize("sql_expr,widened", [
    ("a * b", True),            # two non-literals, ranges unknown
    ("a * 3", False),           # a literal operand
    ("a + b", False),           # no product
    ("a * b * 2", True),
    ("a * f", False),           # f is not of integers: nothing to widen
])
def test_without_ranges_products_of_two_non_literals_widen(sql_expr, widened):
    ranges = {"f": None}
    assert any(ex.widen_marks(_arg(sql_expr), ranges)) == widened
    cols = {"a": jnp.arange(N, dtype=jnp.int32) + 60_000,
            "b": jnp.arange(N, dtype=jnp.int32) + 50_000,
            "f": jnp.ones(N, jnp.float32)}
    got = ex.eval_expr(_arg(sql_expr), cols, jnp, ranges)
    assert (got.dtype == jnp.float32) == (widened or "f" in sql_expr)
    if widened:
        a, b = np.arange(N) + 60_000, np.arange(N) + 50_000
        want = (a * b * (2 if "2" in sql_expr else 1)).astype(np.float64)
        assert np.abs(np.asarray(got, np.float64) / want - 1).max() <= 2e-7


def test_host_long_product_past_int64_goes_to_float64():
    a = np.array([3_000_000_000_000, -4], dtype=np.int64)
    b = np.array([5_000_000_000_000, 7], dtype=np.int64)
    got = ex.eval_expr(_arg("a * b"), {"a": a, "b": b}, np)
    assert got.dtype == np.float64
    assert got.tolist() == [1.5e25, -28.0]
    a = np.array([300_000, -4], dtype=np.int64)
    small = ex.eval_expr(_arg("a * b"), {"a": a, "b": b}, np)
    assert small.dtype == np.int64 and small.tolist() == [15 * 10 ** 17, -28]


def test_int_bounds_are_interval_arithmetic():
    b = ex.int_bounds
    assert b(_arg("p * (100 - d) * (100 + t)"), LINEITEM) \
        == (90_000 * 90 * 100, 10_494_950 * 100 * 108)
    assert b(_arg("d - t"), LINEITEM) == (-8, 10)
    assert b(_arg("p * 1.5"), LINEITEM) == ex._NOT_INTEGERS
    assert b(_arg("p / d"), LINEITEM) == ex._NOT_INTEGERS
    assert b(_arg("p * z"), LINEITEM) is None         # z: no range known
    assert b(_arg("abs(d) * 2"), LINEITEM) is None    # a function: unseen


def test_min_max_of_a_widened_expression_is_exact_on_the_host(tmp_path):
    """MIN/MAX are exact cells: where the argument leaves int32 the plan goes
    to the host (int64) and says why; where it fits it stays on the device."""
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.executor import execute_query
    from pinot_tpu.query.planner import plan_segment
    from pinot_tpu.schema import DataType, Schema, metric
    from pinot_tpu.segment import SegmentBuilder, load_segment
    cols = {c: v for c, v in _columns(LINEITEM).items() if c != "q"}
    schema = Schema("x", [metric(c, DataType.INT) for c in cols])
    seg = load_segment(SegmentBuilder(schema).build(cols, str(tmp_path), "x_0"))
    wide = "SELECT MIN(p * (100 - d) * (100 + t)), MAX(p * (100 - d) * (100 + t)) FROM x WHERE d < 9"
    plan = plan_segment(compile_query(wide, schema), seg)
    assert plan.kind == "host" and "leaves int32" in plan.fallback_reason
    keep = cols["d"] < 9
    charge = (cols["p"].astype(np.int64) * (100 - cols["d"])
              * (100 + cols["t"]))[keep]
    got = execute_query([seg], wide).rows[0]
    assert [int(v) for v in got] == [int(charge.min()), int(charge.max())]
    fits = "SELECT MIN(p * (100 - d)), MAX(p * d), SUM(p * (100 - d) * (100 + t)) FROM x WHERE d < 9"
    assert plan_segment(compile_query(fits, schema), seg).kind == "device"
    got = execute_query([seg], fits).rows[0]
    assert [int(got[0]), int(got[1])] == [
        int((cols["p"] * (100 - cols["d"]))[keep].min()),
        int((cols["p"] * cols["d"])[keep].max())]
    assert abs(got[2] / int(charge.sum()) - 1) <= 2e-7
