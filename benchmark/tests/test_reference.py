"""The reference evaluator and the comparison (benchmark/harness/reference.py):
`python3 -m pytest benchmark/tests -q` from the root of the repo, on the CPU.

Every aggregate alone and all together against a python loop, the merge over
segments, each planted fault seen by `compare`, every control on an answer of
several aggregates, the truncated bfloat16 where the rounded one passes, sums past float64's 2**53 exact, and the one-aggregate
form held to PR 33's evaluator (reference_pr33.py, frozen) to the bit.
"""

import copy
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import reference_pr33  # noqa: E402

from benchmark import selftest  # noqa: E402
from benchmark.harness import cells, reference, traffic  # noqa: E402

LIMIT = 2e-5
ROWS = 3000


def small_table():
    """Two segments of 3,000 rows: a string and an int dimension (codes into
    sorted tables), a dictionary metric, two raw ones. The second segment's
    groups are of other sizes than the first's, and it alone holds the least
    and the greatest `price`."""
    tables = {"region": np.array(["ASIA", "EUROPE", "NORTH", "SOUTH"]),
              "year": np.array([1996, 1997, 1998]),
              "qty": np.arange(1, 51)}
    segs = []
    for i, skew in enumerate(([.25, .25, .25, .25], [.7, .1, .1, .1])):
        rng = np.random.default_rng([35, i])
        segs.append({
            "region": rng.choice(4, ROWS, p=skew).astype(np.int32),
            "year": rng.integers(0, 3, ROWS, dtype=np.int32),
            "qty": rng.integers(0, 50, ROWS, dtype=np.int32),
            "price": rng.integers(90_000, 10_000_000, ROWS, dtype=np.int32),
            "disc": rng.integers(0, 11, ROWS, dtype=np.int32)})
    segs[1]["price"][:2] = (17, 11_000_000)
    segs[1]["disc"][:2] = 4
    return tables, segs


TABLES, SEGS = small_table()
WHOLE = {c: np.concatenate([s[c] for s in SEGS]) for c in SEGS[0]}
CHARGE = [{"coef": 10000, "columns": ["price"]},
          {"coef": 100, "columns": ["price", "qty"]},
          {"coef": -100, "columns": ["price", "disc"]},
          {"coef": -1, "columns": ["price", "disc", "qty"]}]
AGGS = {"count": {"name": "n", "fn": "count"},
        "sum": {"name": "charge", "fn": "sum", "terms": CHARGE},
        "avg": {"name": "mean_price", "fn": "avg",
                "terms": [{"coef": 1, "columns": ["price"]}]},
        "min": {"name": "least", "fn": "min", "column": "price"},
        "max": {"name": "greatest", "fn": "max", "column": "price"}}
FILTER = [{"column": "disc", "op": "between", "args": [2, 8]},
          {"column": "region", "op": "in", "args": ["ASIA", "NORTH", "SOUTH"]}]


def spec_of(fns, group=(), order=(), limit=None, filters=FILTER):
    aggs = [AGGS[f] for f in fns]
    spec = {"filters": filters, "group_by": list(group), "aggregates": aggs,
            "select": list(group) + [a["name"] for a in aggs],
            "order_by": [list(o) for o in order]}
    if limit:
        spec["limit"] = limit
    return spec


def evaluate(spec, segs=SEGS, precision="exact"):
    return selftest.evaluate(spec, segs, TABLES, precision)


ALL = ("sum", "avg", "min", "max", "count")
BY = ("region", "year")
CASES = {
    **{f"{f}-alone": spec_of([f]) for f in ALL},
    **{f"{f}-grouped": spec_of([f], BY, [("region", "asc"), ("year", "desc")])
       for f in ALL},
    "all-together": spec_of(ALL),
    "all-grouped": spec_of(ALL, BY, [("year", "asc"), ("region", "asc")]),
    "by-sum-desc-limit": spec_of(ALL, BY, [("charge", "desc")], 5),
    "by-avg-asc-limit": spec_of(ALL, BY, [("mean_price", "asc")], 4),
    "by-count-desc-then-key": spec_of(
        ["count", "avg"], ["year"], [("n", "desc"), ("year", "asc")]),
    "by-min-asc": spec_of(["min", "max"], ["region"], [("least", "asc")], 2),
    "no-filter": spec_of(ALL, ["region"], [("region", "asc")], filters=[]),
    "over-no-rows": spec_of(ALL, filters=[
        {"column": "disc", "op": "gt", "args": [99]}]),
    "over-no-rows-grouped": spec_of(ALL, BY, filters=[
        {"column": "disc", "op": "gt", "args": [99]}]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_evaluator_equals_a_python_loop(case):
    spec = CASES[case]
    got = evaluate(spec)
    want = selftest.brute_force(spec, WHOLE, TABLES)
    assert [selftest.plain(r) for r in got] == [selftest.plain(r) for r in want]
    c = reference.compare(spec, json.loads(json.dumps(got)), got, LIMIT)
    assert c == {"wrong": 0, "why": "", "sum_gap": 0.0, "count_wrong": 0}
    if case == "over-no-rows":
        assert got == [[0.0, None, None, None, 0]]
    if case == "over-no-rows-grouped":
        assert got == []


def test_an_avg_is_divided_after_the_merge():
    spec = spec_of(["avg", "count"], ["region"], [("region", "asc")],
                   filters=[])
    asia = evaluate(spec)[0]
    parts = [evaluate(spec, [s])[0] for s in SEGS]
    assert asia[0] == "ASIA" and parts[0][2] * 2 < parts[1][2]
    rows = WHOLE["region"] == 0
    assert asia[1] == float(WHOLE["price"][rows].astype(np.int64).sum()) / rows.sum()
    mean_of_means = (parts[0][1] + parts[1][1]) / 2
    assert abs(mean_of_means - asia[1]) > 1e-4 * asia[1]


def test_a_parts_counts_stay_third():
    """tests/test_bytime_served.py reads a part's counts as `part[2]`."""
    spec = CASES["all-grouped"]
    part = reference.partial(spec, SEGS[0], TABLES)
    assert part[2] is part.counts and part[0] is part.keys
    assert reference.merge([part, part])[2].sum() == 2 * part.counts.sum()


def test_an_extreme_in_the_segment_left_out_is_missed():
    spec = spec_of(["min", "max", "count"], filters=[])
    want = evaluate(spec)
    assert want == [[17, 11_000_000, 2 * ROWS]]
    without = evaluate(spec, SEGS[:1])
    assert without[0][0] > 17 and without[0][1] < 11_000_000
    c = reference.compare(spec, without, want, LIMIT)
    assert c["count_wrong"] == 1 and not c["wrong"]


def swap(a, b):
    def fault(rows, at):
        for r in rows:
            r[at[a]], r[at[b]] = r[at[b]], r[at[a]]
        return rows
    return fault


def cell(name, change, row=1):
    def fault(rows, at):
        rows[row][at[name]] = change(rows[row][at[name]])
        return rows
    return fault


FAULTS = {   # name: (what is done to the reference's own rows, what must read)
    "a-sum-scaled": (cell("charge", lambda v: v * (1 + 1e-4)), "sum_gap"),
    "an-avg-scaled": (cell("mean_price", lambda v: v * (1 - 1e-4)), "sum_gap"),
    "a-count-one-too-high": (cell("n", lambda v: v + 1), "count_wrong"),
    "a-min-one-too-low": (cell("least", lambda v: v - 1), "count_wrong"),
    "a-max-as-a-near-float": (cell("greatest", lambda v: v + 0.5),
                              "count_wrong"),
    "an-avg-missing": (cell("mean_price", lambda v: None), "sum_gap"),
    "sum-and-avg-swapped": (swap("charge", "mean_price"), "sum_gap"),
    "min-and-max-swapped": (swap("least", "greatest"), "count_wrong"),
    "a-row-missing": (lambda rows, at: rows[:-1], "wrong"),
    "a-row-twice": (lambda rows, at: rows[:-1] + rows[:1], "wrong"),
    "a-key-renamed": (cell("region", lambda v: "NOWHERE"), "wrong"),
    "a-cell-missing": (lambda rows, at: [r[:-1] for r in rows], "wrong"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_compare_sees_a_fault_in_a_row_of_several_aggregates(fault):
    spec = CASES["all-grouped"]
    want = evaluate(spec)
    assert len(want) == 9
    at = {name: i for i, name in enumerate(spec["select"])}
    change, number = FAULTS[fault]
    got = change(copy.deepcopy(want), at)
    c = reference.compare(spec, got, want, LIMIT)
    others = {"wrong", "sum_gap", "count_wrong"} - {number}
    if number == "sum_gap":
        assert c["sum_gap"] > 4 * LIMIT, c
        if "scaled" in fault:
            assert 0.9e-4 < c["sum_gap"] < 1.1e-4, c
    else:
        assert c[number] == 1 and c["why"], c
    assert not any(c[k] for k in others), c


@pytest.mark.parametrize("by", ["charge", "mean_price", "n", "least"])
def test_compare_sees_a_wrong_order_by_an_aggregate(by):
    spec = spec_of(ALL, BY, [(by, "desc")])
    want = evaluate(spec)
    assert not reference.compare(spec, want, want, LIMIT)["wrong"]
    got = copy.deepcopy(want)
    got[2], got[5] = got[5], got[2]
    c = reference.compare(spec, got, want, LIMIT)
    assert c["wrong"] == 1 and c["why"].startswith("row order"), c
    # rows the ORDER BY cannot separate may swap: sums and averages closer
    # than the limit, equal counts; a MIN one lower may not
    near = copy.deepcopy(want)
    at = spec["select"].index(by)
    closer = {"charge": lambda v: v * (1 - 1e-7), "n": lambda v: v,
              "mean_price": lambda v: v * (1 - 1e-7), "least": lambda v: v - 1}
    near[3][at] = closer[by](near[2][at])
    swapped = near[:2] + [near[3], near[2]] + near[4:]
    assert reference.compare(spec, swapped, near, LIMIT)["wrong"] == \
        (1 if by == "least" else 0)


@pytest.mark.parametrize("precision", sorted(reference.ROUNDINGS))
def test_a_lowered_precision_fails_an_answer_of_several_aggregates(precision):
    spec = CASES["all-grouped"]
    want = evaluate(spec)
    lowered = evaluate(spec, precision=precision)
    c = reference.compare(spec, lowered, want, LIMIT)
    assert c["sum_gap"] > 3 * LIMIT and not c["wrong"] and not c["count_wrong"]
    by_name = reference.gaps_by_name(spec, lowered, want)
    assert set(by_name) == {"charge", "mean_price"}
    assert min(by_name.values()) > LIMIT and max(by_name.values()) == c["sum_gap"]


def test_truncation_fails_a_sum_whose_roundings_cancel():
    """One group of 2**20 rows of six-digit values: the errors of a rounding
    to the nearest bfloat16 cancel under the limit, a truncation's do not."""
    n = 1 << 20
    cols = {"v": np.random.default_rng(35).integers(100_000, 1_000_000, n)}
    spec = {"group_by": [], "select": ["s", "m"], "aggregates": [
        {"name": "s", "fn": "sum", "terms": [{"columns": ["v"]}]},
        {"name": "m", "fn": "avg", "terms": [{"columns": ["v"]}]}]}

    def gap(precision):
        part = reference.partial(spec, cols, {}, precision)
        return reference.compare(
            spec, reference.finish(spec, reference.merge([part]), {}),
            [[int(cols["v"].sum()), cols["v"].sum() / n]], LIMIT)["sum_gap"]
    assert gap("exact") == 0.0
    assert gap("bf16") < LIMIT
    assert 100 * LIMIT < gap("bf16_truncated") < 2.0 ** -7


def test_a_segment_left_out_fails_an_answer_of_several_aggregates():
    spec = CASES["all-grouped"]
    want = evaluate(spec)
    left_out = reference.compare(spec, evaluate(spec, SEGS[:1]), want, LIMIT)
    assert left_out["count_wrong"] == 1 and left_out["sum_gap"] > 0.1


def test_a_sum_past_float64_is_exact():
    """Q1's three-column product at the full table's scale: 2**22 rows of
    about 1.5e11 in four groups. int64 adds to python's own whole numbers;
    `finish` prints each within 2**-52 of it."""
    rng = np.random.default_rng(35)
    n = 1 << 22
    inv = rng.integers(0, 4, n)
    val = rng.integers(10 ** 11, 15 * 10 ** 10, n)
    val[:1000] *= -3
    want = [sum(int(v) for v in val[inv == g]) for g in range(4)]
    assert min(abs(w) for w in want) > 2 ** 53
    got = reference._add_by_group(inv, val, 4)
    assert got.dtype == np.int64 and [int(g) for g in got] == want
    parts = [reference.Part(np.arange(4), {"s": got},
                            np.bincount(inv, minlength=4), {}, {})] * 16
    total = reference.merge(parts).sums["s"]
    assert [int(t) for t in total] == [16 * w for w in want]
    spec = {"group_by": [], "select": ["s"],
            "aggregates": [{"name": "s", "fn": "sum", "terms": []}]}
    one = reference.Part(np.zeros(1, np.int64), {"s": total[:1]},
                         np.array([n]), {}, {})
    printed = reference.finish(spec, one, {})[0][0]
    assert abs(printed - 16 * want[0]) <= 2.0 ** -52 * abs(16 * want[0])
    with pytest.raises(OverflowError):
        reference._add_by_group(np.zeros(4, np.int64),
                                np.full(4, 1 << 61, dtype=np.int64), 1)
    with pytest.raises(OverflowError):
        reference._row_values(
            [{"coef": 1 << 40, "columns": ["price", "price"]}],
            lambda c: SEGS[0][c])


def test_a_fractional_coefficient_is_carried_in_float64():
    spec = spec_of(["count"], ["year"], [("year", "asc")])
    spec["aggregates"] = [{"name": "n", "fn": "count"},
                          {"name": "net", "fn": "sum", "terms": [
                              {"coef": 0.01, "columns": ["price"]},
                              {"coef": -0.0001, "columns": ["price", "disc"]}]}]
    spec["select"] = ["year", "net", "n"]
    got = evaluate(spec)
    whole = spec_of(["count"], ["year"], [("year", "asc")])
    whole["aggregates"] = [{"name": "net", "fn": "sum", "terms": [
        {"coef": 100, "columns": ["price"]},
        {"coef": -1, "columns": ["price", "disc"]}]}]
    whole["select"] = ["year", "net"]
    for g, w in zip(got, evaluate(whole)):
        assert g[0] == w[0] and abs(g[1] - w[1] / 1e4) <= 1e-12 * w[1] / 1e4


def test_columns_are_read_once():
    spec = CASES["all-grouped"]
    assert reference.columns_read(spec) == ["disc", "price", "qty", "region",
                                            "year"]
    q1 = cells.read_json(cells.BENCH, "queries", "check", "q1-shape.json")
    assert reference.columns_read(q1["reference"]) == [
        "lo_discount", "lo_extendedprice", "lo_orderdate", "lo_quantity",
        "p_mfgr", "s_region"]
    old = cells.read_json(cells.BENCH, "queries", "ssb", "q2.1.json")
    assert reference.columns_read(old["reference"]) == \
        reference_pr33.columns_read(old["reference"])


OLD_FORM = sorted(f"{family}/{f[:-5]}" for family in ("ssb", "tiles")
                  for f in os.listdir(os.path.join(cells.BENCH, "queries",
                                                   family)))


@pytest.fixture(scope="module")
def ssb():
    config = cells.read_json(cells.BENCH, "configs", "ssb10-flat.json")
    gen = cells.load_generator(config)
    return gen.tables(config), [gen.segment(config, 35, i, ROWS)
                                for i in range(3)]


def test_the_old_form_is_all_seventeen_templates():
    assert len(OLD_FORM) == 17


@pytest.mark.parametrize("name", OLD_FORM)
def test_the_old_form_answers_as_pr33s_evaluator_to_the_bit(ssb, name):
    tables, segs = ssb
    t = selftest.template(name)
    assert "aggregate" in t["reference"] and "agg" in t["reference"]["select"]
    rng = np.random.default_rng([35, OLD_FORM.index(name)])

    def answer(evaluator, spec, keep=3, precision="exact"):
        return selftest.evaluate(spec, segs[:keep], tables, precision,
                                 evaluator)

    def bits(rows):
        return [[(type(x).__name__, x.hex() if isinstance(x, float) else x)
                 for x in r] for r in rows]
    for _ in range(3):
        spec = reference.bind(t["reference"],
                              traffic.draw_holes(t, tables, rng))
        want = answer(reference_pr33, spec)
        assert bits(answer(reference, spec)) == bits(want)
        served = {"itself": want, "bf16": answer(reference, spec, 3, "bf16"),
                  "a segment short": answer(reference, spec, 2),
                  "a row short": want[:-1], "turned": want[1:] + want[:1],
                  "the last key renamed": want[:-1] + [
                      ["NOWHERE" if isinstance(x, str) else x for x in r]
                      for r in want[-1:]]}
        assert bits(served["bf16"]) == bits(answer(reference_pr33, spec, 3,
                                                   "bf16"))
        for what, got in served.items():
            assert reference.compare(spec, got, want, LIMIT) == \
                reference_pr33.compare(spec, got, want, LIMIT), what
