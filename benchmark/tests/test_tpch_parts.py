"""The `tpch10-parts` generator (benchmark/generators/tpch_parts.py) and its
three templates' reference specs: `python3 -m pytest benchmark/tests -q` from
the root of the repo, on the CPU.

Part keys over dbgen's range and near uniform, the commit date dbgen's order
date + 30..90 days (2,466 days in all) and the ship date + 1..121, prices by
dbgen's formula; and each template's spec, through the reference's partial,
merge and finish, against a brute-force numpy evaluation over one small
segment.
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.harness import cells, reference  # noqa: E402

SEED = 4300000043
ROWS = 1 << 16


@pytest.fixture(scope="module")
def config():
    return cells.read_json(cells.BENCH, "configs", "tpch10-parts.json")


@pytest.fixture(scope="module")
def gen(config):
    return cells.load_generator(config)


@pytest.fixture(scope="module")
def tables(config, gen):
    return gen.tables(config)


def _days(yyyymmdd):
    return np.array([f"{d // 10000:04d}-{d // 100 % 100:02d}-{d % 100:02d}"
                     for d in np.asarray(yyyymmdd)], dtype="datetime64[D]")


def test_tables_are_the_columns_domains(config, tables):
    by_name = {c["name"]: c for c in config["schema"]}
    assert tables["l_partkey"].tolist()[:3] == [1, 2, 3]
    assert len(tables["l_partkey"]) == 2_000_000 == config["parts"]
    assert len(tables["l_commitdate"]) == 2466
    assert (_days(tables["l_commitdate"][[0, -1]])
            == np.array(["1992-01-31", "1998-10-31"],
                        dtype="datetime64[D]")).all()
    for col, table in tables.items():
        c = by_name[col]
        assert len(table) == c["cardinality"], col
        assert (table[0], table[-1]) == (c["min"], c["max"]), col
        assert (np.diff(table) > 0).all(), col
    assert "l_extendedprice" not in tables


@pytest.mark.parametrize("i", [0, 5])
def test_segment_follows_dbgens_rules(config, gen, tables, i):
    d = gen.draws(config, SEED, i, ROWS)
    cols = gen.segment(config, SEED, i, ROWS)
    assert sorted(cols) == sorted(c["name"] for c in config["schema"])
    # part keys: dbgen's range, about uniform (ten bands of 200,000 keys)
    pk = tables["l_partkey"][cols["l_partkey"]]
    assert pk.min() >= 1 and pk.max() <= 2_000_000
    bands = np.bincount((pk - 1) // 200_000, minlength=10)
    assert bands.size == 10 and (abs(bands / ROWS - 0.1) < 0.01).all()
    # the dates from the order date, every row (the walk's too)
    order = np.datetime64("1992-01-01") + d["order"]
    ship = _days(tables["l_shipdate"][cols["l_shipdate"]])
    commit = _days(tables["l_commitdate"][cols["l_commitdate"]])
    assert ((ship - order).astype(int) >= 1).all()
    assert ((ship - order).astype(int) <= 121).all()
    assert ((commit - order).astype(int) >= 30).all()
    assert ((commit - order).astype(int) <= 90).all()
    # the walk: every ship day, commit day, quantity and discount occurs
    for col in ("l_shipdate", "l_commitdate", "l_quantity", "l_discount"):
        assert len(np.unique(cols[col])) == len(tables[col]), col
    # the price: quantity x dbgen's retail price of the part, in cents
    qty = tables["l_quantity"][cols["l_quantity"]]
    retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    assert np.array_equal(cols["l_extendedprice"], qty * retail)
    assert cols["l_extendedprice"].min() >= 90_000
    assert cols["l_extendedprice"].max() <= 10_494_950
    again = gen.segment(config, SEED, i, ROWS)
    assert all(np.array_equal(again[c], cols[c]) for c in cols)


def _brute(name, spec, cols, tables):
    """The template over one segment by plain numpy, the SQL's whole ORDER
    BY (the sum descending, then the key)."""
    a, b = spec["filters"][0]["args"][0], spec["filters"][1]["args"][0]
    ship = tables["l_shipdate"][cols["l_shipdate"]]
    keep = (ship >= a) & (ship < b)
    col = "l_commitdate" if name.endswith("commitdate") else "l_partkey"
    key = tables[col][cols[col]][keep]
    qty = tables["l_quantity"][cols["l_quantity"]][keep].astype(np.int64)
    price = cols["l_extendedprice"][keep].astype(np.int64)
    disc = tables["l_discount"][cols["l_discount"]][keep]
    rows = {}
    for k, q, p, dc in zip(key.tolist(), qty.tolist(), price.tolist(),
                           disc.tolist()):
        r = rows.setdefault(k, [0, 0, 99, -1])
        r[0] += q
        r[1] += p
        r[2], r[3] = min(r[2], dc), max(r[3], dc)
    order = sorted(rows, key=lambda k: (-rows[k][0], k))[:100]
    if name == "top_100_parts_details":
        return [[k, float(rows[k][0]), float(rows[k][1]), rows[k][2],
                 rows[k][3]] for k in order]
    return [[k, float(rows[k][0])] for k in order]


@pytest.mark.parametrize("name,start", [
    ("top_100_parts", 19940301), ("top_100_parts", 19920301),
    ("top_100_parts_details", 19950101), ("top_100_commitdate", 19960601),
    ("top_100_commitdate", 19930701)])
def test_spec_is_the_brute_force_answer(config, gen, tables, name, start):
    t = cells.read_json(cells.BENCH, "queries", "tpch", name + ".json")
    spec = reference.bind(t["reference"], {"a": start, "b": start + 20000})
    cols = gen.segment(config, SEED, 3, ROWS)
    got = reference.finish(spec, reference.merge(
        [reference.partial(spec, cols, tables)]), tables)
    assert got == _brute(name, spec, cols, tables)
    assert len(got) == 100
