"""Generator `tpch_flat`: TPC-H's LINEITEM flattened with the order and
customer attributes Q3 (the shipping priority query, clause 2.4.3) reads, by
dbgen's rules (TPC-H rev. 3, clause 4.2.3), whole orders a segment.

Imports numpy and, for the rules of dates and prices, the generator beside it
(`tpch_lineitem`). `tables(cfg)` gives the sorted value table of every column
but `l_extendedprice`, the 16.8M order keys among them, from `cfg` alone;
`segment(cfg, seed, i, n)` gives segment i's rows from `[seed, i]` alone:
codes into those tables, and the price as values. A segment holds n / 4 whole
orders, the orders i * n / 4 onwards, so the `l_orderkey` dictionaries of two
segments share no key, as in any table pushed in order:

    l_orderkey      dbgen's sparse keys: order o (from 0) is
                    (o // 8) * 32 + o % 8 + 1, 8 of every 32 used
    lines           a shuffled multiset of 1..7 lines an order, as many of
                    each, the rest orders of 4: mean 4, so n rows exactly
    o_orderdate     uniform over 1992-01-01..1998-08-02, an order's
    c_mktsegment    one of the 5 market segments, uniform, an order's
    o_shippriority  0, as dbgen writes it
    l_shipdate      o_orderdate + U[1, 121], a line's
    l_discount      U[0, 10] hundredths, a line's
    l_extendedprice quantity U[1, 50] x dbgen's retail price of a part
                    U[1, 2,000,000], in cents (`tpch_lineitem.retail_price`)

2,406 orders spread evenly through a segment walk the order dates (every
date in every segment, so those dictionaries agree) and the segments, and the
first line of each such order ships 1..121 days after it in turn: as far as
whole orders allow, the ship dates too. Spread, not the first orders: walking
dates in consecutive orders puts a day's worth of orders of one segment in
one tile of 1,024 rows, and Q3 then passes over 64 rows there on some
(SEGMENT, DATE), so that the sort takes every row (PERF.md, section 4). `tables`, which a run calls first, asks the
program's counter names whether it answers a GROUP BY past the dense key space
on the device (`needs_sparse_groupby`).
"""

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "tpch_lineitem_rules",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "tpch_lineitem.py"))
lineitem = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lineitem)

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
LINES = (1, 7)                # lines an order
MEAN_LINES = 4


def needs_sparse_groupby() -> None:
    """A clean failure, before anything is built, on a program that answers a
    GROUP BY over 16.8M order ids on the host (its dense cap is 2^21 ids) or
    not at all: such a program would be timed at another layer than the one
    this configuration measures. Told by the counter the sorted-groups regime
    brought: a light import that does not open JAX."""
    from pinot_tpu.query import stats
    if "sparseGroupByLaunches" not in getattr(stats, "COUNTER_KEYS", ()):
        raise SystemExit(
            "tpch10-flat needs a program that answers a GROUP BY past the "
            "dense key space from its sorted groups on the device (the "
            "program has no `sparseGroupByLaunches` counter): TPC-H Q3 groups "
            "by 16.8M order ids")


def order_key(o: np.ndarray) -> np.ndarray:
    """dbgen's O_ORDERKEY of order o (from 0): 8 keys used of every 32."""
    return (o // 8) * 32 + o % 8 + 1


def _order_days() -> np.ndarray:
    return np.arange(np.datetime64(lineitem.FIRST_ORDER),
                     np.datetime64(lineitem.LAST_ORDER) + 1)


def tables(cfg) -> dict:
    """column -> sorted value table, for every column but the price."""
    needs_sparse_groupby()
    orders = int(cfg["rows"]) // MEAN_LINES
    return {"l_orderkey": order_key(np.arange(orders, dtype=np.int32)),
            "o_orderdate": lineitem._yyyymmdd(_order_days()),
            "o_shippriority": np.array([0]),
            "c_mktsegment": np.array(SEGMENTS),
            "l_shipdate": lineitem._yyyymmdd(lineitem._ship_days()),
            "l_discount": np.arange(0, 11)}


def lines_per_order(orders: int, rng) -> np.ndarray:
    """As many orders of each of 1..7 lines as fit, the rest of 4, shuffled:
    MEAN_LINES x orders lines in all."""
    each, rest = divmod(orders, LINES[1] - LINES[0] + 1)
    lens = np.concatenate([np.repeat(np.arange(LINES[0], LINES[1] + 1,
                                               dtype=np.int32), each),
                           np.full(rest, MEAN_LINES, dtype=np.int32)])
    rng.shuffle(lens)
    return lens


def segment(cfg, seed: int, i: int, n: int) -> dict:
    """Segment i's columns, n / 4 whole orders: codes for the columns in
    `tables`, values for `l_extendedprice`."""
    if n % MEAN_LINES:
        raise ValueError(f"a segment of whole orders has a multiple of "
                         f"{MEAN_LINES} rows, not {n}")
    rng = np.random.default_rng([seed, i])
    orders = n // MEAN_LINES
    days = _order_days().size
    lens = lines_per_order(orders, rng)
    date = rng.integers(0, days, orders, dtype=np.int32)
    market = rng.integers(0, len(SEGMENTS), orders, dtype=np.int32)
    walk = np.arange(min(orders, days), dtype=np.int32)
    walkers = walk * max(orders // days, 1)      # spread through the segment
    date[walkers] = walk
    market[walkers] = walk % len(SEGMENTS)

    of_row = np.repeat(np.arange(orders, dtype=np.int32), lens)
    # the ship day as a code: day 0 is the first order date + 1
    after = rng.integers(1, lineitem.SHIP_AFTER[1] + 1, n, dtype=np.int32)
    first_line = np.cumsum(lens) - lens
    after[first_line[walkers]] = walk % lineitem.SHIP_AFTER[1] + 1
    disc = rng.integers(0, 11, n, dtype=np.int32)
    disc[:min(11, n)] = np.arange(min(11, n))
    qty = rng.integers(1, 51, n, dtype=np.int32)
    partkey = rng.integers(1, lineitem.PARTKEYS + 1, n, dtype=np.int32)
    return {
        "l_orderkey": (i * orders + of_row).astype(np.int32),
        "o_orderdate": date[of_row],
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "c_mktsegment": market[of_row],
        "l_shipdate": date[of_row] + after - lineitem.SHIP_AFTER[0],
        "l_discount": disc,
        "l_extendedprice": qty * lineitem.retail_price(partkey),
    }
