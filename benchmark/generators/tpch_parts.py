"""Generator `tpch_parts`: the six columns of TPC-H's LINEITEM that the Top-N
queries of the Druid TPC-H benchmark read (`top_100_parts`, `_details`,
`_filter`, `top_100_commitdate`), by dbgen's rules (TPC-H rev. 3, clause
4.2.3).

Imports numpy and, for the rules of dates and prices, the generator beside it
(`tpch_lineitem`). `tables(cfg)` gives the sorted value table (dictionary) of
every column but `l_extendedprice`, the part keys among them, from `cfg`
alone; `segment(cfg, seed, i, n)` gives segment i's rows from `[seed, i]`
alone: codes into those tables, and the price as values. One independent
draw a row:

    order date      uniform over 1992-01-01..1998-08-02 (not stored)
    l_partkey       U[1, parts]: 2,000,000 at SF10 (`tpch_lineitem.PARTKEYS`)
    l_shipdate      order date + U[1, 121]: 1992-01-02..1998-12-01
    l_commitdate    order date + U[30, 90]: 1992-01-31..1998-10-31, 2,466
    l_quantity      U[1, 50]; l_discount U[0, 10] hundredths
    l_extendedprice quantity x dbgen's retail price of the part, in cents
                    (`tpch_lineitem.retail_price`), stored raw

The first rows of every segment walk the small key spaces (the ship and
commit days, quantities, discounts) with their order dates chosen so that
both gaps stay in dbgen's ranges, as `tpch_lineitem`'s first rows walk
theirs, so those dictionaries agree from segment to segment. The 2M part keys
are not walked (that would take half a segment): a segment of 4Mi rows holds
about 1.75M of them, the 16 part-key dictionaries disagree, and the set is
planned in a merged id space. `tables`, which a run calls first, asks the
program's counter names whether it cuts such a GROUP BY's ORDER BY ... LIMIT
on the device (`needs_dense_cut`).
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

COMMIT_AFTER = (30, 90)       # days from the order to the commit date


def needs_dense_cut() -> None:
    """A clean failure, before anything is built, on a program that fetches
    the sort regime's dense table whole (2M part keys, up to 40 MB an answer)
    and orders it on the host, and scatters every row into 2M segments for a
    MIN or a MAX: such a program would be timed at another layer than the one
    this configuration measures. Told by the counter the cut brought: a light
    import that does not open JAX."""
    from pinot_tpu.query import stats
    if "denseTrimmedLaunches" not in getattr(stats, "COUNTER_KEYS", ()):
        raise SystemExit(
            "tpch10-parts needs a program that cuts a GROUP BY over a dense "
            "key space past chunk_cap to its ORDER BY ... LIMIT on the device "
            "(the program has no `denseTrimmedLaunches` counter): a Top-N "
            "over 2M part keys would fetch the whole table and order it on "
            "the host")


def parts(cfg) -> int:
    """How many part keys the configuration's scale factor holds."""
    return int(cfg.get("parts", lineitem.PARTKEYS))


def _commit_days() -> np.ndarray:
    first = np.datetime64(lineitem.FIRST_ORDER) + COMMIT_AFTER[0]
    last = np.datetime64(lineitem.LAST_ORDER) + COMMIT_AFTER[1]
    return np.arange(first, last + 1)


def tables(cfg) -> dict:
    """column -> sorted value table, for every column but the price."""
    needs_dense_cut()
    return {"l_partkey": np.arange(1, parts(cfg) + 1, dtype=np.int32),
            "l_shipdate": lineitem._yyyymmdd(lineitem._ship_days()),
            "l_commitdate": lineitem._yyyymmdd(_commit_days()),
            "l_quantity": np.arange(1, 51), "l_discount": np.arange(0, 11)}


def draws(cfg, seed: int, i: int, n: int) -> dict:
    """Segment i's draws: the order day, the ship and commit days as codes
    (day 0 the first order date + 1, + 30), part keys, quantities and
    discounts as values."""
    rng = np.random.default_rng([seed, i])
    orders = int((np.datetime64(lineitem.LAST_ORDER)
                  - np.datetime64(lineitem.FIRST_ORDER)).astype(int)) + 1
    ship_first, commit_first = lineitem.SHIP_AFTER[0], COMMIT_AFTER[0]
    order = rng.integers(0, orders, n, dtype=np.int32)
    ship = order + rng.integers(lineitem.SHIP_AFTER[0],
                                lineitem.SHIP_AFTER[1] + 1, n,
                                dtype=np.int32) - ship_first
    commit = order + rng.integers(COMMIT_AFTER[0], COMMIT_AFTER[1] + 1, n,
                                  dtype=np.int32) - commit_first
    qty = rng.integers(1, 51, n, dtype=np.int32)
    disc = rng.integers(0, 11, n, dtype=np.int32)
    partkey = rng.integers(1, parts(cfg) + 1, n, dtype=np.int32)
    # the walk: row w ships on day w and commits on day w, its order placed
    # as late as both gaps allow and not before the first order date
    w = np.arange(min(n, lineitem._ship_days().size), dtype=np.int32)
    lead = COMMIT_AFTER[1] - COMMIT_AFTER[0]           # 60 days
    order[:w.size] = np.clip(w - lead, 0, orders - 1)
    ship[:w.size] = w
    commit[:w.size] = np.where(w < _commit_days().size, w,
                               order[:w.size] + w % (lead + 1))
    qty[:w.size] = w % 50 + 1
    disc[:w.size] = w % 11
    return {"order": order, "ship": ship, "commit": commit, "qty": qty,
            "disc": disc, "partkey": partkey}


def segment(cfg, seed: int, i: int, n: int) -> dict:
    """Segment i's columns: codes for the columns in `tables`, values for
    `l_extendedprice`."""
    d = draws(cfg, seed, i, n)
    return {
        "l_partkey": d["partkey"] - 1,      # codes into tables()["l_partkey"]
        "l_quantity": d["qty"] - 1,
        "l_extendedprice": d["qty"] * lineitem.retail_price(d["partkey"]),
        "l_discount": d["disc"],
        "l_shipdate": d["ship"],
        "l_commitdate": d["commit"],
    }
