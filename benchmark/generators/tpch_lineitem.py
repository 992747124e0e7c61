"""Generator `tpch_lineitem`: the seven columns of TPC-H's LINEITEM that Q1
(pricing summary report) and Q6 (forecasting revenue change) read, by dbgen's
rules (TPC-H rev. 3, clause 4.2.3).

Imports numpy only. `tables(cfg)` gives the sorted value table (dictionary) of
every column but `l_extendedprice`; `segment(cfg, seed, i, n)` gives segment
i's rows from `[seed, i]` alone: codes into those tables, and the price as
values. One independent draw a row:

    order date   uniform over 1992-01-01..1998-08-02 (not stored)
    l_shipdate   order date + U[1, 121]: 1992-01-02..1998-12-01, INT yyyymmdd
    receipt date ship date + U[1, 30] (drawn, not stored)
    l_returnflag R or A with equal chance where receipt date <= 1995-06-17,
                 else N
    l_linestatus O where ship date > 1995-06-17, else F
    l_quantity   U[1, 50];  l_discount U[0, 10] and l_tax U[0, 8], hundredths
    l_extendedprice  quantity x retail price in cents, retail price = 90000 +
                 ((partkey / 10) mod 20001) + 100 x (partkey mod 1000),
                 partkey U[1, 2,000,000]: 90,000..10,494,950

so the four groups of Q1 are (A,F) (N,F) (N,O) (R,F) at about 24.6%, 0.65%,
50.1% and 24.6% of the rows. The first rows of every segment walk every key
space (ship day, quantity, discount, tax), as `ssb_flat`'s do, so that every
dictionary value occurs in every segment. `tables`, which a run calls first,
asks the program's counter names whether it widens INT arithmetic
(`needs_widened_arithmetic`).
"""

import numpy as np

FIRST_ORDER, LAST_ORDER = "1992-01-01", "1998-08-02"
SHIP_AFTER = (1, 121)         # days from the order to the shipment
RECEIPT_AFTER = (1, 30)       # days from the shipment to the receipt
CUTOFF = "1995-06-17"         # dbgen's CURRENTDATE
FLAGS, STATUSES = ("A", "N", "R"), ("F", "O")
PARTKEYS = 2_000_000          # SF10: 200,000 x 10 parts


def needs_widened_arithmetic() -> None:
    """A clean failure, before anything is built, on a program whose INT
    arithmetic wraps in int32: Q1's `l_extendedprice * (100 - l_discount) *
    (100 + l_tax)` is up to 1.1e11 a row, and such a program's `sum_charge`
    reads off by 0.9998 with no error (PERF.md section 6, PR 35): it would be
    timed under a comparison it fails. Told by the counter the widening
    brought: a light import that does not open JAX."""
    from pinot_tpu.query import stats
    if "widenedAggLaunches" not in getattr(stats, "COUNTER_KEYS", ()):
        raise SystemExit(
            "tpch10-lineitem needs a program that widens INT arithmetic which "
            "leaves int32 (the program has no `widenedAggLaunches` counter): "
            "TPC-H Q1's charge would wrap in silence")


def _ship_days() -> np.ndarray:
    first = np.datetime64(FIRST_ORDER) + SHIP_AFTER[0]
    last = np.datetime64(LAST_ORDER) + SHIP_AFTER[1]
    return np.arange(first, last + 1)


def _yyyymmdd(days: np.ndarray) -> np.ndarray:
    y = days.astype("datetime64[Y]").astype(int) + 1970
    m = days.astype("datetime64[M]").astype(int) % 12 + 1
    d = (days - days.astype("datetime64[M]")).astype(int) + 1
    return y * 10000 + m * 100 + d


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """dbgen's P_RETAILPRICE in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def tables(cfg) -> dict:
    """column -> sorted value table, for every column but the price."""
    needs_widened_arithmetic()
    return {"l_returnflag": np.array(FLAGS), "l_linestatus": np.array(STATUSES),
            "l_shipdate": _yyyymmdd(_ship_days()),
            "l_quantity": np.arange(1, 51), "l_discount": np.arange(0, 11),
            "l_tax": np.arange(0, 9)}


def segment(cfg, seed: int, i: int, n: int) -> dict:
    """Segment i's columns: codes for the columns in `tables`, values for
    `l_extendedprice`."""
    rng = np.random.default_rng([seed, i])
    ship_days = _ship_days()
    orders = int((np.datetime64(LAST_ORDER) - np.datetime64(FIRST_ORDER))
                 .astype(int)) + 1
    cutoff = int((np.datetime64(CUTOFF) - ship_days[0]).astype(int))
    walk = np.arange(min(n, ship_days.size))

    order = rng.integers(0, orders, n, dtype=np.int32)
    # the ship day as a code: day 0 is the first order date + 1
    ship = order + rng.integers(SHIP_AFTER[0], SHIP_AFTER[1] + 1, n,
                                dtype=np.int32) - SHIP_AFTER[0]
    ship[:walk.size] = walk
    receipt = ship + rng.integers(RECEIPT_AFTER[0], RECEIPT_AFTER[1] + 1, n,
                                  dtype=np.int32)
    returned = np.where(rng.integers(0, 2, n, dtype=np.int8) == 0,
                        FLAGS.index("R"), FLAGS.index("A"))
    qty = rng.integers(1, 51, n, dtype=np.int32)
    disc = rng.integers(0, 11, n, dtype=np.int32)
    tax = rng.integers(0, 9, n, dtype=np.int32)
    qty[:walk.size] = walk % 50 + 1
    disc[:walk.size] = walk % 11
    tax[:walk.size] = walk % 9
    partkey = rng.integers(1, PARTKEYS + 1, n, dtype=np.int32)
    return {
        "l_returnflag": np.where(receipt <= cutoff, returned,
                                 FLAGS.index("N")).astype(np.int32),
        "l_linestatus": (ship > cutoff).astype(np.int32),
        "l_shipdate": ship,
        "l_quantity": qty - 1,       # codes into tables()["l_quantity"]
        "l_discount": disc,
        "l_tax": tax,
        "l_extendedprice": qty * retail_price(partkey),
    }
