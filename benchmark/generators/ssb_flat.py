"""Generator `ssb_flat`: SSB's lineorder, flattened with the attributes of date,
part, supplier and customer that the thirteen SSB queries read.

Imports numpy only. `tables(cfg)` gives every dimension column's sorted value
table (its dictionary); `segment(cfg, seed, i, n)` gives segment i's rows from
`[seed, i]` alone: dimension columns as codes into those tables, metric columns
as values. Shapes follow dbgen (SSB rev. 3): uniform draws, order dates
1992-01-01..1998-08-02, quantity 1..50, discount 0..10, a part price of
900.00..2,000.00 in cents, extendedprice = quantity x price, revenue =
extendedprice x (100 - discount) / 100, supplycost = 6 x price / 10; 5 regions
x 5 nations x 10 cities for supplier and for customer; 5 mfgr x 5 categories x
40 brands. The first rows of every segment walk every key space so that every
dictionary value occurs in every segment.
"""

import numpy as np

REGIONS = {
    "AFRICA": ["ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE"],
    "AMERICA": ["ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES"],
    "ASIA": ["CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM"],
    "EUROPE": ["FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM"],
    "MIDDLE EAST": ["EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA"],
}
MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun",
          "Jul", "Aug", "Sep", "Oct", "Nov", "Dec"]
FIRST_DAY, LAST_DAY = "1992-01-01", "1998-08-02"
PRICE_LO, PRICE_HI = 90_000, 200_000      # cents


def _natural():
    """Every dimension attribute by natural id (day, brand, city)."""
    days = np.arange(np.datetime64(FIRST_DAY), np.datetime64(LAST_DAY) + 1)
    y = days.astype("datetime64[Y]").astype(int) + 1970
    m = days.astype("datetime64[M]").astype(int) % 12 + 1
    d = (days - days.astype("datetime64[M]")).astype(int) + 1
    doy = (days - days.astype("datetime64[Y]")).astype(int)
    date = {
        "lo_orderdate": y * 10000 + m * 100 + d,
        "d_year": y,
        "d_yearmonthnum": y * 100 + m,
        "d_weeknuminyear": doy // 7 + 1,
        "d_yearmonth": np.array([f"{MONTHS[mm - 1]}{yy}"
                                 for yy, mm in zip(y, m)]),
    }
    brand = np.arange(1000)
    part = {
        "p_mfgr": np.array([f"MFGR#{b // 200 + 1}" for b in brand]),
        "p_category": np.array([f"MFGR#{b // 200 + 1}{b // 40 % 5 + 1}"
                                for b in brand]),
        "p_brand1": np.array([f"MFGR#{b // 200 + 1}{b // 40 % 5 + 1}"
                              f"{b % 40 + 1}" for b in brand]),
    }
    region, nation, city = [], [], []
    for r, nations in REGIONS.items():
        for n in nations:
            for k in range(10):
                region.append(r)
                nation.append(n)
                city.append(f"{n:<9.9}{k}")
    geo = {"region": np.array(region), "nation": np.array(nation),
           "city": np.array(city)}
    return date, part, geo


def _coded(natural_values):
    """(sorted table, natural id -> code)."""
    table, code = np.unique(natural_values, return_inverse=True)
    return table, code.astype(np.int32)


def _dimension_maps():
    date, part, geo = _natural()
    out = {}
    for col, vals in date.items():
        out[col] = ("day",) + _coded(vals)
    for col, vals in part.items():
        out[col] = ("brand",) + _coded(vals)
    for who, base in (("s", "scity"), ("c", "ccity")):
        for level, vals in geo.items():
            out[f"{who}_{level}"] = (base,) + _coded(vals)
    return out


_MAPS = None


def _maps():
    global _MAPS
    if _MAPS is None:
        _MAPS = _dimension_maps()
    return _MAPS


def tables(cfg) -> dict:
    """column -> sorted value table, for every dimension column."""
    out = {col: table for col, (_, table, _) in _maps().items()}
    out["lo_quantity"] = np.arange(1, 51)
    out["lo_discount"] = np.arange(0, 11)
    return out


def segment(cfg, seed: int, i: int, n: int) -> dict:
    """Segment i's columns: codes for the columns in `tables`, values for the
    other metrics."""
    rng = np.random.default_rng([seed, i])
    maps = _maps()
    sizes = {"day": len(maps["lo_orderdate"][2]), "brand": 1000,
             "scity": 250, "ccity": 250}
    walk = np.arange(min(n, max(sizes.values())))
    base = {}
    for key, size in sizes.items():
        ids = rng.integers(0, size, n, dtype=np.int32)
        ids[:walk.size] = walk % size
        base[key] = ids
    cols = {col: code[base[key]] for col, (key, _, code) in maps.items()}
    qty = rng.integers(1, 51, n, dtype=np.int32)
    disc = rng.integers(0, 11, n, dtype=np.int32)
    qty[:walk.size] = walk % 50 + 1
    disc[:walk.size] = walk % 11
    price = rng.integers(PRICE_LO, PRICE_HI + 1, n, dtype=np.int32)
    ext = qty * price
    cols["lo_quantity"] = qty - 1          # codes into tables()["lo_quantity"]
    cols["lo_discount"] = disc
    cols["lo_extendedprice"] = ext
    cols["lo_revenue"] = (ext.astype(np.int64) * (100 - disc) // 100
                          ).astype(np.int32)
    cols["lo_supplycost"] = (6 * price.astype(np.int64) // 10).astype(np.int32)
    return cols
