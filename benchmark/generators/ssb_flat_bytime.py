"""Generator `ssb_flat_bytime`: `ssb_flat`'s table pushed in order-date order,
one time range a segment (how a Pinot table with a time column is laid out:
an offline push or a realtime commit covers one range of time).

`tables(cfg)` is `ssb_flat`'s, so the templates' literal domains do not
change. `segment(cfg, seed, i, n)` is `ssb_flat`'s from `[seed, i]` except for
the day of a row: drawn uniformly (from `[seed, i, 1]`) out of segment i's
range `[2406 * i // S, 2406 * (i + 1) // S)` of SSB's 2,406 days, S =
`cfg["segments"]`. The first rows walk every brand and city, as there, and the
segment's OWN days only: the INT date columns (`lo_orderdate`, `d_year`,
`d_yearmonthnum`, `d_weeknuminyear`) then get per-segment dictionaries and
real min/max from the builder. Imports numpy and the sibling generator only.
"""

import importlib.util
import os

import numpy as np

_FLAT = None


def _flat():
    """The sibling `ssb_flat.py`, loaded by path as the harness loads this."""
    global _FLAT
    if _FLAT is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "ssb_flat.py")
        spec = importlib.util.spec_from_file_location("bench_ssb_flat", path)
        _FLAT = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_FLAT)
    return _FLAT


def tables(cfg) -> dict:
    return _flat().tables(cfg)


def day_range(cfg, i: int) -> tuple:
    """[lo, hi): the natural day ids segment i's order dates are drawn from."""
    maps = _flat()._maps()
    days, s = len(maps["lo_orderdate"][2]), int(cfg["segments"])
    return days * i // s, days * (i + 1) // s


def segment(cfg, seed: int, i: int, n: int) -> dict:
    flat = _flat()
    cols = flat.segment(cfg, seed, i, n)    # every other column as it is there
    lo, hi = day_range(cfg, i)
    day = np.random.default_rng([seed, i, 1]).integers(lo, hi, n,
                                                       dtype=np.int32)
    walk = np.arange(min(n, hi - lo))
    day[:walk.size] = lo + walk
    for col, (key, _, code) in flat._maps().items():
        if key == "day":
            cols[col] = code[day]
    return cols
