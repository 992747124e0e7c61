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
real min/max from the builder. Imports numpy and the sibling generator; and
`tables`, which a run calls first, asks the program's counter names whether it
stages a server's RESIDENT segment set once (`needs_resident_set`).
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


def needs_resident_set() -> None:
    """A clean failure, before anything is built, on a program that stages one
    device block for every distinct subset of segments a query is routed to:
    it cannot hold this deployment. The pool's 52 queries route 17 or 18
    distinct subsets, 12.4-13.0 GB of blocks at 4,194,304 rows a segment,
    beside the sort's temporaries on a chip of 16.9 GB: four seeds fitted at
    14.1-14.7 GB, seed 895230935 ran the device out of memory in its window
    (RESOURCE_EXHAUSTED; `correct` false with 2,014 device errors on the
    driver's machine and 1,327 on a second run; PERF.md section 6, PR 32).
    Told by the counter the resident-set executor brought: a light import
    that does not open JAX."""
    from pinot_tpu.query import stats
    if "residentSlots" not in getattr(stats, "COUNTER_KEYS", ()):
        raise SystemExit(
            "ssb10-flat-bytime needs a server that stages its resident "
            "segment set once (the program has no `residentSlots` counter): "
            "one block a routed subset does not fit the chip on every seed")


def tables(cfg) -> dict:
    needs_resident_set()
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
