"""The plain reference: one generic numpy evaluator for a template's structured
spec, and the comparison that decides `correct`.

Imports numpy only; nothing of the program. A spec is
    {"filters": [{"column", "op", "args"}...],     conjunctive
     "group_by": [column...],
     "aggregates": [{"name", "fn": "count"}
                    | {"name", "fn": "sum" | "avg", "terms": [{"coef", "columns"}]}
                    | {"name", "fn": "min" | "max", "column"} ...],
     "select": [aggregate name | column ...],      the broker's column order
     "order_by": [[aggregate name | column, "asc" | "desc"] ...],   all selected
     "limit": n}
with "$hole" arguments already replaced by `bind`. The one-aggregate form
`"aggregate": {"fn": ...}` with `"agg"` in `select` means
`"aggregates": [{"name": "agg", "fn": ...}]`. `terms` is a signed sum of
products of columns; an AVG is that sum over the group's count of rows that
passed. Dimension columns arrive as codes into a sorted value table, metrics
as values; every column a term, a MIN or a MAX reads is of integers.

`partial` evaluates one segment, `merge` adds partials by group key (counts
and sums added, the least and the greatest taken), `finish` divides the AVGs
(after the merge: never a mean of means) and orders, cuts and lays out the
rows as the SQL does.

What is exact. COUNT, MIN and MAX are integers and exact. A SUM whose
coefficients are whole is carried in int64 from the row to `finish` and is
exact whatever its size, a product of three fixed-point columns over 67M rows
too (1.5e11 a row, 4e18 a table, where float64's 2**53 = 9e15 ends): below
2**53 one float64 `np.bincount` adds it as before, past it `np.add.at` adds
in int64 (`_add_by_group`). A row's value, or a group's sum of magnitudes,
past 2**63 raises OverflowError; nothing wraps in silence. `finish` rounds
each sum once to the float64 it prints, and an AVG once more in its division:
the reference's own error on a SUM or AVG cell is at most 2**-52 = 2.2e-16 of
the cell, eleven orders under the 2e-5 the program is held to. Only a sum with
a fractional `coef` is carried in float64: `np.bincount` adds in row order, so
a part over n rows is within n * 1.1e-16 of the sum of the rows' magnitudes
(5e-10 at the 4Mi rows of a segment); no template the benchmark holds has one.

A `precision` other than "exact" is a control (`ROUNDINGS`): each row's value
of every SUM and AVG aggregate is brought to bfloat16 before it is added (in
float64). "bf16" rounds to the nearest, ties to even: its errors cancel as the
root of a group's rows, so it fails a selective sum (3e-3 where thousands of
rows are added) and passes an unselective one (8e-6 at 670,000 rows a group).
"bf16_truncated" keeps the float32's high half, rounding toward zero: every
row loses 0 to 2**-7 of itself, nothing cancels, and a sum of values wider than
eight bits reads 2.7e-3 to 2.9e-3 low whatever its rows.
"""

from collections import namedtuple

import numpy as np

# One segment's part of an answer, and the merged whole: the group codes, then
# by group the rows that passed (`counts`), {name: sums} of every SUM and AVG,
# {name: the least} of every MIN and {name: the greatest} of every MAX.
# `counts` stays third, where PR 33's (keys, sums, counts) had it.
Part = namedtuple("Part", "keys sums counts least greatest")

OPS = {
    "eq": lambda v, a: v == a[0],
    "lt": lambda v, a: v < a[0],
    "le": lambda v, a: v <= a[0],
    "gt": lambda v, a: v > a[0],
    "ge": lambda v, a: v >= a[0],
    "between": lambda v, a: (v >= a[0]) & (v <= a[1]),
    "in": lambda v, a: np.isin(v, np.asarray(a, dtype=v.dtype)),
}
ADDED = ("sum", "avg")          # judged by `sum_gap`; the other cells are exact
EXTREME = {     # fn: (its field of a Part, the fold, where the fold starts)
    "min": ("least", np.minimum.at, np.iinfo(np.int64).max),
    "max": ("greatest", np.maximum.at, np.iinfo(np.int64).min)}
INT64_END = 2.0 ** 63 * (1 - 2.0 ** -20)    # float64 estimates stay under it


def bind(spec, holes: dict):
    """The spec with every "$name" replaced by the hole's value."""
    if isinstance(spec, dict):
        return {k: bind(v, holes) for k, v in spec.items()}
    if isinstance(spec, list):
        return [bind(v, holes) for v in spec]
    if isinstance(spec, str) and spec.startswith("$"):
        return holes[spec[1:]]
    return spec


def aggregates(spec) -> list:
    """The spec's named aggregates; the one-aggregate form is one named "agg"."""
    if "aggregates" in spec:
        return spec["aggregates"]
    return [dict(spec["aggregate"], name="agg")]


def columns_read(spec) -> list:
    """The columns a spec reads, each once: filters, group keys, and every
    aggregate's terms or column."""
    cols = [f["column"] for f in spec.get("filters", [])]
    cols += spec.get("group_by", [])
    for a in aggregates(spec):
        for t in a.get("terms", []):
            cols += t["columns"]
        cols += [a["column"]] if "column" in a else []
    return sorted(set(cols))


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    u = (u + (((u >> 16) & 1) + 0x7FFF)) & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def truncated_to_bf16(x: np.ndarray) -> np.ndarray:
    """The float32's high 16 bits (bfloat16 toward zero), as float64."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    return (u & np.uint32(0xFFFF0000)).view(np.float32).astype(np.float64)


# The controls that lower the precision: {`precision`: what it does to each
# row's value of a SUM or AVG}. run.py puts every one in the program's place.
ROUNDINGS = {"bf16": to_bf16, "bf16_truncated": truncated_to_bf16}


def _typed(args, table):
    if table.dtype.kind in "US":
        return [str(a) for a in args]
    return [int(a) for a in args]


def _reach(v: np.ndarray) -> int:
    """The largest magnitude in an integer array."""
    return max(int(v.max(initial=0)), -int(v.min(initial=0)))


def _row_values(terms, value) -> np.ndarray:
    """Each row's signed sum of products: int64 and exact where every `coef`
    is whole, else float64. `value(column)` gives the rows' values."""
    whole = all(float(t.get("coef", 1)).is_integer() for t in terms)
    val, reach = 0, 0
    for t in terms:
        coef = int(t.get("coef", 1)) if whole else float(t.get("coef", 1))
        term, most = coef, abs(coef)
        for c in t["columns"]:
            v = value(c)
            term = term * (v.astype(np.int64) if whole else v)
            most *= _reach(v)
        val, reach = val + term, reach + most
    if whole and reach >= 2 ** 63:
        raise OverflowError(f"a row's value can reach {reach}: past int64")
    return val


def _add_by_group(inv, val, size: int) -> np.ndarray:
    """The sum of `val` by group. float64 values: one `np.bincount`, in row
    order. int64 values: exact. Where rows x the largest magnitude stays below
    2**53 one float64 bincount holds every partial sum exactly; else
    `np.add.at` adds in int64, which cannot wrap where a group's sum of
    magnitudes (a float64 estimate, good to 1e-9) is under 2**63."""
    if val.dtype.kind == "f":
        return np.bincount(inv, weights=val, minlength=size)
    if _reach(val) * val.size < 2 ** 53:
        return np.bincount(inv, weights=val, minlength=size).astype(np.int64)
    most = np.bincount(inv, weights=np.abs(val.astype(np.float64)),
                       minlength=size).max()
    if most >= INT64_END:
        raise OverflowError(f"a group's sum can reach {most}: past int64")
    out = np.zeros(size, dtype=np.int64)
    np.add.at(out, inv, val)
    return out


def _extreme_by_group(fn: str, inv, val, size: int) -> np.ndarray:
    """The least (`min`) or the greatest (`max`) of `val` by group, int64."""
    _, fold, start = EXTREME[fn]
    out = np.full(size, start, dtype=np.int64)
    fold(out, inv, val)
    return out


def partial(spec, cols: dict, tables: dict, precision: str = "exact") -> Part:
    """One segment's part of the answer; the group codes are the mixed-radix
    number of the group columns' table codes."""
    n = len(next(iter(cols.values())))
    mask = np.ones(n, dtype=bool)
    for f in spec.get("filters", []):
        col, op = f["column"], OPS[f["op"]]
        if col in tables:
            table = tables[col]
            mask &= op(table, _typed(f["args"], table))[cols[col]]
        else:
            mask &= op(cols[col], [int(a) for a in f["args"]])
    idx = np.flatnonzero(mask)
    key = np.zeros(idx.size, dtype=np.int64)
    for col in spec.get("group_by", []):
        key = key * len(tables[col]) + cols[col][idx]
    keys, inv = np.unique(key, return_inverse=True)

    def value(c):
        v = cols[c][idx]
        return tables[c][v] if c in tables else v
    part = Part(keys, {}, np.bincount(inv, minlength=keys.size).astype(np.int64),
                {}, {})
    for a in aggregates(spec):
        if a["fn"] in ADDED:
            val = _row_values(a["terms"], value)
            if precision != "exact":
                val = ROUNDINGS[precision](val)
            part.sums[a["name"]] = _add_by_group(inv, val, keys.size)
        elif a["fn"] in EXTREME:
            getattr(part, EXTREME[a["fn"]][0])[a["name"]] = _extreme_by_group(
                a["fn"], inv, value(a["column"]), keys.size)
        elif a["fn"] != "count":
            raise ValueError(f"aggregate {a}: no such fn")
    return part


def merge(parts) -> Part:
    """The parts as one, by group key: counts and sums added, the least of the
    MINs and the greatest of the MAXs."""
    uniq, inv = np.unique(np.concatenate([p.keys for p in parts]),
                          return_inverse=True)

    def joined(field, name=None):
        held = [getattr(p, field) for p in parts]
        return np.concatenate([h if name is None else h[name] for h in held])
    out = Part(uniq, {}, _add_by_group(inv, joined("counts"), uniq.size), {}, {})
    for name in parts[0].sums:
        out.sums[name] = _add_by_group(inv, joined("sums", name), uniq.size)
    for fn, (field, _, _) in EXTREME.items():
        for name in getattr(parts[0], field):
            getattr(out, field)[name] = _extreme_by_group(
                fn, inv, joined(field, name), uniq.size)
    return out


def _finished(a, merged: Part) -> np.ndarray:
    """One aggregate's cells, a group each."""
    if a["fn"] == "count":
        return merged.counts
    if a["fn"] in EXTREME:
        return getattr(merged, EXTREME[a["fn"]][0])[a["name"]]
    total = merged.sums[a["name"]].astype(np.float64)
    return total / merged.counts if a["fn"] == "avg" else total


def finish(spec, merged: Part, tables: dict) -> list:
    """The rows the SQL asks for, in its order: python lists of str/int/float,
    columns as in `select`."""
    keys = merged.keys
    group = spec.get("group_by", [])
    aggs = aggregates(spec)
    if not group and not keys.size:
        # aggregates with no GROUP BY answer one row even over no rows:
        # COUNT 0, SUM 0.0, and None (the SQL's NULL) for AVG, MIN and MAX
        over_none = {a["name"]: {"count": 0, "sum": 0.0}.get(a["fn"])
                     for a in aggs}
        return [[over_none[c] for c in spec["select"]]]
    decoded, rest = {}, keys.copy()
    for col in reversed(group):
        card = len(tables[col])
        decoded[col] = tables[col][rest % card]
        rest //= card
    for a in aggs:
        decoded[a["name"]] = _finished(a, merged)
    order = np.arange(keys.size)
    for col, direction in reversed(spec.get("order_by", [])):
        v = decoded[col][order]
        if direction == "desc":
            # stable descending: sort the negated ranks
            _, rank = np.unique(v, return_inverse=True)
            v = -rank
        order = order[np.argsort(v, kind="stable")]
    order = order[:int(spec.get("limit", keys.size))]
    rows = []
    for i in order:
        rows.append([_plain(decoded[c][i]) for c in spec["select"]])
    return rows


def _plain(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return str(v)


# -- the comparison ------------------------------------------------------------

def _layout(spec):
    """Where a row's cells are: {position: fn} of the aggregates `select`
    names, and the positions of the group keys."""
    sel = spec["select"]
    named = {a["name"]: a["fn"] for a in aggregates(spec)}
    fn_at = {i: named[c] for i, c in enumerate(sel) if c in named}
    return fn_at, [i for i in range(len(sel)) if i not in fn_at]


def _gap(got, want) -> float:
    """|got - want| / max(|want|, 1); a nan or a cell that is no number counts
    as the widest. None (an AVG over no rows) matches only None."""
    if want is None or got is None:
        return 0.0 if got is want else float("inf")
    try:
        gap = abs(float(got) - want) / max(abs(want), 1.0)
    except (TypeError, ValueError):
        return float("inf")
    return gap if gap == gap else float("inf")


def _same(got, want) -> bool:
    """An exact cell: the same whole number, or None for None."""
    if want is None or got is None:
        return got is want
    try:
        return int(got) == int(want) and float(got) == float(want)
    except (TypeError, ValueError, OverflowError):
        return False


def compare(spec, got: list, want: list, sum_limit: float) -> dict:
    """One answer against the reference. Returns
        {"wrong": 0|1, "why": str, "sum_gap": float, "count_wrong": 0|1}
    `wrong` is structural: row count, group keys, or the SQL's row order
    (positions may differ only among rows the ORDER BY does not separate: equal
    order keys, or sums and averages closer together than `sum_limit`).
    `sum_gap` is the widest |got - want| / max(|want|, 1) over every SUM and
    AVG cell of the answer; the exact cells (COUNT, MIN, MAX) must be equal,
    and one that is not sets `count_wrong`."""
    return _compare(spec, got, want, sum_limit)[0]


def gaps_by_name(spec, got: list, want: list) -> dict:
    """{aggregate name: its widest gap} over the SUM and AVG cells of the
    answer's rows, as far as their keys are the reference's."""
    return _compare(spec, got, want, 0.0)[1]


def _compare(spec, got, want, sum_limit):
    """(what `compare` returns, what `gaps_by_name` returns)."""
    sel = spec["select"]
    fn_at, key_at = _layout(spec)
    out = {"wrong": 0, "why": "", "sum_gap": 0.0, "count_wrong": 0}
    by_name = {}
    if len(got) != len(want):
        out.update(wrong=1, why=f"{len(got)} rows, want {len(want)}")
        return out, by_name
    want_by_key = {tuple(r[i] for i in key_at): r for r in want}
    ref_rows, seen = [], set()
    for r in got:
        if len(r) != len(sel):
            out.update(wrong=1, why=f"row of {len(r)} columns")
            return out, by_name
        k = tuple(_like(r[i], want[0][i]) for i in key_at)
        if k in seen:
            out.update(wrong=1, why=f"group {k} twice")
            return out, by_name
        seen.add(k)
        if k not in want_by_key:
            out.update(wrong=1, why=f"group {k} is not in the reference")
            return out, by_name
        w = want_by_key[k]
        ref_rows.append(w)
        for i, fn in fn_at.items():
            if fn in ADDED:
                gap = _gap(r[i], w[i])
                by_name[sel[i]] = max(by_name.get(sel[i], 0.0), gap)
                out["sum_gap"] = max(out["sum_gap"], gap)
            elif not _same(r[i], w[i]):
                out["count_wrong"] = 1
                out["why"] = f"{fn} {r[i]} at {k}, want {w[i]}"
    order = [(sel.index(c), d) for c, d in spec.get("order_by", [])]
    if order and len(got) > 1:
        def okey(r, w):
            # an aggregate orders by the reference's value of it, a key by
            # the answer's own
            return [(w[i] if i in fn_at else _like(r[i], want[0][i]), d)
                    for i, d in order]
        prev = okey(got[0], ref_rows[0])
        for r, w in zip(got[1:], ref_rows[1:]):
            cur = okey(r, w)
            if not _in_order(prev, cur, sum_limit):
                out.update(wrong=1, why=f"row order at {r}")
                return out, by_name
            prev = cur
    return out, by_name


def _like(v, model):
    """The got cell in the reference cell's type (JSON has one number type)."""
    if isinstance(model, str):
        return str(v)
    if isinstance(model, int):
        return int(v) if float(v) == int(v) else v
    return float(v)


def _in_order(prev, cur, tol: float) -> bool:
    for (a, d), (b, _) in zip(prev, cur):
        if isinstance(a, float):
            if abs(a - b) <= 2 * tol * max(abs(a), abs(b), 1.0):
                continue
        elif a == b:
            continue
        return (a < b) if d == "asc" else (a > b)
    return True
