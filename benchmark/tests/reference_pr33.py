"""FROZEN: benchmark/harness/reference.py as PR 33 left it (`git show
490a145:benchmark/harness/reference.py`), one COUNT or one SUM a query. Kept so
that test_reference.py can hold the evaluator of PR 35 to its answers on the
seventeen one-aggregate templates, to the bit. Edit nothing below.

The plain reference: one generic numpy evaluator for a template's structured
spec, and the comparison that decides `correct`.

Imports numpy only; nothing of the program. A spec is
    {"filters": [{"column", "op", "args"}...],     conjunctive
     "group_by": [column...],
     "aggregate": {"fn": "count"} | {"fn": "sum", "terms": [{"coef", "columns"}]},
     "select": ["agg" | column ...],              the broker's column order
     "order_by": [["agg" | column, "asc" | "desc"] ...],
     "limit": n}
with "$hole" arguments already replaced by `bind`. Dimension columns arrive as
codes into a sorted value table, metrics as values. Every sum is of integers
and is carried exactly (float64 holds them: all partials are below 2**53).

`partial` evaluates one segment, `merge` adds partials by group key, `finish`
orders and cuts the rows as the SQL does. `precision="bf16"` is the control:
each row's term is rounded to bfloat16 before it is added.
"""

import numpy as np

OPS = {
    "eq": lambda v, a: v == a[0],
    "lt": lambda v, a: v < a[0],
    "le": lambda v, a: v <= a[0],
    "gt": lambda v, a: v > a[0],
    "ge": lambda v, a: v >= a[0],
    "between": lambda v, a: (v >= a[0]) & (v <= a[1]),
    "in": lambda v, a: np.isin(v, np.asarray(a, dtype=v.dtype)),
}


def bind(spec, holes: dict):
    """The spec with every "$name" replaced by the hole's value."""
    if isinstance(spec, dict):
        return {k: bind(v, holes) for k, v in spec.items()}
    if isinstance(spec, list):
        return [bind(v, holes) for v in spec]
    if isinstance(spec, str) and spec.startswith("$"):
        return holes[spec[1:]]
    return spec


def columns_read(spec) -> list:
    """The columns a spec reads: filters, group keys, aggregate terms."""
    cols = [f["column"] for f in spec.get("filters", [])]
    cols += spec.get("group_by", [])
    for t in spec["aggregate"].get("terms", []):
        cols += t["columns"]
    return sorted(set(cols))


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    u = (u + (((u >> 16) & 1) + 0x7FFF)) & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def _typed(args, table):
    if table.dtype.kind in "US":
        return [str(a) for a in args]
    return [int(a) for a in args]


def partial(spec, cols: dict, tables: dict, precision: str = "exact"):
    """One segment's part of the answer: (group codes, sums, counts), the codes
    being the mixed-radix number of the group columns' table codes."""
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
    agg = spec["aggregate"]
    if agg["fn"] == "count":
        val = None
    else:
        val = np.zeros(idx.size, dtype=np.float64)
        for t in agg["terms"]:
            term = np.full(idx.size, float(t.get("coef", 1)))
            for c in t["columns"]:
                v = cols[c][idx]
                term = term * (tables[c][v] if c in tables else v)
            val += term
        if precision == "bf16":
            val = to_bf16(val)
    keys, inv = np.unique(key, return_inverse=True)
    counts = np.bincount(inv, minlength=keys.size).astype(np.int64)
    sums = (np.bincount(inv, weights=val, minlength=keys.size)
            if val is not None else counts.astype(np.float64))
    return keys, sums, counts


def merge(parts):
    """Add (keys, sums, counts) partials by key."""
    keys = np.concatenate([p[0] for p in parts])
    uniq, inv = np.unique(keys, return_inverse=True)
    sums = np.bincount(inv, weights=np.concatenate([p[1] for p in parts]),
                       minlength=uniq.size)
    counts = np.bincount(inv, weights=np.concatenate([p[2] for p in parts]),
                         minlength=uniq.size).astype(np.int64)
    return uniq, sums, counts


def finish(spec, merged, tables: dict) -> list:
    """The rows the SQL asks for, in its order: python lists of str/int/float,
    columns as in `select`."""
    keys, sums, counts = merged
    group = spec.get("group_by", [])
    agg_is_count = spec["aggregate"]["fn"] == "count"
    if not group:
        # an aggregate with no GROUP BY answers one row even over no rows
        total = (int(counts.sum()) if agg_is_count else float(sums.sum()))
        return [[total]]
    decoded, rest = {}, keys.copy()
    for col in reversed(group):
        card = len(tables[col])
        decoded[col] = tables[col][rest % card]
        rest //= card
    decoded["agg"] = counts if agg_is_count else sums
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

def compare(spec, got: list, want: list, sum_limit: float) -> dict:
    """One answer against the reference. Returns
        {"wrong": 0|1, "why": str, "sum_gap": float, "count_wrong": 0|1}
    `wrong` is structural: row count, group keys, or the SQL's row order
    (positions may differ only among rows the ORDER BY does not separate: equal
    order keys, or sums closer together than `sum_limit`). `sum_gap` is the
    widest |got - want| / max(|want|, 1) over the SUM cells; a COUNT must be
    equal."""
    sel = spec["select"]
    agg_at = sel.index("agg")
    key_at = [i for i in range(len(sel)) if i != agg_at]
    is_count = spec["aggregate"]["fn"] == "count"
    out = {"wrong": 0, "why": "", "sum_gap": 0.0, "count_wrong": 0}
    if len(got) != len(want):
        out.update(wrong=1, why=f"{len(got)} rows, want {len(want)}")
        return out
    want_by_key = {tuple(r[i] for i in key_at): r[agg_at] for r in want}
    ref_agg, seen = [], set()
    for r in got:
        if len(r) != len(sel):
            out.update(wrong=1, why=f"row of {len(r)} columns")
            return out
        k = tuple(_like(r[i], want[0][i]) for i in key_at)
        if k in seen:
            out.update(wrong=1, why=f"group {k} twice")
            return out
        seen.add(k)
        if k not in want_by_key:
            out.update(wrong=1, why=f"group {k} is not in the reference")
            return out
        w = want_by_key[k]
        ref_agg.append(w)
        if is_count:
            if int(r[agg_at]) != int(w) or float(r[agg_at]) != float(w):
                out["count_wrong"] = 1
                out["why"] = f"count {r[agg_at]} at {k}, want {w}"
        else:
            gap = abs(float(r[agg_at]) - w) / max(abs(w), 1.0)
            if not gap <= out["sum_gap"]:      # a nan counts as the widest
                out["sum_gap"] = gap if gap == gap else float("inf")
    order = spec.get("order_by", [])
    if order and len(got) > 1:
        def okey(r, a):
            return [(a if c == "agg" else _like(r[sel.index(c)],
                                                 want[0][sel.index(c)]), d)
                    for c, d in order]
        prev = okey(got[0], ref_agg[0])
        for r, a in zip(got[1:], ref_agg[1:]):
            cur = okey(r, a)
            if not _in_order(prev, cur, sum_limit):
                out.update(wrong=1, why=f"row order at {r}")
                return out
            prev = cur
    return out


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
