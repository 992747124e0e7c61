"""Expression compiler: AST -> array ops, generic over numpy (host) and jax.numpy (device).

Analog of the reference's vectorized transform functions
(`pinot-core/.../operator/transform/function/`, 52 classes): arithmetic, comparison,
logical, CASE, CAST and a library of scalar functions, all operating on whole column
batches. One evaluator serves both backends — the device path is traced under jit, the
host path powers selection/reduce/post-aggregation, so semantics match by construction.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, Tuple

import numpy as np

from ..sql.ast import Expr, Function, Identifier, Literal

# Scalar/transform function registry: name -> (xp, *args) -> array.
# Mirrors TransformFunctionFactory registration (reference file above) and the scalar
# @ScalarFunction registry (`pinot-common/.../function/FunctionRegistry.java:39`).
_FUNCTIONS: Dict[str, Callable] = {}


def register_function(name: str):
    def deco(fn):
        _FUNCTIONS[name.lower()] = fn
        return fn
    return deco


def eval_expr(e: Expr, columns: Mapping[str, Any], xp=np, ranges=None):
    """Evaluate expression over a column environment.

    `columns` maps identifier name -> array (already decoded values, or whatever the
    caller wants identifiers to mean — the reduce stage maps aggregation result columns).
    `xp` is numpy or jax.numpy. `ranges` (device plans: `int_bounds`) maps a column to
    the (lo, hi) its integers lie in, or to None for a column that is not of integers:
    `+`, `-` and `*` of integers are WIDENED where the result can leave int32, never
    wrapped (`_widen_host`, `_widen_device`).
    """
    if isinstance(e, Literal):
        return e.value
    if isinstance(e, Identifier):
        try:
            return columns[e.name]
        except KeyError:
            raise KeyError(f"expression references unbound column {e.name!r}") from None
    assert isinstance(e, Function)
    name = e.name
    args = e.args

    if name == "and":
        out = _as_bool(eval_expr(args[0], columns, xp, ranges), xp)
        for a in args[1:]:
            out = out & _as_bool(eval_expr(a, columns, xp, ranges), xp)
        return out
    if name == "or":
        out = _as_bool(eval_expr(args[0], columns, xp, ranges), xp)
        for a in args[1:]:
            out = out | _as_bool(eval_expr(a, columns, xp, ranges), xp)
        return out
    if name == "not":
        return ~_as_bool(eval_expr(args[0], columns, xp, ranges), xp)
    if name == "case":
        # case(w1, t1, ..., wn, tn, default): right-fold of xp.where
        default = eval_expr(args[-1], columns, xp, ranges)
        out = default
        for i in range(len(args) - 3, -1, -2):
            cond = _as_bool(eval_expr(args[i - 1], columns, xp, ranges), xp)
            out = xp.where(cond, eval_expr(args[i], columns, xp, ranges), out)
        return out
    if name == "cast":
        val = eval_expr(args[0], columns, xp, ranges)
        return _cast(val, args[1].value, xp)
    if name == "in":
        needle = eval_expr(args[0], columns, xp, ranges)
        out = None
        for a in args[1:]:
            m = needle == eval_expr(a, columns, xp, ranges)
            out = m if out is None else (out | m)
        return out
    if name == "not_in":
        return ~eval_expr(Function("in", args), columns, xp, ranges)
    if name == "between":
        v = eval_expr(args[0], columns, xp, ranges)
        return (v >= eval_expr(args[1], columns, xp, ranges)) \
            & (v <= eval_expr(args[2], columns, xp, ranges))

    binop = _BINOPS.get(name)
    if binop is not None:
        left = eval_expr(args[0], columns, xp, ranges)
        right = eval_expr(args[1], columns, xp, ranges)
        if name in _WIDENING and _of_integers(left) and _of_integers(right):
            if xp is np:
                left, right = _widen_host(name, left, right)
            elif widens(e, ranges):
                left, right = _widen_device(left), _widen_device(right)
        return binop(left, right, xp)

    fn = _FUNCTIONS.get(name)
    if fn is not None:
        return fn(xp, *[eval_expr(a, columns, xp, ranges) for a in args])
    raise KeyError(f"unknown function {name!r}")


# -- INT arithmetic that can leave int32 is widened, never wrapped ------------
# Upstream's Addition/Subtraction/MultiplicationTransformFunction compute in
# double. Here: on numpy (host executor, reduce, post-aggregation) operands
# narrower than 64 bits go to int64, which holds every sum, difference and
# product of two of them exactly, and 64-bit operands whose result can pass
# 2^63 go to float64. Under jax.numpy (no 64-bit types on the chip) the
# operands go to float32 where the result can leave int32, decided from the
# plan's `ranges`: literals and the columns' min/max. A result that fits stays
# the int32 program it was.

_WIDENING = ("plus", "minus", "times")
_INT32_LO, _INT32_HI = -(1 << 31), (1 << 31) - 1
_NOT_INTEGERS = "not integers"   # `int_bounds` of a float sub-expression


def _of_integers(v) -> bool:
    if isinstance(v, (bool, np.bool_)):
        return False
    if isinstance(v, int):
        return True
    return getattr(getattr(v, "dtype", None), "kind", "") in "iu" \
        and hasattr(v, "astype")


def _reach(v) -> int:
    """The largest magnitude among integers `v` (a python int)."""
    if isinstance(v, int):
        return abs(v)
    v = np.asarray(v)
    return max(abs(int(v.max())), abs(int(v.min()))) if v.size else 0


def _widen_host(name: str, left, right):
    """numpy operands of integers: to int64; to float64 where a 64-bit operand
    (a LONG column, a product of products) lets the result pass 2^63."""
    arrays = [v for v in (left, right) if hasattr(v, "astype")]
    if any(v.dtype.itemsize >= 8 for v in arrays):
        a, b = _reach(left), _reach(right)
        if (a * b if name == "times" else a + b) >= 1 << 63:
            return tuple(v.astype(np.float64) if hasattr(v, "astype")
                         else float(v) for v in (left, right))
    return tuple(v.astype(np.int64) if hasattr(v, "astype") else v
                 for v in (left, right))


def _widen_device(v):
    return v.astype(np.float32) if hasattr(v, "astype") else v


def int_bounds(e: Expr, ranges):
    """What the plan knows of an expression's integers: (lo, hi);
    _NOT_INTEGERS for a float sub-expression; None where it cannot see (a
    column `ranges` does not hold, a function that is not `+`, `-`, `*`)."""
    if isinstance(e, Literal):
        v = e.value
        if isinstance(v, (int, np.integer)):    # a bool is 0 or 1
            return int(v), int(v)
        return _NOT_INTEGERS if isinstance(v, (float, np.floating)) else None
    if isinstance(e, Identifier):
        if not ranges or e.name not in ranges:
            return None
        return _NOT_INTEGERS if ranges[e.name] is None else ranges[e.name]
    if e.name == "divide":
        return _NOT_INTEGERS
    if e.name == "cast":
        return _NOT_INTEGERS if str(e.args[1].value).upper() in (
            "FLOAT", "DOUBLE") else None
    if e.name not in _WIDENING:
        return None
    a, b = (int_bounds(x, ranges) for x in e.args)
    if _NOT_INTEGERS in (a, b):
        return _NOT_INTEGERS
    if a is None or b is None:
        return None
    if e.name == "plus":
        return a[0] + b[0], a[1] + b[1]
    if e.name == "minus":
        return a[0] - b[1], a[1] - b[0]
    corners = [x * y for x in a for y in b]
    return min(corners), max(corners)


def widens(e: Expr, ranges) -> bool:
    """Whether the device evaluates this `+`, `-` or `*` of integers in
    float32: its result can leave int32; or, where the plan cannot see a
    range, it is a product of two non-literals."""
    b = int_bounds(e, ranges)
    if b == _NOT_INTEGERS:
        return False
    if b is None:       # no operand is known to be a float, or b would say so
        return e.name == "times" and not any(isinstance(a, Literal)
                                             for a in e.args)
    return b[0] < _INT32_LO or b[1] > _INT32_HI


def widen_marks(e, ranges) -> Tuple[bool, ...]:
    """`widens` of every `+`, `-`, `*` in `e`, in walk order: what of `ranges`
    a compiled program depends on (part of the kernels' cache keys)."""
    if not isinstance(e, Function):
        return ()
    mine = (widens(e, ranges),) if e.name in _WIDENING else ()
    return mine + tuple(m for a in e.args for m in widen_marks(a, ranges))


def _as_bool(v, xp):
    if isinstance(v, bool):
        return v
    return v.astype(bool) if hasattr(v, "astype") else bool(v)


def _true_div(l, r, xp):
    # SQL semantics: `/` is float division regardless of integer inputs.
    l = l * 1.0 if not np.isscalar(l) else float(l)
    return l / r


_BINOPS = {
    "plus": lambda l, r, xp: l + r,
    "minus": lambda l, r, xp: l - r,
    "times": lambda l, r, xp: l * r,
    "divide": _true_div,
    "mod": lambda l, r, xp: l % r,
    "eq": lambda l, r, xp: l == r,
    "neq": lambda l, r, xp: l != r,
    "gt": lambda l, r, xp: l > r,
    "gte": lambda l, r, xp: l >= r,
    "lt": lambda l, r, xp: l < r,
    "lte": lambda l, r, xp: l <= r,
}


def _cast(val, target: str, xp):
    target = target.upper()
    if target in ("INT", "INTEGER"):
        return _astype(val, np.int32, xp)
    if target in ("LONG", "BIGINT"):
        return _astype(val, np.int64, xp)
    if target in ("FLOAT",):
        return _astype(val, np.float32, xp)
    if target in ("DOUBLE",):
        return _astype(val, np.float64, xp)
    if target in ("BOOLEAN",):
        return _astype(val, bool, xp)
    if target in ("STRING", "VARCHAR"):
        if xp is not np:
            raise ValueError("CAST to STRING is host-side only")
        return np.asarray(val).astype(str)
    raise ValueError(f"unsupported CAST target {target}")


def _astype(val, dtype, xp):
    if hasattr(val, "astype"):
        return val.astype(dtype)
    return np.dtype(dtype).type(val) if dtype is not bool else bool(val)


# -- scalar function library (extend over time) ------------------------------

@register_function("abs")
def _abs(xp, v):
    return xp.abs(v)


@register_function("ceil")
def _ceil(xp, v):
    return xp.ceil(v)


@register_function("floor")
def _floor(xp, v):
    return xp.floor(v)


@register_function("exp")
def _exp(xp, v):
    return xp.exp(v)


@register_function("ln")
def _ln(xp, v):
    return xp.log(v)


@register_function("log10")
def _log10(xp, v):
    return xp.log10(v)


@register_function("sqrt")
def _sqrt(xp, v):
    return xp.sqrt(v)


@register_function("power")
def _power(xp, v, p):
    return xp.power(v, p)


@register_function("round")
def _round(xp, v, digits=0):
    if digits:
        f = 10.0 ** digits
        return xp.round(v * f) / f
    return xp.round(v)


@register_function("least")
def _least(xp, *vs):
    out = vs[0]
    for v in vs[1:]:
        out = xp.minimum(out, v)
    return out


@register_function("greatest")
def _greatest(xp, *vs):
    out = vs[0]
    for v in vs[1:]:
        out = xp.maximum(out, v)
    return out


@register_function("sign")
def _sign(xp, v):
    return xp.sign(v)


@register_function("truncate")
def _truncate(xp, v, digits=0):
    f = 10.0 ** int(digits)
    return xp.trunc(v * f) / f


@register_function("log2")
def _log2(xp, v):
    return xp.log2(v)


@register_function("log")
def _log(xp, v):
    return xp.log(v)


for _trig in ("sin", "cos", "tan", "arcsin", "arccos", "arctan", "sinh", "cosh", "tanh"):
    _sql_name = _trig.replace("arc", "a")  # SQL: ASIN/ACOS/ATAN

    def _make(tn):
        def f(xp, v):
            return getattr(xp, tn)(v)
        return f
    register_function(_sql_name)(_make(_trig))


@register_function("atan2")
def _atan2(xp, y, x):
    return xp.arctan2(y, x)


@register_function("degrees")
def _degrees(xp, v):
    return xp.degrees(v)


@register_function("radians")
def _radians(xp, v):
    return xp.radians(v)


@register_function("coalesce")
def _coalesce(xp, *vs):
    """First non-null argument. Null = NaN for float arrays, None for scalars/objects
    (nulls surface as NaN on the decoded-value host path; see NullValueVector handling)."""
    out = vs[0]
    for v in vs[1:]:
        if out is None:
            out = v
            continue
        if hasattr(out, "dtype") and np.issubdtype(getattr(out, "dtype"), np.floating):
            out = xp.where(xp.isnan(out), v, out)
        elif hasattr(out, "dtype") and out.dtype == object:
            out = np.asarray([v_ if o is None else o
                              for o, v_ in zip(out, np.broadcast_to(np.asarray(v, dtype=object),
                                                                    out.shape))], dtype=object)
    return out


@register_function("nullif")
def _nullif(xp, a, b):
    if hasattr(a, "dtype") and np.issubdtype(getattr(a, "dtype"), np.floating):
        return xp.where(a == b, xp.nan, a)
    if hasattr(a, "dtype"):
        if a.dtype == object or (xp is np and not np.issubdtype(a.dtype, np.number)):
            arr = np.asarray(a, dtype=object).copy()
            arr[np.asarray(a == b)] = None
            return arr
        # integer path: NaN is this module's null representation, so widen to float —
        # a sentinel in-domain value would collide with legitimate data
        af = a.astype(np.float64)
        return xp.where(a == b, xp.nan, af)
    return None if a == b else a


# -- multi-value transforms (reference: ArrayLengthTransformFunction,
# ValueInTransformFunction — host path only; MV cells are object arrays of
# per-row numpy arrays and the planner keeps MV expressions off the device) ----

@register_function("arraylength")
def _arraylength(xp, v):
    arr = np.asarray(v)
    if arr.dtype == object:
        return np.fromiter((len(np.atleast_1d(x)) for x in arr), dtype=np.int64,
                           count=len(arr))
    return np.ones(len(arr), dtype=np.int64)  # SV column: one value per row


@register_function("cardinality")
def _cardinality(xp, v):
    return _arraylength(xp, v)


@register_function("valuein")
def _valuein(xp, v, *allowed):
    """MV -> MV: per-row intersection with the literal set, preserving row order."""
    sel = set(allowed)
    out = np.empty(len(v), dtype=object)
    for i, row in enumerate(v):
        out[i] = np.asarray([x for x in np.atleast_1d(np.asarray(row)).tolist()
                             if x in sel])
    return out


@register_function("arrayelementat")
def _arrayelementat(xp, v, idx):
    """1-based element access; out-of-range -> None (reference: arrayElementAt)."""
    i = int(idx) - 1
    out = np.empty(len(v), dtype=object)
    for r, row in enumerate(v):
        row = np.atleast_1d(np.asarray(row))
        out[r] = row[i].item() if 0 <= i < len(row) else None
    return out


@register_function("__packobj")
def _packobj(xp, *cols):
    """Internal: stack k argument columns into an [n, k] OBJECT matrix —
    like __pack but type-preserving, for aggregations whose key column may be
    strings (filtered theta sketches). Host-only."""
    arrs = [np.asarray(c, dtype=object) for c in cols]
    n = max((len(a) for a in arrs if a.ndim), default=0)
    arrs = [np.full(n, a.item(), dtype=object) if a.ndim == 0 else a
            for a in arrs]
    return np.stack(arrs, axis=1)


@register_function("__pack")
def _pack(xp, *cols):
    """Internal: stack k argument columns into an [n, k] matrix so multi-argument
    aggregations (COVAR/CORR/FIRSTWITHTIME) flow through the single-argument
    executor surface. Host-only by construction (not in planner._DEVICE_FUNCS)."""
    return np.stack([np.asarray(c, dtype=np.float64) for c in cols], axis=1)


@register_function("cot")
def _cot(xp, v):
    return 1.0 / xp.tan(v)


# -- MV reductions (reference: ArraySum/ArrayMin/ArrayMax/ArrayAverage/
# ArrayDistinct/ArraySort transform functions) --------------------------------

def _mv_reduce(v, fn, empty):
    arr = np.asarray(v, dtype=object)
    return np.asarray([fn(np.atleast_1d(np.asarray(row)).astype(np.float64))
                       if row is not None and len(np.atleast_1d(row)) else empty
                       for row in arr], dtype=np.float64)


@register_function("arraysum")
def _arraysum(xp, v):
    return _mv_reduce(v, np.sum, 0.0)


@register_function("arraymin")
def _arraymin(xp, v):
    return _mv_reduce(v, np.min, float("nan"))


@register_function("arraymax")
def _arraymax(xp, v):
    return _mv_reduce(v, np.max, float("nan"))


@register_function("arrayaverage")
def _arrayaverage(xp, v):
    return _mv_reduce(v, np.mean, float("nan"))


@register_function("arraydistinct")
def _arraydistinct(xp, v):
    out = np.empty(len(v), dtype=object)
    for i, row in enumerate(v):
        vals = np.atleast_1d(np.asarray(row))
        seen, keep = set(), []
        for x in vals.tolist():
            if x not in seen:
                seen.add(x)
                keep.append(x)
        out[i] = np.asarray(keep)
    return out


@register_function("arraysortasc")
def _arraysortasc(xp, v):
    out = np.empty(len(v), dtype=object)
    for i, row in enumerate(v):
        out[i] = np.sort(np.atleast_1d(np.asarray(row)))
    return out


@register_function("arraysortdesc")
def _arraysortdesc(xp, v):
    out = np.empty(len(v), dtype=object)
    for i, row in enumerate(v):
        out[i] = np.sort(np.atleast_1d(np.asarray(row)))[::-1]
    return out


@register_function("arrayindexof")
def _arrayindexof(xp, v, target):
    """0-based index of `target` in each row's values; -1 when absent
    (reference: arrayIndexOf)."""
    out = np.empty(len(v), dtype=np.int64)
    for i, row in enumerate(v):
        vals = np.atleast_1d(np.asarray(row)).tolist()
        out[i] = vals.index(target) if target in vals else -1
    return out


@register_function("arraycontains")
def _arraycontains(xp, v, target):
    return np.asarray([target in np.atleast_1d(np.asarray(row)).tolist()
                       for row in v], dtype=bool)


@register_function("arrayreverse")
def _arrayreverse(xp, v):
    out = np.empty(len(v), dtype=object)
    for i, row in enumerate(v):
        out[i] = np.atleast_1d(np.asarray(row))[::-1]
    return out


@register_function("arrayslice")
def _arrayslice(xp, v, start, end):
    s, e = int(start), int(end)
    out = np.empty(len(v), dtype=object)
    for i, row in enumerate(v):
        out[i] = np.atleast_1d(np.asarray(row))[s:e]
    return out


@register_function("arrayremove")
def _arrayremove(xp, v, target):
    # first occurrence only (reference: ArrayUtils.removeElement semantics)
    out = np.empty(len(v), dtype=object)
    for i, row in enumerate(v):
        vals = np.atleast_1d(np.asarray(row)).tolist()
        if target in vals:
            vals.remove(target)
        out[i] = np.asarray(vals)
    return out


@register_function("arrayunion")
def _arrayunion(xp, a, b):
    out = np.empty(len(a), dtype=object)
    for i in range(len(a)):
        seen, keep = set(), []
        for src in (a[i], b[i]):
            for x in np.atleast_1d(np.asarray(src)).tolist():
                if x not in seen:
                    seen.add(x)
                    keep.append(x)
        out[i] = np.asarray(keep)
    return out


@register_function("arrayconcat")
def _arrayconcat(xp, a, b):
    out = np.empty(len(a), dtype=object)
    for i in range(len(a)):
        out[i] = np.concatenate([np.atleast_1d(np.asarray(a[i])),
                                 np.atleast_1d(np.asarray(b[i]))])
    return out


# the reference registers type-suffixed spellings (arraySortInt/arraySortString
# etc.) — same implementations here, values are already typed
for _base in ("arrayconcat", "arraycontains", "arraydistinct", "arrayindexof",
              "arrayremove", "arrayreverse", "arrayslice", "arrayunion"):
    for _suffix in ("int", "long", "float", "double", "string"):
        if _base in _FUNCTIONS:
            _FUNCTIONS[f"{_base}{_suffix}"] = _FUNCTIONS[_base]
for _suffix in ("int", "string"):
    _FUNCTIONS[f"arraysort{_suffix}"] = _FUNCTIONS["arraysortasc"]
