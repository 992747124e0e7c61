"""Filter compilation: predicate AST + segment dictionaries -> device filter program.

Analog of the reference's predicate evaluators
(`pinot-core/.../operator/filter/predicate/`, 13 factories): every predicate over a
dict-encoded column is resolved host-side against the *sorted dictionary* into a boolean
lookup table (LUT) over dict ids, so on device it is one gather (`lut[ids]`) regardless of
whether it was EQ/IN/RANGE/LIKE/REGEXP. Predicates over raw numeric columns (and arbitrary
expressions — the reference's `ExpressionFilterOperator`) compile to vectorized comparisons
with scalar operands passed as runtime inputs, keeping the jit kernel reusable across
literal changes.

Integer normalization: float literals against integer expressions are normalized host-side
(`x > 2.5` -> `x >= 3`) so the device compares integers exactly instead of in float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np

from ..segment.reader import ColumnReader, ImmutableSegment
from ..sql.ast import Expr, Function, Identifier, Literal
from .context import QueryValidationError

# filter tree: ("and"|"or", (children...)) | ("not", child) | ("leaf", index) | ("const", bool)
FilterTree = Tuple


# A LUT whose true-set decomposes into at most this many contiguous id runs is
# evaluated on device as interval compares over the id vector — zero gathers, zero
# matmuls. Sorted dictionaries make this the common case: EQ is one run, RANGE is one
# run, small IN-lists are <= k runs. (Gathers are the slow lane of a TPU scan, so
# gather-free filters keep the mask on the vector units.)
MAX_LUT_INTERVALS = 8


def _lut_intervals(lut: np.ndarray) -> Optional[List[Tuple[int, int]]]:
    """Decompose a boolean LUT into inclusive [lo, hi] runs of True, or None if the
    decomposition exceeds MAX_LUT_INTERVALS (dense scattered sets: big IN / LIKE)."""
    idx = np.flatnonzero(lut)
    if len(idx) == 0:
        return []
    breaks = np.flatnonzero(np.diff(idx) > 1)
    if len(breaks) + 1 > MAX_LUT_INTERVALS:
        return None
    starts = np.concatenate(([idx[0]], idx[breaks + 1]))
    ends = np.concatenate((idx[breaks], [idx[-1]]))
    return [(int(lo), int(hi)) for lo, hi in zip(starts, ends)]


@dataclass
class LutLeaf:
    """Dict-column predicate resolved to a boolean LUT over dict ids.

    `intervals` is the contiguous-run decomposition of the LUT (None when the true-set
    is too scattered): the device kernel evaluates intervals as id-range compares with
    runtime scalar operands, and falls back to a one-hot matmul (small dictionaries) or
    a gather (large ones) only for scattered sets.
    """
    col: str
    lut: np.ndarray  # bool[lut_size(card)] — padding ids map to False
    intervals: Optional[List[Tuple[int, int]]] = field(default=None)
    # source predicate (op + literal values), kept so the LUT can be REBUILT
    # against a different dictionary snapshot (mutable segments: dict ids remap
    # as the sorted dictionary grows). Excluded from signature(): same kernel.
    op: Optional[str] = field(default=None)
    values: Optional[List[Any]] = field(default=None)

    def __post_init__(self):
        if self.intervals is None:
            self.intervals = _lut_intervals(self.lut)

    def rebuild_lut(self, dictionary, cardinality: int) -> np.ndarray:
        """The same predicate resolved against another dictionary snapshot."""
        assert self.op is not None
        return build_lut(self.op, self.values, dictionary, cardinality)

    @property
    def kind(self) -> str:
        return "lut"

    def signature(self) -> Tuple:
        # interval count is structural (operand values are runtime inputs); scattered
        # LUTs key on size only, their contents are runtime inputs too
        mode = len(self.intervals) if self.intervals is not None else "dense"
        return ("lut", self.col, len(self.lut), mode)


@dataclass
class CmpLeaf:
    """Comparison of a device-evaluable numeric expression against scalar operands.

    op in {eq, neq, gt, gte, lt, lte, between, in}; operands live in the runtime scalar
    arrays (int slots for integer compares, float slots otherwise).
    """
    expr: Expr
    op: str
    operands: List[Any]
    is_int: bool

    @property
    def kind(self) -> str:
        return "cmp"

    def signature(self) -> Tuple:
        return ("cmp", repr(self.expr), self.op, len(self.operands), self.is_int)


@dataclass
class NullLeaf:
    col: str
    negated: bool  # True for IS NOT NULL

    @property
    def kind(self) -> str:
        return "null"

    def signature(self) -> Tuple:
        return ("null", self.col, self.negated)


@dataclass
class DocSetLeaf:
    """Predicate resolved host-side into a per-doc bitmap: JSON_MATCH / TEXT_MATCH.

    The reference's JsonMatchFilterOperator / TextMatchFilterOperator likewise resolve
    these against their index into a doc bitmap before the scan; on the device path the
    bitmap ships as a runtime input (padded bool vector) consumed by one load.
    """
    col: str
    desc: str
    mask: np.ndarray  # bool[num_docs]
    # fully identifies the mask's CONTENTS for a given immutable segment
    # (kind + every predicate parameter); "" = not content-addressable
    # (id-set leaves), never cache. Excluded from signature(): masks are
    # runtime inputs and must not fragment the kernel cache.
    cache_token: str = ""

    @property
    def kind(self) -> str:
        return "docset"

    def signature(self) -> Tuple:
        # mask contents are runtime inputs; only structure keys the kernel cache
        return ("docset", self.col)


Leaf = Union[LutLeaf, CmpLeaf, NullLeaf, DocSetLeaf]


@dataclass
class FilterProgram:
    tree: FilterTree = ("const", True)
    leaves: List[Leaf] = field(default_factory=list)

    def signature(self) -> Tuple:
        return (_tree_sig(self.tree), tuple(l.signature() for l in self.leaves))

    @property
    def is_match_all(self) -> bool:
        return self.tree == ("const", True)


def _tree_sig(tree: FilterTree) -> Tuple:
    kind = tree[0]
    if kind in ("and", "or"):
        return (kind, tuple(_tree_sig(c) for c in tree[1]))
    if kind == "not":
        return ("not", _tree_sig(tree[1]))
    return tree  # ("leaf", i) / ("const", b)


_RANGE_OPS = {"gt", "gte", "lt", "lte", "between"}
_NEGATIONS = {"neq": "eq", "not_in": "in", "not_like": "like"}
# boolean transform functions usable bare or as `f(...) = 1/0` comparisons
_BOOL_PREDICATES = {"in_id_set", "inidset", "json_match", "text_match"}


def _as_bool(v) -> Optional[bool]:
    if isinstance(v, bool):
        return v
    if isinstance(v, (int, float)) and v in (0, 1):
        return bool(v)
    if isinstance(v, str) and v.lower() in ("true", "false"):
        return v.lower() == "true"
    return None


def compile_filter(expr: Optional[Expr], segment: ImmutableSegment) -> FilterProgram:
    """Compile a WHERE tree for one segment (reference: FilterPlanNode.run, per-segment
    because dictionaries — and therefore LUT contents — are per-segment)."""
    prog = FilterProgram()
    if expr is None:
        return prog
    prog.tree = _compile_node(expr, segment, prog.leaves)
    prog.tree = _simplify(prog.tree)
    return prog


def _compile_node(e: Expr, seg: ImmutableSegment, leaves: List[Leaf]) -> FilterTree:
    if isinstance(e, Literal):
        return ("const", bool(e.value))
    if isinstance(e, Identifier):
        raise QueryValidationError(f"bare column {e.name!r} is not a boolean predicate")
    assert isinstance(e, Function)
    name = e.name
    if name == "and":
        return ("and", tuple(_compile_node(a, seg, leaves) for a in e.args))
    if name == "or":
        return ("or", tuple(_compile_node(a, seg, leaves) for a in e.args))
    if name == "not":
        return ("not", _compile_node(e.args[0], seg, leaves))
    if name in _NEGATIONS:
        return ("not", _compile_node(Function(_NEGATIONS[name], e.args), seg, leaves))
    if name == "eq" and len(e.args) == 2:
        # `IN_ID_SET(col,'…') = 1` / `TEXT_MATCH(col,'…') = 0` — the
        # reference's documented comparison form for boolean transform
        # functions (InIdSetTransformFunction and friends return 1/0):
        # normalize to the bare predicate / its negation. (`!= n` arrives
        # here too: _NEGATIONS rewrites neq to not(eq(...)) above.)
        for fn, lit in (e.args, e.args[::-1]):
            if isinstance(fn, Function) and fn.name in _BOOL_PREDICATES \
                    and isinstance(lit, Literal) and _as_bool(lit.value) is not None:
                node = _compile_node(fn, seg, leaves)
                return node if _as_bool(lit.value) else ("not", node)
    if name in ("is_null", "is_not_null"):
        col = e.args[0]
        if not isinstance(col, Identifier):
            raise QueryValidationError("IS NULL requires a plain column")
        leaves.append(NullLeaf(col.name, negated=(name == "is_not_null")))
        return ("leaf", len(leaves) - 1)
    if name in ("json_match", "text_match"):
        if len(e.args) != 2 or not isinstance(e.args[0], Identifier) \
                or not isinstance(e.args[1], Literal):
            raise QueryValidationError(f"{name.upper()}(column, 'filter') expected: {e!r}")
        col, arg = e.args[0], e.args[1]
        reader = seg.column(col.name)
        query = str(arg.value)
        try:
            if name == "json_match":
                # mutable (realtime) column readers carry no aux indexes -> scan fallback
                idx = getattr(reader, "json_index", None)
                if idx is not None:
                    mask = idx.match(query)
                else:
                    from ..segment.indexes.jsonidx import json_match_scan
                    mask = json_match_scan(reader.values(), query)
            else:
                idx = getattr(reader, "text_index", None)
                if idx is not None:
                    mask = idx.match(query)
                else:
                    from ..segment.indexes.text import text_match_scan
                    mask = text_match_scan(reader.values(), query)
        except (ValueError, AssertionError, IndexError, KeyError) as exc:
            raise QueryValidationError(f"{name.upper()}: {exc}") from exc
        leaves.append(DocSetLeaf(col.name, query, mask,
                                 cache_token=f"{name}:{query}"))
        return ("leaf", len(leaves) - 1)
    if name in ("in_id_set", "inidset"):
        # membership against a serialized IdSet literal (reference:
        # InIdSetTransformFunction). Dict column -> LUT once over the sorted
        # dictionary; raw column -> host doc mask (same shape as TEXT_MATCH).
        from .idset import IdSet, IdSetError
        if len(e.args) != 2 or not isinstance(e.args[0], Identifier) \
                or not isinstance(e.args[1], Literal):
            raise QueryValidationError(
                f"IN_ID_SET(column, 'serialized-idset') expected: {e!r}")
        col, lit = e.args[0], e.args[1]
        try:
            ids = IdSet.deserialize(str(lit.value))
        except IdSetError as exc:
            raise QueryValidationError(str(exc)) from exc
        reader = seg.column(col.name)
        if reader.has_dictionary:
            lut = build_lut("idset", [ids], reader.dictionary,
                            reader.cardinality)
            leaves.append(LutLeaf(col.name, lut, op="idset", values=[ids]))
        else:
            import hashlib
            mask = ids.contains(reader.values())
            # content-addressed token: the serialized literal IS the set
            digest = hashlib.sha1(str(lit.value).encode()).hexdigest()
            leaves.append(DocSetLeaf(col.name, f"idset[{len(ids)}]", mask,
                                     cache_token=f"idset:{digest}"))
        return ("leaf", len(leaves) - 1)
    geo = _try_geo_predicate(e, seg, leaves)
    if geo is not None:
        return geo
    if name in ("stwithin", "stcontains", "stequals"):
        # boolean geo function used directly as a predicate -> compare to true
        return _compile_predicate(Function("eq", (e, Literal(1))), seg, leaves)
    return _compile_predicate(e, seg, leaves)


def _try_geo_predicate(e: Function, seg: ImmutableSegment,
                       leaves: List[Leaf]):
    """`ST_DISTANCE(ST_POINT(lngCol, latCol), <const point>) < r`:
    geo-cell-index candidate mask (when the segment has one for the column
    pair) ANDed with the exact haversine compare — the H3 coarse-cover +
    exact-refine pattern (reference: H3IndexFilterOperator). Without an index
    the predicate still compiles: the rewrite below turns it into elementwise
    device math."""
    from ..engine.geo_fns import distance_predicate_parts
    parts = distance_predicate_parts(e)
    if parts is None:
        return None
    lng_col, lat_col, cx, cy, radius = parts
    exact = _compile_predicate(e, seg, leaves)  # rewrites to haversine inside
    geo_idx = None
    getter = getattr(seg, "geo_index", None)
    if getter is not None:
        geo_idx = getter(lng_col, lat_col)
    if geo_idx is None:
        return exact
    mask = geo_idx.candidate_mask(cx, cy, radius, seg.num_docs)
    leaves.append(DocSetLeaf(f"{lng_col},{lat_col}",
                             f"geo cells r={radius:g}m", mask,
                             cache_token=f"geo:{cx!r}:{cy!r}:{radius!r}"))
    return ("and", (("leaf", len(leaves) - 1), exact))


def _compile_predicate(e: Function, seg: ImmutableSegment, leaves: List[Leaf]) -> FilterTree:
    from ..engine.geo_fns import rewrite_geo
    lhs = e.args[0]
    rhs = list(e.args[1:])
    # normalize `literal op column` to `column op' literal`
    if isinstance(lhs, Literal) and len(rhs) == 1 and not isinstance(rhs[0], Literal):
        flip = {"eq": "eq", "gt": "lt", "gte": "lte", "lt": "gt", "lte": "gte"}
        if e.name in flip:
            lhs, rhs = rhs[0], [lhs]
            e = Function(flip[e.name], (lhs, *rhs))
    # AFTER the flip, so `r > stdistance(...)` rewrites too:
    # distance-over-columns -> elementwise device haversine
    lhs = rewrite_geo(lhs)
    if not all(isinstance(r, Literal) for r in rhs):
        raise QueryValidationError(f"predicate operands must be literals: {e!r}")
    values = [r.value for r in rhs]

    # dictionary-encoded single-column predicate -> LUT leaf
    if isinstance(lhs, Identifier):
        reader = seg.column(lhs.name)
        if reader.has_dictionary:
            leaves.append(LutLeaf(lhs.name, _build_lut(e.name, values, reader),
                                  op=e.name, values=values))
            return ("leaf", len(leaves) - 1)

    # raw column / expression predicate -> comparison leaf
    op, operands, is_int, const = _normalize_cmp(e.name, values, lhs, seg)
    if const is not None:
        return ("const", const)
    leaves.append(CmpLeaf(lhs, op, operands, is_int))
    return ("leaf", len(leaves) - 1)


def _build_lut(op: str, values: List[Any], reader: ColumnReader) -> np.ndarray:
    return build_lut(op, values, reader.dictionary, reader.cardinality,
                     fst_index=getattr(reader, "fst_index", None))


def build_lut(op: str, values: List[Any], d, cardinality: int,
              fst_index=None) -> np.ndarray:
    """Resolve a predicate against a specific dictionary snapshot. Factored out
    of the reader-based path so mutable segments can rebuild LUTs against the
    one dictionary snapshot the whole filter evaluates under."""
    from ..engine.datablock import lut_size  # local import to avoid jax at module import
    lut = np.zeros(lut_size(cardinality), dtype=bool)
    if op == "idset":
        if cardinality:
            lut[:cardinality] = values[0].contains(d._np_values)
    elif op == "eq":
        i = d.index_of(values[0])
        if i >= 0:
            lut[i] = True
    elif op == "in":
        lut[d.ids_for_values(values)] = True
    elif op == "between":
        lo, hi = d.id_range(values[0], values[1])
        lut[lo:hi] = True
    elif op in ("gt", "gte"):
        lo, hi = d.id_range(values[0], None, lower_inclusive=(op == "gte"))
        lut[lo:hi] = True
    elif op in ("lt", "lte"):
        lo, hi = d.id_range(None, values[0], upper_inclusive=(op == "lte"))
        lut[lo:hi] = True
    elif op == "like":
        lut[d.ids_matching_like(str(values[0]))] = True
    elif op == "regexp_like":
        # trigram FST-analog index prefilters the dictionary scan when present
        # (reference: FSTBasedRegexpPredicateEvaluatorFactory); falls back to
        # the full per-distinct-value regex otherwise
        ids = None
        if fst_index is not None:
            from ..segment.indexes.fst import ids_matching_regex_indexed
            ids = ids_matching_regex_indexed(fst_index, d.values, str(values[0]))
        if ids is None:
            ids = d.ids_matching_regex(str(values[0]))
        lut[ids] = True
    else:
        raise QueryValidationError(f"unsupported predicate {op} on dictionary column")
    return lut


def _normalize_cmp(op: str, values: List[Any], lhs: Expr, seg: ImmutableSegment):
    """Normalize operands for a raw/expression compare; returns (op, operands, is_int, const).

    const is a bool when the predicate folds to a constant (e.g. `int_col = 2.5` -> False).
    """
    is_int = _expr_is_integer(lhs, seg)
    if op == "like" or op == "regexp_like":
        raise QueryValidationError("LIKE/REGEXP on raw (non-dictionary) columns is unsupported")
    if not is_int:
        return op, [float(v) for v in values], False, None

    # integer expression: normalize float literals to exact integer comparisons
    if op == "eq":
        v = values[0]
        if float(v) != int(v):
            return op, [], True, False
        return op, [int(v)], True, None
    if op == "in":
        ints = [int(v) for v in values if float(v) == int(v)]
        if not ints:
            return op, [], True, False
        return op, ints, True, None
    if op == "between":
        lo, hi = math.ceil(values[0]), math.floor(values[1])
        if lo > hi:
            return op, [], True, False
        return op, [lo, hi], True, None
    if op == "gt":
        return "gte", [math.floor(values[0]) + 1], True, None
    if op == "gte":
        return "gte", [math.ceil(values[0])], True, None
    if op == "lt":
        return "lte", [math.ceil(values[0]) - 1], True, None
    if op == "lte":
        return "lte", [math.floor(values[0])], True, None
    raise QueryValidationError(f"unsupported comparison {op}")


def _expr_is_integer(e: Expr, seg: ImmutableSegment) -> bool:
    """Conservatively: integer iff all leaves are integer columns/literals and ops preserve
    integrality (no divide)."""
    if isinstance(e, Literal):
        return isinstance(e.value, int) and not isinstance(e.value, bool)
    if isinstance(e, Identifier):
        reader = seg.column(e.name)
        return np.dtype(reader.meta["fwdDtype"]).kind in "iu" and (
            not reader.has_dictionary or reader.data_type.is_numeric)
    if isinstance(e, Function):
        if e.name in ("plus", "minus", "times", "mod"):
            return all(_expr_is_integer(a, seg) for a in e.args)
        return False
    return False


def _simplify(tree: FilterTree) -> FilterTree:
    """Constant-fold and flatten (reference: filter optimizer, `core/query/optimizer/filter/`)."""
    kind = tree[0]
    if kind in ("and", "or"):
        absorb, identity = (False, True) if kind == "and" else (True, False)
        children = []
        for c in tree[1]:
            c = _simplify(c)
            if c[0] == "const":
                if c[1] == absorb:
                    return ("const", absorb)
                continue  # identity: drop
            if c[0] == kind:  # flatten nested and(and(...)) — reference: FlattenAndOrFilterOptimizer
                children.extend(c[1])
            else:
                children.append(c)
        if not children:
            return ("const", identity)
        if len(children) == 1:
            return children[0]
        return (kind, tuple(children))
    if kind == "not":
        c = _simplify(tree[1])
        if c[0] == "const":
            return ("const", not c[1])
        if c[0] == "not":
            return c[1]
        return ("not", c)
    return tree
