"""Combine + reduce: merge per-segment partials, finalize, project, HAVING/ORDER/LIMIT.

Analog of the reference's combine operators + broker reduce
(`pinot-core/.../operator/combine/GroupByOrderByCombineOperator.java` merging into
`ConcurrentIndexedTable`, then `core/query/reduce/GroupByDataTableReducer.java`,
`PostAggregationHandler.java`, `HavingFilterHandler.java`). Here both levels use the same
value-keyed hash merge, because group keys are decoded to *values* before leaving a segment
(per-segment dictionaries don't align across segments).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..sql.ast import Expr, Function, Identifier, Literal
from ..engine.expr import eval_expr
from .aggregates import AggFunc
from .context import QueryContext
from .result import ResultTable


@dataclass
class DensePartial:
    """A group-by partial in ARRAY form over an aligned dense key space.

    At high cardinality the dict-of-states partial is the bottleneck: building
    (and merging, and wire-encoding) 500k Python state lists costs seconds
    while the kernel runs in tens of milliseconds. When every aggregation is
    dense-finalizable and the servers share aligned dictionaries (`token`
    matches), partials stay as the kernel's dense output arrays end to end:
    merge is elementwise (+/min/max), the wire carries flat ndarrays, and the
    broker finalizes vectorized (reference contrast: GroupByDataTableReducer's
    IndexedTable hash merge).
    """

    token: Tuple                  # (group cols, cards, dict hashes, num_keys)
    cards: Tuple[int, ...]
    strides: Tuple[int, ...]
    num_keys_real: int
    counts: np.ndarray            # int64[num_keys_real] (exact, mergeable by +)
    outs: Dict[str, np.ndarray]   # "<agg idx>.<out>" arrays, trimmed to real keys
    group_values: List[Any]       # per group col: the full dictionary value table
    # build-side only (never on the wire): lets server-local consumers
    # materialize classic state dicts without replanning
    aggs: Optional[List[AggFunc]] = None

    def merge_from(self, other: "DensePartial") -> None:
        self.counts = self.counts + other.counts
        for k, v in other.outs.items():
            if k.endswith(".min"):
                self.outs[k] = np.minimum(self.outs[k], v)
            elif k.endswith(".max"):
                self.outs[k] = np.maximum(self.outs[k], v)
            else:
                self.outs[k] = self.outs[k] + v


@dataclass
class SparsePartial:
    """A group-by partial in ARRAY form over the groups that OCCUR.

    Past `KernelCaps.dense_keys` a dense table of the key space would be one
    entry a key (67 MB a value row at TPC-H Q3's 16.8M order ids, of which
    about 117k occur), so the device answers with its sorted groups
    (`kernels._grouped_sparse`) and they stay arrays end to end: the group
    key VALUES (dictionaries differ across servers: values, not ids), the
    counts and every output a group, in key order. Partials merge by value,
    vectorized (`merge_sparse`), and the broker finalizes them as it does a
    `DensePartial`. A `trimmed` partial is already cut to the ORDER BY ...
    LIMIT on the device: it is the whole answer, and merges with nothing.
    """

    group_values: List[np.ndarray]  # per group column, each group's value
    counts: np.ndarray              # int64[groups] rows that passed a group
    outs: Dict[str, np.ndarray]     # "<agg idx>.<out>" arrays, a group each
    groups: int                     # groups the rows that passed held in all
    trimmed: bool = False
    # build-side only (never on the wire), as DensePartial's
    aggs: Optional[List[AggFunc]] = None


def merge_sparse(parts: List[SparsePartial]) -> SparsePartial:
    """Sparse partials as one, by group key value: counts and sums added, the
    least of the MINs and the greatest of the MAXs, in one vectorized pass.
    The groups come out in the order a device's key gives them (the last
    group column most significant, each column's values ascending), so a tie
    of the ORDER BY falls as it does in one server's trimmed answer."""
    if len(parts) == 1:
        return parts[0]
    if any(p.trimmed for p in parts):
        raise ValueError("a trimmed sparse partial is a whole answer: it "
                         "merges with no other partial")
    n_cols = len(parts[0].group_values)
    code = np.zeros(sum(len(p.counts) for p in parts), dtype=np.int64)
    for j in reversed(range(n_cols)):
        col = np.concatenate([np.asarray(p.group_values[j]) for p in parts])
        _, inv = np.unique(col, return_inverse=True)
        # compress after every column, so the code stays below the rows
        _, code = np.unique(code * (int(inv.max(initial=0)) + 1)
                            + inv.reshape(-1), return_inverse=True)
        code = code.reshape(-1)
    uniq, first = np.unique(code, return_index=True)
    size = len(uniq)
    values = [np.concatenate([np.asarray(p.group_values[j])
                              for p in parts])[first] for j in range(n_cols)]
    counts = np.zeros(size, dtype=np.int64)
    np.add.at(counts, code, np.concatenate([p.counts for p in parts]))
    outs: Dict[str, np.ndarray] = {}
    for name in parts[0].outs:
        v = np.concatenate([p.outs[name] for p in parts])
        if name.endswith((".min", ".max")):
            lo = name.endswith(".min")
            start = (v.max() if lo else v.min()) if v.size else 0
            acc = np.full(size, start, dtype=v.dtype)
            (np.minimum if lo else np.maximum).at(acc, code, v)
        else:
            acc = np.zeros(size, dtype=np.float64)
            np.add.at(acc, code, v.astype(np.float64))
        outs[name] = acc
    return SparsePartial(values, counts, outs, groups=size,
                         aggs=parts[0].aggs)


@dataclass
class SegmentResult:
    """Partial result of one segment (reference: IntermediateResultsBlock)."""

    kind: str  # "groups" | "scalar" | "selection"
    groups: Dict[Tuple, List[Any]] = field(default_factory=dict)  # key values -> agg states
    scalar: Optional[List[Any]] = None                            # agg states (no group-by)
    rows: List[Tuple] = field(default_factory=list)               # selection output rows
    sort_keys: List[Tuple] = field(default_factory=list)          # selection sort keys
    num_docs_scanned: int = 0
    # segments this SERVER-LEVEL partial actually covered (None for per-segment
    # results): lets the broker detect a replica that silently skipped a
    # segment mid-transition and retry it on another replica
    served: Optional[List[str]] = None
    # high-cardinality array-form partial; when set, `groups` is EMPTY until
    # `materialize_dense` converts (consumers that need the dict form call it)
    dense: Optional[DensePartial] = None
    # the array form past the dense key space (SparsePartial); `groups` is
    # EMPTY beside it, as beside `dense`
    sparse: Optional[SparsePartial] = None
    # per-query ExecutionStats counters accumulated producing this partial
    # (flat summable dict — see query/stats.py); rides the wire and merges
    # into the broker's record
    stats: Optional[Dict[str, float]] = None

    def materialize_dense(self, aggs: Optional[List[AggFunc]] = None) -> None:
        """Convert the array-form partial into the classic state dict (for
        dict-merge with non-dense partials, hash-partition shuffles, ...)."""
        if self.sparse is not None:
            self._materialize_sparse(aggs)
        dp = self.dense
        if dp is None:
            return
        use_aggs = aggs if aggs is not None else dp.aggs
        if use_aggs is None:
            raise ValueError("dense partial needs aggs to materialize")
        occupied = np.nonzero(dp.counts > 0)[0]
        value_cols = [
            np.asarray(dp.group_values[j])[
                (occupied // dp.strides[j]) % max(dp.cards[j], 1)]
            for j in range(len(dp.strides))]
        keys = (list(zip(*[c.tolist() for c in value_cols]))
                if len(occupied) else [])
        for row, k in enumerate(occupied):
            states = []
            for i, agg in enumerate(use_aggs):
                o = {"count": int(dp.counts[k])}
                for out_name in agg.device_outputs:
                    if out_name != "count":
                        o[out_name] = dp.outs[f"{i}.{out_name}"][k]
                states.append(agg.state_from_device(o))
            self.groups[keys[row]] = states
        self.dense = None

    def _materialize_sparse(self, aggs: Optional[List[AggFunc]]) -> None:
        sp = self.sparse
        use_aggs = aggs if aggs is not None else sp.aggs
        if use_aggs is None:
            raise ValueError("sparse partial needs aggs to materialize")
        if sp.trimmed:
            raise ValueError("a trimmed sparse partial is a whole answer")
        keys = list(zip(*[np.asarray(v).tolist() for v in sp.group_values]))
        for row, key in enumerate(keys):
            states = []
            for i, agg in enumerate(use_aggs):
                o = {"count": int(sp.counts[row])}
                for out_name in agg.device_outputs:
                    if out_name != "count":
                        o[out_name] = sp.outs[f"{i}.{out_name}"][row]
                states.append(agg.state_from_device(o))
            self.groups[key] = states
        self.sparse = None


def merge_segment_results(results: List[SegmentResult], aggs: List[AggFunc]) -> SegmentResult:
    """Server-level combine (also reused broker-side across servers)."""
    if not results:
        return SegmentResult("scalar", scalar=None)
    kind = results[0].kind
    out = SegmentResult(kind)
    out.num_docs_scanned = sum(r.num_docs_scanned for r in results)
    from .stats import MAX_KEYS, MIN_KEYS
    merged_stats: Dict[str, float] = {}
    for r in results:
        for k, v in (r.stats or {}).items():
            if k in MIN_KEYS:   # freshness timestamps: stalest side wins
                cur = merged_stats.get(k)
                merged_stats[k] = v if cur is None else min(cur, v)
            elif k in MAX_KEYS:  # per-launch skew: worst side wins
                cur = merged_stats.get(k)
                merged_stats[k] = v if cur is None else max(cur, v)
            else:
                merged_stats[k] = merged_stats.get(k, 0) + v
    out.stats = merged_stats or None  # set BEFORE the dense early return
    if kind == "groups":
        held = [r for r in results if r.groups or r.dense is not None
                or r.sparse is not None]
        if held and all(r.sparse is not None for r in held):
            # groups past the dense key space: merged by value, as arrays
            # (a pruned segment's empty partial adds nothing)
            out.sparse = merge_sparse([r.sparse for r in held])
            return out
        denses = [r.dense for r in results]
        if all(d is not None for d in denses) and \
                len({d.token for d in denses}) == 1:
            # partition-wise partial merge: servers with aligned dictionaries
            # agree on dense keys, so high-card partials combine elementwise
            # WITHOUT densifying 100k+ Python state dicts per server
            base = denses[0]
            acc = DensePartial(base.token, base.cards, base.strides,
                               base.num_keys_real,
                               base.counts.astype(np.int64, copy=True),
                               {k: v.copy() for k, v in base.outs.items()},
                               base.group_values, aggs=base.aggs)
            for d in denses[1:]:
                acc.merge_from(d)
            out.dense = acc
            return out
        for r in results:
            # mixed dense/dict (or unaligned dictionaries): densify once here
            r.materialize_dense(aggs)
        merged: Dict[Tuple, List[Any]] = {}
        for r in results:
            for key, states in r.groups.items():
                cur = merged.get(key)
                if cur is None:
                    merged[key] = list(states)
                else:
                    for i, agg in enumerate(aggs):
                        cur[i] = agg.merge(cur[i], states[i])
        out.groups = merged
    elif kind == "scalar":
        merged_states: Optional[List[Any]] = None
        for r in results:
            if r.scalar is None:
                continue
            if merged_states is None:
                merged_states = list(r.scalar)
            else:
                for i, agg in enumerate(aggs):
                    merged_states[i] = agg.merge(merged_states[i], r.scalar[i])
        out.scalar = merged_states
    else:
        for r in results:
            out.rows.extend(r.rows)
            out.sort_keys.extend(r.sort_keys)
    return out


def _object_array(vals: List[Any]) -> np.ndarray:
    """1-D object array of exactly len(vals) cells. np.array(vals, dtype=object)
    would splat equal-length LIST values (e.g. HISTOGRAM results) into a 2-D
    array instead of keeping one list per cell."""
    out = np.empty(len(vals), dtype=object)
    for i, v in enumerate(vals):
        out[i] = v
    return out


def reduce_to_result(ctx: QueryContext, merged: SegmentResult, aggs: List[AggFunc],
                     group_exprs: List[Expr]) -> ResultTable:
    """Broker-side reduce: finalize states, post-aggregate, HAVING, ORDER BY, LIMIT."""
    if merged.kind == "selection":
        return _reduce_selection(ctx, merged)

    # -- build the result-expression environment ---------------------------
    env: Dict[str, np.ndarray] = {}
    n_groups = None
    if merged.kind == "groups" and merged.dense is not None:
        # array-form partial: finalize VECTORIZED over occupied dense keys
        # (dense_values per agg + dictionary takes per group column) instead
        # of the per-group Python state loop below
        dp = merged.dense
        occupied = np.nonzero(dp.counts > 0)[0]
        n = len(occupied)
        key_values = [np.asarray(dp.group_values[j])[
            (occupied // dp.strides[j]) % max(dp.cards[j], 1)]
            for j in range(len(group_exprs))]
        _array_env(env, ctx, aggs, group_exprs, key_values,
                   dp.counts[occupied], lambda k: dp.outs[k][occupied])
    elif merged.kind == "groups" and merged.sparse is not None:
        # the groups that occur, already arrays (SparsePartial): the same
        # vectorized finalize
        sp = merged.sparse
        n, n_groups = len(sp.counts), sp.groups
        _array_env(env, ctx, aggs, group_exprs, sp.group_values, sp.counts,
                   lambda k: sp.outs[k])
    elif merged.kind == "groups":
        keys = list(merged.groups.keys())
        n = len(keys)
        for j, g in enumerate(group_exprs):
            env[repr(g)] = np.array([k[j] for k in keys], dtype=object)
        for i, call in enumerate(ctx.aggregations):
            vals = [aggs[i].finalize(merged.groups[k][i]) for k in keys]
            env[repr(call)] = _object_array(vals)
    else:
        n = 1
        states = merged.scalar
        for i, call in enumerate(ctx.aggregations):
            v = (aggs[i].finalize(states[i]) if states is not None
                 else aggs[i].empty_result())
            env[repr(call)] = _object_array([v])

    # -- HAVING ------------------------------------------------------------
    keep = np.ones(n, dtype=bool)
    if ctx.having is not None:
        keep &= np.asarray(_eval_result(ctx.having, env, n), dtype=bool)

    # -- project select items ---------------------------------------------
    out_cols: List[np.ndarray] = []
    for expr, _name in ctx.select_items:
        out_cols.append(np.asarray(_eval_result(expr, env, n), dtype=object))

    # -- ORDER BY ----------------------------------------------------------
    idx = np.nonzero(keep)[0].tolist()
    if ctx.order_by:
        sort_cols = [np.asarray(_eval_result(o.expr, env, n), dtype=object)
                     for o in ctx.order_by]
        idx.sort(key=lambda i: _sort_key(
            [c[i] for c in sort_cols], ctx.order_by))

    if ctx.gapfill is not None:
        rows = _apply_gapfill(ctx, group_exprs,
                              [[col[i] for col in out_cols] for i in idx])
        if ctx.order_by:
            # gap rows were generated series-first/bucket-ascending; re-apply the
            # query's ORDER BY (over select-item columns) before OFFSET/LIMIT
            sel_repr = {repr(e): j for j, (e, _) in enumerate(ctx.select_items)}
            cols = [sel_repr.get(repr(o.expr)) for o in ctx.order_by]
            if all(c is not None for c in cols):
                rows.sort(key=lambda r: _sort_key([r[c] for c in cols],
                                                  ctx.order_by))
        rows = rows[ctx.offset:ctx.offset + ctx.limit]
        return ResultTable([name for _, name in ctx.select_items], _pyify(rows),
                           {"numDocsScanned": merged.num_docs_scanned,
                            "gapfilled": True})

    idx = idx[ctx.offset:ctx.offset + ctx.limit]
    rows = [[col[i] for col in out_cols] for i in idx]
    if merged.kind == "groups" and n_groups is None:
        n_groups = n
    return ResultTable([name for _, name in ctx.select_items], _pyify(rows),
                       {"numDocsScanned": merged.num_docs_scanned,
                        "numGroupsTotal": n_groups})


def _array_env(env: Dict[str, np.ndarray], ctx: QueryContext,
               aggs: List[AggFunc], group_exprs: List[Expr], key_values,
               counts: np.ndarray, out) -> None:
    """The reduce's environment from array-form groups: each group column's
    values, and each aggregation finalized over every group at once
    (`dense_values`; `out(name)` is an output array a group)."""
    for j, g in enumerate(group_exprs):
        env[repr(g)] = _object_array(np.asarray(key_values[j]).tolist())
    for i, call in enumerate(ctx.aggregations):
        agg = aggs[i]

        def get(name, i=i):
            if name == "count":
                return counts
            return out(f"{i}.{name}")

        vals = np.asarray(agg.dense_values(get, counts))
        cells = _object_array(vals.tolist())
        if agg.dense_nan_is_null and vals.dtype.kind == "f":
            # scalar finalize returns None where the dense form emits NaN
            for bad in np.nonzero(vals != vals)[0]:
                cells[bad] = None
        env[repr(call)] = cells


def _apply_gapfill(ctx: QueryContext, group_exprs: List[Expr],
                   rows: List[List[Any]]) -> List[List[Any]]:
    """Fill missing time buckets per series (reference: GapfillProcessor).

    Output is ordered (series in first-seen order, then time bucket ascending);
    series keys are the non-time group-by select items."""
    gf = ctx.gapfill
    ti = gf.index
    group_reprs = {repr(g) for g in group_exprs}
    key_idx = [j for j, (e, _) in enumerate(ctx.select_items)
               if j != ti and repr(e) in group_reprs]

    series: Dict[Tuple, Dict[Any, List[Any]]] = {}
    for row in rows:
        key = tuple(row[j] for j in key_idx)
        series.setdefault(key, {})[row[ti]] = row

    buckets = range(gf.start, gf.end, gf.bucket)
    out: List[List[Any]] = []
    for key, by_time in series.items():
        prev: Dict[int, Any] = {}
        for b in buckets:
            row = by_time.get(b)
            if row is None:
                row = [None] * len(ctx.select_items)
                row[ti] = b
                for j, v in zip(key_idx, key):
                    row[j] = v
                for j in range(len(row)):
                    if j == ti or j in key_idx:
                        continue
                    mode, default = gf.fills.get(j, (None, None))
                    if mode == "FILL_PREVIOUS_VALUE":
                        row[j] = prev.get(j)
                    elif mode == "FILL_DEFAULT_VALUE":
                        row[j] = default
            else:
                for j in range(len(row)):
                    prev[j] = row[j]
            out.append(row)
    return out


def _reduce_selection(ctx: QueryContext, merged: SegmentResult) -> ResultTable:
    order = list(range(len(merged.rows)))
    if ctx.order_by:
        order.sort(key=lambda i: _sort_key(list(merged.sort_keys[i]), ctx.order_by))
    order = order[ctx.offset:ctx.offset + ctx.limit]
    rows = [list(merged.rows[i]) for i in order]
    return ResultTable([name for _, name in ctx.select_items], _pyify(rows),
                       {"numDocsScanned": merged.num_docs_scanned})


class _Reverse:
    """Inverts comparison order for DESC keys of arbitrary comparable type."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, other):
        return other.v < self.v

    def __eq__(self, other):
        return self.v == other.v


def _sort_key(values: List[Any], order_by) -> Tuple:
    key = []
    for v, o in zip(values, order_by):
        # Null ordering: reference treats null as largest unless NULLS FIRST/LAST given.
        nulls_last = o.nulls_last if o.nulls_last is not None else not o.desc
        is_null = v is None
        null_rank = (1 if is_null else 0) if nulls_last else (0 if is_null else 1)
        v = 0 if is_null else v
        key.append((null_rank, _Reverse(v) if o.desc else v))
    return tuple(key)


def _eval_result(e: Expr, env: Dict[str, np.ndarray], n: int):
    """Evaluate a result-shaping expression: aggregation/group subtrees come from `env`
    (keyed by canonical repr), remaining arithmetic evaluates vectorized on host."""
    sub, bindings = _substitute(e, env)
    out = eval_expr(sub, bindings, np)
    if np.isscalar(out) or not hasattr(out, "__len__"):
        return np.full(n, out, dtype=object)
    return out


def _substitute(e: Expr, env: Dict[str, np.ndarray], bindings=None):
    if bindings is None:
        bindings = {}
    r = repr(e)
    if r in env:
        name = f"\x00{len(bindings)}"
        # reuse binding for identical subtrees
        for k, v in bindings.items():
            if v is env[r]:
                name = k
                break
        bindings[name] = env[r]
        return Identifier(name), bindings
    if isinstance(e, Function):
        new_args = []
        for a in e.args:
            na, bindings = _substitute(a, env, bindings)
            new_args.append(na)
        return Function(e.name, tuple(new_args), e.distinct), bindings
    if isinstance(e, Identifier):
        raise KeyError(f"unresolved column {e.name!r} in post-aggregation expression")
    return e, bindings


def _pyify(rows: List[List[Any]]) -> List[List[Any]]:
    out = []
    for row in rows:
        out.append([v.item() if isinstance(v, np.generic) else v for v in row])
    return out
