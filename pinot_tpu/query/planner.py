"""Per-segment plan maker: choose execution strategy and build kernel specs/inputs.

Analog of the reference's `InstancePlanMakerImplV2.makeSegmentPlanNode`
(`pinot-core/.../plan/maker/InstancePlanMakerImplV2.java:153,243,288`) + segment pruners
(`core/query/pruner/`): decide per segment whether the query runs as

* `metadata` — answered from segment metadata alone, no scan (reference:
  `NonScanBasedAggregationOperator`): COUNT(*)/MIN/MAX with no filter;
* `empty`    — pruned: filter folds to constant-false (bloom / min-max / dictionary miss);
* `device`   — the fused TPU kernel (aggregation/group-by hot path);
* `host`     — numpy fallback for shapes the device path doesn't cover yet
  (group-by on expressions/raw columns, percentile/mode, huge key spaces);
* `selection`— mask on device, gather + order on host.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..segment.reader import ImmutableSegment
from ..sql.ast import Expr, Function, Identifier, Literal, identifiers_in
from .aggregates import AggContext, AggFunc, make_agg
from .context import QueryContext, QueryValidationError
from .dense_reduce import _dense_capable
from .predicate import CmpLeaf, FilterProgram, LutLeaf, NullLeaf, compile_filter

# The device's GROUP BY key is one int32 (`sum(ids * strides)`, the overflow
# key `num_keys_pad` for masked rows): a key space past it plans on the host.
# Up to `KernelCaps.dense_keys` the answer is a dense table of every key, past
# it the sort regime's sorted groups (`SegmentPlan.sparse`).
MAX_DEVICE_KEY_SPACE = (1 << 31) - 2
# grouped distinct presence matrix cap: (padded keys) x (dict-id lut) int32 cells
MAX_GROUPED_DISTINCT_CELLS = 1 << 22  # 16MB of presence counts per aggregation

# At or below this row count a scan on an accelerator backend goes to the host:
# one numpy pass is assumed cheaper than a dispatch + fetch round trip (star-tree
# record tables, small dimension tables). The threshold predates the directly
# attached chip and has not been re-measured there (ROADMAP queues the retune;
# chip_smoke.py prints the measured round trip).
SMALL_SCAN_DOCS = 1 << 16


def _accelerator_backend() -> bool:
    """True on a non-CPU jax backend (every device dispatch pays a host round
    trip); False under CPU jax, where tests keep full kernel coverage."""
    global _ACCELERATOR_BACKEND
    if _ACCELERATOR_BACKEND is None:
        import jax
        _ACCELERATOR_BACKEND = jax.default_backend() != "cpu"
    return _ACCELERATOR_BACKEND


_ACCELERATOR_BACKEND: Optional[bool] = None

from ..engine.datetime_fns import DEVICE_DATETIME_FUNCS
from ..engine.expr import widen_marks

_DEVICE_FUNCS = {"plus", "minus", "times", "divide", "mod", "case", "cast", "abs", "ceil",
                 "floor", "exp", "ln", "log10", "log2", "log", "sqrt", "power", "round",
                 "least", "greatest", "sign", "truncate", "eq", "neq", "gt", "gte", "lt",
                 "lte", "and", "or", "not", "in", "not_in", "between", "sin", "cos", "tan",
                 "asin", "acos", "atan", "sinh", "cosh", "tanh", "cot", "atan2", "degrees",
                 "radians"} | set(DEVICE_DATETIME_FUNCS)


@dataclass
class SegmentPlan:
    kind: str  # metadata | empty | device | host | selection
    segment: ImmutableSegment
    ctx: QueryContext
    aggs: List[AggFunc] = field(default_factory=list)
    group_exprs: List[Expr] = field(default_factory=list)
    filter_prog: Optional[FilterProgram] = None
    # device group-by geometry
    group_cols: Tuple[str, ...] = ()
    cards: Tuple[int, ...] = ()
    strides: Tuple[int, ...] = ()
    num_keys_real: int = 0
    num_keys_pad: int = 0
    # the key space is past `KernelCaps.dense_keys`: the kernel answers with
    # the groups that occur (`kernels._grouped_sparse`), not a dense table
    sparse: bool = False
    # upper bound on OCCUPIED groups (dictionary key-space product capped by
    # scanned docs): drives merge/decode strategy — array-form dense partials
    # vs per-group state dicts — without waiting for exact device counts
    card_hint: int = 0
    fallback_reason: str = ""
    # upsert: only rows set in this mask are visible (None = all rows)
    valid_docs: Optional[np.ndarray] = None
    # LUT-leaf indices the executor routed to the packed-word bitmap index
    # (select_bitmap_leaves; () when the knob is off or nothing qualifies)
    bitmap_leaves: Tuple[int, ...] = ()


def plan_segment(ctx: QueryContext, segment: ImmutableSegment,
                 valid_docs: Optional[np.ndarray] = None,
                 scan_docs: Optional[int] = None) -> SegmentPlan:
    """`scan_docs` overrides the row count the small-scan heuristic sees: the
    mesh path plans a whole SET from one probe segment and amortizes ONE
    dispatch across all of it, so it passes the set's total."""
    aggs = [make_agg(f) for f in ctx.aggregations]
    # DISTINCT rewrites to a group-by over the select expressions with no aggregations
    # (reference: DistinctOperator is a specialized group-by).
    if ctx.distinct:
        group_exprs = [e for e, _ in ctx.select_items]
    else:
        group_exprs = list(ctx.group_by)

    plan = SegmentPlan("host", segment, ctx, aggs, group_exprs)
    plan.valid_docs = valid_docs
    _validate_mv_usage(ctx, aggs, segment)
    for agg in aggs:
        agg.validate_args(segment)

    # -- filter compilation + constant-fold pruning ------------------------
    try:
        prog = compile_filter(ctx.filter, segment)
    except QueryValidationError:
        raise
    plan.filter_prog = _fold_leaves(prog, segment)
    if plan.filter_prog.tree == ("const", False):
        plan.kind = "empty"
        return plan

    if not ctx.is_aggregation_query and not ctx.distinct:
        plan.kind = "selection"
        return plan

    # -- metadata-only answers (unavailable under an upsert mask) ----------
    if (not group_exprs and plan.filter_prog.is_match_all and valid_docs is None
            and aggs and all(_metadata_answerable(a, segment) for a in aggs)):
        plan.kind = "metadata"
        return plan

    # -- device path feasibility ------------------------------------------
    if getattr(segment, "is_mutable", False):
        # consuming segments stay host-side; the TPU path starts at commit
        plan.kind = "host"
        plan.fallback_reason = "mutable (consuming) segment"
        return plan
    if (scan_docs if scan_docs is not None
            else segment.num_docs) <= SMALL_SCAN_DOCS \
            and _accelerator_backend():
        # tiny scans (star-tree record tables, mini dimension tables): one
        # numpy pass costs microseconds while a device dispatch pays a host
        # round trip per sync. CPU-jax (tests) keeps the device path so
        # kernel coverage is unaffected.
        plan.kind = "host"
        plan.fallback_reason = "small scan (host beats device dispatch)"
        return plan
    reason = _device_feasible(plan, segment)
    if reason:
        plan.kind = "host"
        plan.fallback_reason = reason
        return plan
    plan.kind = "device"
    # a group needs at least one row, so occupied groups <= min(key space, docs
    # actually scanned — the SET total on the mesh path, not the probe segment)
    if plan.card_hint:
        plan.card_hint = min(plan.card_hint,
                             scan_docs if scan_docs is not None
                             else segment.num_docs)
    return plan


def select_bitmap_leaves(plan: SegmentPlan,
                         segment: ImmutableSegment) -> Tuple[int, ...]:
    """LUT leaves worth evaluating through the packed-word bitmap index.

    Per-leaf regime choice (reference: the broker/server pruners choose
    index-vs-scan per predicate): a leaf qualifies when its column can carry a
    bitmap index (single-value dict column within BITMAP_MAX_CARD) AND its
    estimated selectivity sits at or below
    `KernelCaps.bitmap_sel_cap`. Selectivity comes from the inverted index's
    posting offsets when the segment has one (exact, O(ids) arithmetic),
    otherwise from matched-ids / cardinality (uniform-occupancy assumption).
    Dense predicates keep the interval-compare / one-hot LUT path, which beats
    streaming the whole word matrix when most rows match anyway."""
    from ..engine.caps import get_caps
    from ..engine.datablock import BITMAP_MAX_CARD
    if plan.filter_prog is None or plan.filter_prog.is_match_all \
            or getattr(segment, "is_mutable", False):
        return ()
    cap = get_caps().bitmap_sel_cap
    n = max(segment.num_docs, 1)
    out = []
    for i, leaf in enumerate(plan.filter_prog.leaves):
        if not isinstance(leaf, LutLeaf):
            continue
        reader = segment.column(leaf.col)
        if not reader.has_dictionary \
                or getattr(reader, "is_multi_value", False):
            continue
        card = reader.cardinality
        if card <= 0 or card > BITMAP_MAX_CARD:
            continue
        matched = leaf.lut[:card]
        inv = getattr(reader, "inverted_index", None)
        if inv is not None:
            sel = inv.match_count_for_ids(np.flatnonzero(matched)) / n
        else:
            sel = float(matched.sum()) / card
        if sel <= cap:
            out.append(i)
    return tuple(out)


def _validate_mv_usage(ctx: QueryContext, aggs: List[AggFunc],
                       segment: ImmutableSegment) -> None:
    """Reject shapes whose semantics need the *MV function family, with a clear
    error instead of a deep numpy crash (reference: AggregationFunctionFactory
    rejects SV functions over MV arguments)."""
    def is_mv(name: str) -> bool:
        try:
            return getattr(segment.column(name), "is_multi_value", False)
        except KeyError:
            return False

    for agg in aggs:
        if (isinstance(agg.arg, Identifier) and agg.arg.name != "*"
                and is_mv(agg.arg.name)
                and not agg.name.endswith("mv") and agg.name != "count"):
            raise QueryValidationError(
                f"{agg.name.upper()} over multi-value column {agg.arg.name!r}: "
                f"use {agg.name.upper()}MV")
    # selection ORDER BY on an MV cell compares ragged arrays — undefined. (In a
    # group-by, ORDER BY the MV *group key* is fine: keys are scalars after the
    # explode; ARRAYLENGTH/CARDINALITY order keys are scalars too.)
    if not ctx.is_aggregation_query and not ctx.distinct:
        for o in ctx.order_by:
            if any(is_mv(c) for c in identifiers_in(o.expr)) \
                    and not (isinstance(o.expr, Function)
                             and o.expr.name in ("arraylength", "cardinality")):
                raise QueryValidationError(
                    f"ORDER BY over multi-value column in {o.expr!r} is undefined")


def _fold_leaves(prog: FilterProgram, segment: ImmutableSegment) -> FilterProgram:
    """Fold decidable leaves to constants — this is segment pruning for free: an EQ
    literal absent from the dictionary, or a range disjoint from a raw column's
    [min, max] metadata, folds the whole tree to constant-false (reference:
    ColumnValueSegmentPruner + dictionary-miss shortcut; bloom filters serve the same
    role for EQ in the cluster-level pruner, see cluster/routing)."""
    from .predicate import _simplify  # shared with filter compilation

    def fold(node):
        if node[0] == "leaf":
            leaf = prog.leaves[node[1]]
            if isinstance(leaf, LutLeaf):
                card = segment.column(leaf.col).cardinality
                if not leaf.lut.any():
                    return ("const", False)
                if leaf.lut[:card].all():
                    return ("const", True)
            if isinstance(leaf, NullLeaf):
                has_nulls = segment.column(leaf.col).meta.get("hasNulls", False)
                if not has_nulls:
                    return ("const", leaf.negated)
            if isinstance(leaf, CmpLeaf) and isinstance(leaf.expr, Identifier):
                folded = _fold_cmp_minmax(leaf, segment)
                if folded is None:
                    folded = _fold_cmp_bloom(leaf, segment)
                if folded is not None:
                    return ("const", folded)
            return node
        if node[0] in ("and", "or"):
            return (node[0], tuple(fold(c) for c in node[1]))
        if node[0] == "not":
            return ("not", fold(node[1]))
        return node

    prog.tree = _simplify(fold(prog.tree))
    return prog


def _fold_cmp_minmax(leaf: CmpLeaf, segment: ImmutableSegment):
    """Decide a raw-column comparison from metadata min/max when possible.

    Returns True (matches everything), False (matches nothing), or None (must scan).
    """
    reader = segment.column(leaf.expr.name)
    mn, mx = reader.min_value, reader.max_value
    if mn is None or mx is None or not leaf.operands:
        return None
    ops = leaf.operands
    if leaf.op == "eq":
        return False if (ops[0] < mn or ops[0] > mx) else None
    if leaf.op == "in":
        return False if all(v < mn or v > mx for v in ops) else None
    if leaf.op in ("gte", "gt"):
        if ops[0] <= mn and leaf.op == "gte":
            return True
        if ops[0] < mn:
            return True
        if ops[0] > mx or (ops[0] == mx and leaf.op == "gt"):
            return False
        return None
    if leaf.op in ("lte", "lt"):
        if ops[0] >= mx and leaf.op == "lte":
            return True
        if ops[0] > mx:
            return True
        if ops[0] < mn or (ops[0] == mn and leaf.op == "lt"):
            return False
        return None
    if leaf.op == "between":
        lo, hi = ops
        if lo <= mn and hi >= mx:
            return True
        if hi < mn or lo > mx:
            return False
    return None


def _fold_cmp_bloom(leaf: CmpLeaf, segment: ImmutableSegment):
    """EQ/IN on a raw column with a bloom filter: definitely-absent values fold
    the leaf to constant false (reference: BloomFilterSegmentPruner runs this
    server-side per segment, not just at routing)."""
    if leaf.op not in ("eq", "in") or not leaf.operands:
        return None
    bloom = segment.column(leaf.expr.name).bloom_filter
    if bloom is None:
        return None
    if all(not bloom.might_contain(v) for v in leaf.operands):
        return False
    return None


def _metadata_answerable(agg: AggFunc, segment: ImmutableSegment) -> bool:
    if agg.name == "count" and (agg.arg is None or
                                (isinstance(agg.arg, Identifier) and agg.arg.name == "*")):
        return True
    if agg.name in ("min", "max", "minmaxrange") and isinstance(agg.arg, Identifier):
        reader = segment.column(agg.arg.name)
        return (reader.data_type.is_numeric and reader.min_value is not None
                and not getattr(reader, "is_multi_value", False))
    return False


def _device_feasible(plan: SegmentPlan, segment: ImmutableSegment) -> str:
    """Empty string if the fused device kernel can run this plan; else the reason."""
    # group-by columns must be plain dict-encoded columns with a bounded key space
    cards: List[int] = []
    cols: List[str] = []
    for e in plan.group_exprs:
        if not isinstance(e, Identifier):
            return f"group-by expression {e!r} (host transform)"
        reader = segment.column(e.name)
        if not reader.has_dictionary:
            return f"group-by on raw column {e.name}"
        if getattr(reader, "is_multi_value", False):
            # MV group-by explodes one row into one group per value — dense-key
            # matmul can't express that; host path explodes via mv offsets
            return f"group-by on multi-value column {e.name}"
        cols.append(e.name)
        cards.append(reader.cardinality)
    num_keys = 1
    for c in cards:
        num_keys *= max(c, 1)
    if cols and pad_keys(num_keys) > MAX_DEVICE_KEY_SPACE:
        return f"group key space {num_keys} is past the device's int32 key"
    from ..engine.caps import get_caps
    plan.sparse = bool(cols) and pad_keys(num_keys) > get_caps().dense_keys
    plan.group_cols = tuple(cols)
    plan.card_hint = num_keys if cols else 0  # clamped by scan docs in plan_segment

    group_by = bool(cols)
    for agg in plan.aggs:
        arg = agg.arg
        arg_is_dict = isinstance(arg, Identifier) and arg.name != "*" and \
            segment.column(arg.name).has_dictionary and \
            not getattr(segment.column(arg.name), "is_multi_value", False)
        arg_numeric = arg is None or not isinstance(arg, Identifier) or arg.name == "*" or \
            (segment.column(arg.name).data_type.is_numeric
             and not getattr(segment.column(arg.name), "is_multi_value", False))
        if not agg.device_ok(AggContext(group_by, arg_is_dict, arg_numeric)):
            return f"aggregation {agg.name} not device-supported here"
        if plan.sparse and ("distinct" in agg.device_outputs
                            or not _dense_capable(agg)):
            return (f"aggregation {agg.name} over a key space of {num_keys} "
                    f"(past the dense table: sorted groups carry sums, "
                    f"counts, MINs and MAXs)")
        err = _power_sum_f32_safe(agg, segment)
        if err:
            return err
        if arg_is_dict and "distinct" in agg.device_outputs:
            if group_by:
                # grouped distinct materializes a [keys, ids] presence matrix
                # on device; bound its memory (padded keys <= 2x real product)
                from ..engine.datablock import lut_size
                cells = 2 * num_keys * lut_size(
                    segment.column(arg.name).cardinality)
                if cells > MAX_GROUPED_DISTINCT_CELLS:
                    return (f"grouped {agg.name} presence matrix "
                            f"({cells} cells) exceeds device cap")
            continue  # distinct-family over a dict column works on ids; dtype irrelevant
        if arg is not None and not (isinstance(arg, Identifier) and arg.name == "*"):
            err = _expr_device_ok(arg, segment)
            if err:
                return err
            if {"min", "max"} & set(agg.device_outputs) \
                    and any(widen_marks(arg, int_ranges(plan))):
                # widened, the device's MIN/MAX would be a float32 near the
                # whole number; the host computes it in int64
                return (f"{agg.name} of INT arithmetic that leaves int32 "
                        f"(exact on the host: the device has no 64-bit integers)")

    if plan.filter_prog:
        for leaf in plan.filter_prog.leaves:
            if isinstance(leaf, CmpLeaf):
                err = _expr_device_ok(leaf.expr, segment)
                if err:
                    return err
    return ""


# Device power sums accumulate in f32 (~7 significant digits). Allow the device
# path only when max|x|^p stays within the f32 integer-exact-ish range, so the
# centered-moment subtraction at finalize is not pure cancellation noise; large
# columns (epoch timestamps, ids) take the f64 host path instead.
POWER_SUM_F32_LIMIT = float(1 << 20)


def _power_sum_f32_safe(agg, segment: ImmutableSegment) -> str:
    powers = [p for o, p in (("sum2", 2), ("sum3", 3), ("sum4", 4))
              if o in agg.device_outputs]
    if not powers:
        return ""
    if not isinstance(agg.arg, Identifier):
        return f"{agg.name} over an expression: unknown bounds for f32 power sums"
    reader = segment.column(agg.arg.name)
    mn, mx = reader.min_value, reader.max_value
    if mn is None or mx is None:
        return f"{agg.name}: no column bounds to prove f32 power sums safe"
    max_abs = max(abs(float(mn)), abs(float(mx)))
    if max_abs ** max(powers) > POWER_SUM_F32_LIMIT:
        return (f"{agg.name}: |{agg.arg.name}|^{max(powers)} exceeds f32 "
                f"precision budget (host f64 path)")
    return ""


def _expr_device_ok(e: Expr, segment: ImmutableSegment) -> str:
    """Device-evaluable: numeric identifiers representable in 32 bits, known functions."""
    for node_name in identifiers_in(e):
        reader = segment.column(node_name)
        if getattr(reader, "is_multi_value", False):
            return f"multi-value column {node_name} in expression (host path)"
        if not reader.data_type.is_numeric:
            return f"non-numeric column {node_name} in expression"
        mn, mx = reader.min_value, reader.max_value
        if (mn is not None and mx is not None and isinstance(mn, (int, np.integer))
                and (mn < -(2 ** 31) or mx >= 2 ** 31)):
            return f"column {node_name} exceeds int32 range (device is 32-bit)"
        if (mn is None or mx is None) and reader.data_type.numpy_dtype.itemsize > 4 \
                and np.dtype(reader.data_type.numpy_dtype).kind in "iu":
            # unknown bounds on a 64-bit integer column: cannot prove int32-safe
            return f"column {node_name} is 64-bit with unknown bounds"
    def check(node):
        if isinstance(node, Function):
            if node.name not in _DEVICE_FUNCS:
                return f"function {node.name} not device-supported"
            for a in node.args:
                err = check(a)
                if err:
                    return err
        return ""
    return check(e)


def int_ranges(plan: SegmentPlan, extra=()) -> Dict[str, Optional[Tuple[int, int]]]:
    """What the plan knows of the integers in every column its compare leaves,
    its aggregate arguments and the `extra` expressions read: column -> (lo,
    hi) from the segment's (on the mesh path the SET's) min/max, the type's own
    range where the metadata has none, and None for a column that is not of
    integers. `KernelSpec.int_ranges`: from it the device decides which `+`,
    `-`, `*` leave int32 and are computed in float32 (`engine/expr.widens`)."""
    exprs = [leaf.expr for leaf in (plan.filter_prog.leaves if plan.filter_prog
                                    else ()) if isinstance(leaf, CmpLeaf)]
    exprs += [a.arg for a in plan.aggs if a.arg is not None] + list(extra)
    out: Dict[str, Optional[Tuple[int, int]]] = {}
    for name in {n for e in exprs for n in identifiers_in(e)}:
        try:
            reader = plan.segment.column(name)
        except KeyError:
            continue
        if not reader.data_type.is_numeric:
            continue
        dtype = np.dtype(reader.data_type.numpy_dtype)
        if dtype.kind not in "iu":
            out[name] = None
            continue
        mn, mx = reader.min_value, reader.max_value
        if isinstance(mn, (int, np.integer)) and isinstance(mx, (int, np.integer)):
            out[name] = (int(mn), int(mx))
        else:
            out[name] = (int(np.iinfo(dtype).min), int(np.iinfo(dtype).max))
    return out


def pad_keys(s: int) -> int:
    """The padded key count of a key space of `s` keys: a power of two to
    4096, then a multiple of 4096 (`build_device_geometry`)."""
    if s <= 4096:
        return 1 << max(0, (s - 1)).bit_length()
    return -(-s // 4096) * 4096


def build_device_geometry(plan: SegmentPlan) -> None:
    """Fill dense-key geometry: strides over real cardinalities, padded key count.

    Padding quantizes the kernel-cache key (tables with nearby cardinalities
    share a compiled program): pow2 up to 4096, then MULTIPLES of 4096 — the
    chunked group-by kernel's work is linear in padded keys with 4096-key
    chunk granularity, so pow2 past 4096 would waste up to 2x device work
    (e.g. 20k real keys -> 32768 pow2 = 9 chunks vs 24576 = 6)."""
    cards = [plan.segment.column(c).cardinality for c in plan.group_cols]
    strides = []
    s = 1
    for c in cards:
        strides.append(s)
        s *= max(c, 1)
    plan.cards = tuple(cards)
    plan.strides = tuple(strides)
    plan.num_keys_real = s
    plan.num_keys_pad = pad_keys(s)
