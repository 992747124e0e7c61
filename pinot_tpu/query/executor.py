"""Per-server query executor: plan + run each segment, combine, reduce.

Analog of `ServerQueryExecutorV1Impl.processQuery`
(`pinot-core/.../query/executor/ServerQueryExecutorV1Impl.java:130`): acquire segments,
plan per segment (`planner.py`), execute (device kernel / host fallback / selection),
combine partials (`reduce.merge_segment_results`) and — when used standalone, as in the
single-process tests — run the broker reduce too (`reduce.reduce_to_result`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..segment.reader import ImmutableSegment
from ..sql.ast import Expr, Function, Identifier, identifiers_in
from . import stats as qstats
from .aggregates import AggFunc, make_agg
from .context import QueryContext, compile_query
from .planner import (SegmentPlan, build_device_geometry, int_ranges,
                      plan_segment)
from .predicate import CmpLeaf, DocSetLeaf, LutLeaf, NullLeaf
from .reduce import DensePartial, SegmentResult, merge_segment_results, reduce_to_result
from .result import ResultTable

#: per-segment plan kind -> the explain-plan label family it annotates in
#: EXPLAIN ANALYZE (prefix-matched against plan-node labels)
_PLAN_OP_LABELS = {"empty": "PRUNED", "metadata": "METADATA_ONLY_AGGREGATE",
                   "selection": "SELECT", "device": "DEVICE_FUSED",
                   "host": "HOST"}

#: below this dense-key-space size the classic dict partial is cheap enough
#: that the array form only adds wire weight (it ships full dictionaries)
DENSE_PARTIAL_MIN_GROUPS = 4096

#: functions allowed to materialize doc ids on the host (np.nonzero /
#: postings loops): declared fallbacks and decode paths. Everything else in
#: this module must stay in the vectorized/device regime — enforced by the
#: `filter-path-host-materialization` graftcheck rule
__graft_slow_paths__ = ("_decode_group_partials", "_decode_scalar_partials",
                        "_host_aggregate", "_selection", "host_filter_mask")


class ServerQueryExecutor:
    """Executes a QueryContext over a set of local segments."""

    def __init__(self, use_device: bool = True, bitmap_enabled: bool = True,
                 fused_enabled: bool = True):
        self.use_device = use_device
        # packed-word bitmap filter indexes (clusterConfig/
        # server.index.bitmap.enabled): off -> every dict filter leaf keeps
        # the interval-compare / LUT path regardless of selectivity
        self.bitmap_enabled = bitmap_enabled
        # fused single-launch execution over compressed resident forms.
        # No production caller passes False: it forces the staged two-launch
        # path (decoded HBM columns, mask launch + aggregate launch)
        # everywhere, which tests hold as the fused path's byte-identical
        # reference
        self.fused_enabled = fused_enabled

    # -- public API --------------------------------------------------------
    def execute(self, segments: Sequence[ImmutableSegment],
                query: Union[str, QueryContext], schema=None) -> ResultTable:
        import time as _t
        t0 = _t.perf_counter()
        ctx = compile_query(query, schema or (segments[0].schema if segments else None)) \
            if isinstance(query, str) else query
        if ctx.analyze:
            return self._execute_analyze(segments, ctx)
        if ctx.explain:
            from .explain import explain_result
            return explain_result(ctx, segments)
        if qstats.current_stats() is None:
            # single-process entry (no server wrapper installed a record):
            # collect here so the engine API surfaces the same stats block
            # as the broker path does
            with qstats.collect_stats():
                return self.execute(segments, ctx)
        aggs = [make_agg(f) for f in ctx.aggregations]
        group_exprs = ([e for e, _ in ctx.select_items] if ctx.distinct
                       else list(ctx.group_by))
        t_compile = _t.perf_counter()
        results = [self.execute_segment(ctx, seg) for seg in segments]
        t_scan = _t.perf_counter()
        merged = merge_segment_results(results, aggs)
        if not results:
            merged.kind = ("groups" if (group_exprs or ctx.distinct) else
                           "scalar" if aggs else "selection")
        result = reduce_to_result(ctx, merged, aggs, group_exprs)
        # per-phase wall times (reference: ServerQueryPhase SCHEDULER_WAIT /
        # QUERY_PLANNING / QUERY_PROCESSING), surfaced in the response stats
        result.stats["phaseTimesMs"] = {
            "compile": round((t_compile - t0) * 1000, 3),
            "scan": round((t_scan - t_compile) * 1000, 3),
            "reduce": round((_t.perf_counter() - t_scan) * 1000, 3),
        }
        # per-operator rollups for EXPLAIN ANALYZE (no-op without a record)
        qstats.record_operator("COMBINE", rows=merged.num_docs_scanned,
                               ms=(t_scan - t_compile) * 1000)
        qstats.record_operator("BROKER_REDUCE", rows=len(result.rows),
                               ms=(_t.perf_counter() - t_scan) * 1000)
        st = qstats.current_stats()
        if st is not None:
            result.stats.update(st.to_public_dict())
        return result

    def _execute_analyze(self, segments: Sequence[ImmutableSegment],
                         ctx: QueryContext) -> ResultTable:
        """EXPLAIN ANALYZE (single-process path): run the real query with a
        fresh stats record, then render the plan tree annotated with each
        node's rows/ms (reference: postgres-style EXPLAIN ANALYZE; the
        reference engine has no direct analog)."""
        from .explain import analyze_result
        run_ctx = dataclasses.replace(ctx, explain=False, analyze=False)
        t0 = time.perf_counter()
        with qstats.collect_stats() as st:
            inner = self.execute(segments, run_ctx)
        total_ms = (time.perf_counter() - t0) * 1000
        return analyze_result(ctx, segments, st, inner, total_ms)

    # -- per-segment execution --------------------------------------------
    def execute_segment(self, ctx: QueryContext, segment: ImmutableSegment,
                        valid_docs: Optional[np.ndarray] = None) -> SegmentResult:
        # star-tree rewrite (not under an upsert valid-doc mask: pre-aggregated
        # records cannot honor per-doc visibility, same restriction as the reference)
        if valid_docs is None and not getattr(segment, "is_mutable", False):
            from .startree_exec import reassemble, try_star_tree
            stp = try_star_tree(ctx, segment)
            if stp is not None:
                sub = self.execute_segment(stp.ctx2, stp.tree.view,
                                           valid_docs=stp.record_mask)
                reassemble(stp, sub)
                return sub
        from ..utils.trace import span
        with span("plan"):
            plan = plan_segment(ctx, segment, valid_docs)
        if not self.use_device and plan.kind == "device":
            plan.kind = "host"
            plan.fallback_reason = "device disabled"
        t0 = time.perf_counter()
        with span(f"exec:{plan.kind}"):
            if plan.kind == "empty":
                r = self._empty_result(plan)
            elif plan.kind == "metadata":
                r = self._metadata_result(plan)
            elif plan.kind == "selection":
                r = self._selection(plan)
            elif plan.kind == "device":
                r = self._device_aggregate(plan)
            else:
                r = self._host_aggregate(plan)
        st = qstats.current_stats()
        if st is not None:
            ms = (time.perf_counter() - t0) * 1000
            if plan.kind == "empty":
                st.add(qstats.NUM_SEGMENTS_PRUNED)
                st.add(qstats.SCAN_ROWS_AVOIDED, segment.num_docs)
            else:
                st.add(qstats.NUM_SEGMENTS_QUERIED)
                if (r.num_docs_scanned > 0 or r.groups or r.rows
                        or r.dense is not None or plan.kind == "metadata"):
                    st.add(qstats.NUM_SEGMENTS_MATCHED)
            st.add_operator("SEGMENT_PLAN", rows=r.num_docs_scanned, ms=ms)
            label = _PLAN_OP_LABELS[plan.kind]
            if plan.kind == "device" and \
                    getattr(plan, "exec_mode", "fused") == "staged":
                label = "DEVICE_STAGED"  # two-launch fallback rung
            st.add_operator(label, rows=r.num_docs_scanned, ms=ms)
        return r

    # ------------------------------------------------------------------
    def _result_kind(self, plan: SegmentPlan) -> str:
        return "groups" if plan.group_exprs else "scalar"

    def _empty_result(self, plan: SegmentPlan) -> SegmentResult:
        if plan.group_exprs:
            return SegmentResult("groups")
        if not plan.ctx.is_aggregation_query and not plan.ctx.distinct:
            # a pruned SELECTION segment contributes zero rows — NOT a scalar
            # block, which would route the broker reduce down the aggregation
            # path and crash resolving bare columns
            return SegmentResult("selection")
        empty = np.empty(0, dtype=np.float64)
        return SegmentResult("scalar",
                             scalar=[a.host_state(empty) for a in plan.aggs] or None)

    def _metadata_result(self, plan: SegmentPlan) -> SegmentResult:
        """Answer from metadata without scanning (NonScanBasedAggregationOperator)."""
        seg = plan.segment
        states: List[Any] = []
        for agg in plan.aggs:
            if agg.name == "count":
                states.append(seg.num_docs)
            else:
                reader = seg.column(agg.arg.name)
                mn, mx = float(reader.min_value), float(reader.max_value)
                if agg.name == "min":
                    states.append(mn)
                elif agg.name == "max":
                    states.append(mx)
                else:  # minmaxrange
                    states.append((mn, mx))
        return SegmentResult("scalar", scalar=states, num_docs_scanned=0)

    # -- device aggregation path ----------------------------------------
    def _device_aggregate(self, plan: SegmentPlan) -> SegmentResult:
        from ..engine import kernels
        from ..engine.datablock import block_for, lut_size

        seg = plan.segment
        build_device_geometry(plan)
        agg_specs: List[Tuple[AggFunc, Tuple[str, ...]]] = []
        distinct_lut_sizes: Dict[int, int] = {}
        for i, agg in enumerate(plan.aggs):
            agg_specs.append((agg, agg.device_outputs))
            if "distinct" in agg.device_outputs:
                distinct_lut_sizes[i] = lut_size(seg.column(agg.arg.name).cardinality)

        block = block_for(seg)
        plan.bitmap_leaves = self._bitmap_leaves(plan, seg)
        fused_cols = self._fused_cols(plan, seg, block)
        plan.exec_mode = "staged" if fused_cols is None else "fused"
        spec = kernels.KernelSpec(plan.filter_prog, plan.group_cols, plan.num_keys_pad,
                                  tuple(agg_specs), distinct_lut_sizes, block.padded,
                                  mv_cols=_mv_lut_cols(plan, seg),
                                  bitmap_leaves=plan.bitmap_leaves,
                                  fused_cols=fused_cols or (),
                                  int_ranges=int_ranges(plan))
        inputs = self._kernel_inputs(plan, spec, block)
        if fused_cols is None:
            outs = kernels.run_kernel_staged(spec, inputs)
        else:
            outs = kernels.run_kernel(spec, inputs)

        if plan.sparse:
            # one segment's groups are a partial: never trimmed here
            r = self._decode_sparse_partial(plan, outs, trimmed=False)
            return r if r is not None else self._host_aggregate(plan)
        if plan.group_cols:
            return self._decode_group_partials(plan, outs)
        return self._decode_scalar_partials(plan, outs)

    def _fused_cols(self, plan: SegmentPlan, seg,
                    block) -> Optional[Tuple[Tuple[str, str], ...]]:
        """(col, form) routing for a fused single-launch plan, or None when
        the plan takes the staged two-launch path, decided from the plan
        alone.

        Fused iff every value column (filter compare expressions + aggregate
        arguments) stays in a compressed resident form the kernel can decode
        in-register: a single-value dict column whose padded decode table
        fits `KernelCaps.fused_lut_cap` routes as ("dict"), a raw int column
        with a profitable frame-of-reference form as ("for"), and plain raw
        columns pass through unrouted (their resident form IS the value
        form). A multi-value or over-cap dict value column means the decoded
        HBM cache would be built anyway — the plan stages instead."""
        from ..engine.caps import get_caps
        from ..engine.datablock import lut_size
        caps = get_caps()
        if not self.fused_enabled or getattr(seg, "is_mutable", False):
            return None
        fused: List[Tuple[str, str]] = []
        for c in sorted(_plan_vals_cols(plan)):
            reader = seg.column(c)
            if getattr(reader, "is_multi_value", False):
                return None
            if reader.has_dictionary:
                if lut_size(reader.cardinality) > caps.fused_lut_cap:
                    return None
                fused.append((c, "dict"))
            elif block.for_form(c) is not None:
                fused.append((c, "for"))
        return tuple(fused)

    def _bitmap_leaves(self, plan: SegmentPlan, seg) -> Tuple[int, ...]:
        if not self.bitmap_enabled:
            return ()
        from .planner import select_bitmap_leaves
        return select_bitmap_leaves(plan, seg)

    def _kernel_inputs(self, plan: SegmentPlan, spec, block):
        import jax.numpy as jnp
        from ..engine.kernels import KernelInputs

        ids_cols = set(plan.group_cols)
        vals_cols = set()
        nulls_cols = set()
        luts = []
        iscal: List[int] = []
        fscal: List[float] = []
        docsets = []
        bitmaps = []
        for li, leaf in enumerate(plan.filter_prog.leaves):
            if isinstance(leaf, LutLeaf):
                if li in spec.bitmap_index:
                    # packed-word path: gather only the LUT-selected dict-id
                    # rows from the HBM word matrix, padded to pow2 by
                    # repeating a selected row (OR-idempotent, bounds
                    # retraces); this leaf never reads the forward id column
                    # and its word traffic scales with selectivity, not card
                    words = block.bitmap_words(leaf.col)
                    assert words is not None, (
                        f"leaf {li} ({leaf.col}) marked bitmap but the block "
                        "declined to build words — planner/block gating drifted")
                    luts.append(jnp.asarray(leaf.lut))
                    sel = np.asarray(leaf.lut)[:words.shape[0]].astype(bool)
                    rows = np.where(sel)[0]
                    if rows.size == 0:
                        bitmaps.append(jnp.zeros((1, words.shape[1]),
                                                 dtype=jnp.uint32))
                    else:
                        k = 1 << int(rows.size - 1).bit_length()
                        idx = np.concatenate(
                            [rows, np.full(k - rows.size, rows[0])])
                        bitmaps.append(jnp.take(
                            words, jnp.asarray(idx.astype(np.int32)), axis=0))
                    continue
                ids_cols.add(leaf.col)
                if leaf.intervals is not None:
                    # interval bounds ride the int scalar stream, in leaf order —
                    # must mirror KernelSpec.__post_init__ routing exactly
                    for lo, hi in leaf.intervals:
                        iscal.extend((lo, hi))
                else:
                    luts.append(jnp.asarray(leaf.lut))
            elif isinstance(leaf, CmpLeaf):
                vals_cols.update(identifiers_in(leaf.expr))
                (iscal if leaf.is_int else fscal).extend(leaf.operands)
            elif isinstance(leaf, NullLeaf):
                nulls_cols.add(leaf.col)
            elif isinstance(leaf, DocSetLeaf):
                padded = np.zeros(block.padded, dtype=bool)
                padded[:len(leaf.mask)] = leaf.mask
                docsets.append(jnp.asarray(padded))
        agg_luts: Dict[str, "jnp.ndarray"] = {}
        for i, agg in enumerate(plan.aggs):
            if "distinct" in agg.device_outputs:
                ids_cols.add(agg.arg.name)
            elif agg.arg is not None and not (isinstance(agg.arg, Identifier)
                                              and agg.arg.name == "*"):
                vals_cols.update(identifiers_in(agg.arg))

        valid = block.valid
        valid_words = block.valid_words
        if plan.valid_docs is not None:
            padded = np.zeros(block.padded, dtype=bool)
            padded[:len(plan.valid_docs)] = plan.valid_docs
            valid = valid & jnp.asarray(padded)  # upsert valid-doc intersection
            valid_words = None                   # packed form is now stale

        # fused plans keep value columns in compressed resident form: a
        # "dict" column ships its padded decode table via vals plus the id
        # column via ids (decoded by _fused_env: selects for a small table,
        # a gather for a wide one), a "for"
        # column ships narrow deltas via vals with its base appended to
        # iscal AFTER every filter scalar, in fused_cols order — must
        # mirror KernelSpec.__post_init__'s for_offset routing exactly
        fused = dict(spec.fused_cols)
        vals = {}
        for c in vals_cols:
            form = fused.get(c)
            if form == "dict":
                ids_cols.add(c)
                vals[c] = block.dict_values(c)
            elif form == "for":
                vals[c] = block.for_form(c)[1]
            else:
                vals[c] = block.values(c)
        for c, form in spec.fused_cols:
            if form == "for":
                iscal.append(block.for_form(c)[0])

        return KernelInputs(
            ids={c: block.ids(c) for c in ids_cols},
            vals=vals,
            luts=tuple(luts),
            iscal=jnp.asarray(np.asarray(iscal, dtype=np.int32)),
            fscal=jnp.asarray(np.asarray(fscal, dtype=np.float32)),
            nulls={c: block.null_mask(c) for c in nulls_cols},
            valid=valid,
            strides=jnp.asarray(np.asarray(plan.strides, dtype=np.int32)),
            agg_luts=agg_luts,
            docsets=tuple(docsets),
            bitmaps=tuple(bitmaps),
            valid_words=valid_words,
        )

    def _decode_group_partials(self, plan: SegmentPlan, outs,
                               trim_global: bool = False) -> SegmentResult:
        seg = plan.segment
        counts = outs["count"][:plan.num_keys_real]
        occupied = np.nonzero(counts > 0)[0]
        if trim_global:
            # outs are GLOBAL (post-collective) partials, so an order-by trim here is
            # exact — the TableResizer analog, but vectorized over the dense key space
            # instead of a heap, bounding the decode loop to k groups
            occupied = _trim_occupied(plan, outs, occupied)
        # decode dense keys -> per-column dict ids -> values (vectorized per column)
        value_cols = []
        for j, col in enumerate(plan.group_cols):
            ids_j = (occupied // plan.strides[j]) % max(plan.cards[j], 1)
            value_cols.append(seg.column(col).dictionary.take(ids_j.astype(np.int64)))
        keys = list(zip(*[c.tolist() for c in value_cols])) if len(occupied) else []

        result = SegmentResult("groups")
        result.num_docs_scanned = int(counts.sum())
        # per-agg distinct decode inputs (grouped presence matrices)
        distinct_readers = {
            i: seg.column(agg.arg.name)
            for i, agg in enumerate(plan.aggs)
            if "distinct" in agg.device_outputs}
        for row, k in enumerate(occupied):
            states = []
            for i, agg in enumerate(plan.aggs):
                if i in distinct_readers:
                    reader = distinct_readers[i]
                    presence = outs[f"{i}.distinct"][k][:reader.cardinality]
                    if getattr(agg, "wants_id_counts", False):
                        states.append(agg.state_from_id_counts(
                            reader.dictionary, np.asarray(presence)))
                    else:
                        states.append(agg.state_from_present_ids(
                            reader.dictionary, np.nonzero(presence > 0)[0]))
                    continue
                o = {"count": int(counts[k])}
                for out_name in agg.device_outputs:
                    if out_name != "count":
                        o[out_name] = outs[f"{i}.{out_name}"][k]
                states.append(agg.state_from_device(o))
            result.groups[tuple(keys[row])] = states
        return result

    def _decode_dense_partial(self, plan: SegmentPlan, outs) -> Optional[SegmentResult]:
        """Array-form partial decode (see `reduce.DensePartial`): skip the
        per-group Python state loop entirely at high cardinality. Returns None
        when the plan can't prove cross-server key alignment (missing dict
        hashes) or the dense form wouldn't pay for itself."""
        from .dense_reduce import _dense_capable
        if plan.num_keys_real < DENSE_PARTIAL_MIN_GROUPS:
            return None
        if not all(_dense_capable(a) for a in plan.aggs):
            return None
        if any("distinct" in a.device_outputs for a in plan.aggs):
            return None
        seg = plan.segment
        dict_hashes = []
        for col in plan.group_cols:
            h = seg.column(col).meta.get("dictHash")
            if h is None:
                return None  # can't prove dictionaries align across servers
            dict_hashes.append(h)
        counts = np.asarray(outs["count"][:plan.num_keys_real]).astype(np.int64)
        dp_outs = {}
        for i, agg in enumerate(plan.aggs):
            for out_name in agg.device_outputs:
                if out_name != "count":
                    dp_outs[f"{i}.{out_name}"] = np.asarray(
                        outs[f"{i}.{out_name}"][:plan.num_keys_real])
        group_values = [
            seg.column(col).dictionary.take(
                np.arange(plan.cards[j], dtype=np.int64))
            for j, col in enumerate(plan.group_cols)]
        token = (tuple(plan.group_cols), tuple(plan.cards),
                 tuple(dict_hashes), plan.num_keys_real)
        dp = DensePartial(token, tuple(plan.cards), tuple(plan.strides),
                          plan.num_keys_real, counts, dp_outs, group_values,
                          aggs=plan.aggs)
        return SegmentResult("groups", dense=dp,
                             num_docs_scanned=int(counts.sum()))

    def _decode_sparse_partial(self, plan: SegmentPlan, outs,
                               trimmed: bool) -> Optional[SegmentResult]:
        """The sorted groups of a GROUP BY past the dense key space
        (`kernels._grouped_sparse`) as a `SparsePartial`: each group's key
        decoded to its values through the plan's dictionaries (a merged id
        space's global ones on that path), counts and outputs as arrays, in
        key order or, `trimmed`, in the ORDER BY's. None where more rows
        passed than the launch held: the host answers."""
        from ..engine.kernels import SPARSE_GROUPS, SPARSE_KEYS, SPARSE_ROWS
        from .reduce import SparsePartial
        groups = int(outs[SPARSE_GROUPS])
        if groups < 0:
            return None
        keys = np.asarray(outs[SPARSE_KEYS])
        n = min(groups, len(keys))
        keys = keys[:n].astype(np.int64)
        seg = plan.segment
        values = [seg.column(col).dictionary.take(
            (keys // plan.strides[j]) % max(plan.cards[j], 1))
            for j, col in enumerate(plan.group_cols)]
        arrays = {f"{i}.{o}": np.asarray(outs[f"{i}.{o}"][:n])
                  for i, agg in enumerate(plan.aggs)
                  for o in agg.device_outputs if o != "count"}
        sp = SparsePartial(values, np.asarray(outs["count"][:n]).astype(
            np.int64), arrays, groups=groups, trimmed=trimmed, aggs=plan.aggs)
        return SegmentResult("groups", sparse=sp,
                             num_docs_scanned=int(outs[SPARSE_ROWS]))

    def _decode_scalar_partials(self, plan: SegmentPlan, outs) -> SegmentResult:
        seg = plan.segment
        count = int(outs["count"])
        states: List[Any] = []
        for i, agg in enumerate(plan.aggs):
            if "distinct" in agg.device_outputs:
                presence = outs[f"{i}.distinct"]
                reader = seg.column(agg.arg.name)
                if getattr(agg, "wants_id_counts", False):
                    states.append(agg.state_from_id_counts(
                        reader.dictionary,
                        np.asarray(presence[:reader.cardinality])))
                    continue
                present_ids = np.nonzero(presence[:reader.cardinality] > 0)[0]
                states.append(agg.state_from_present_ids(reader.dictionary,
                                                         present_ids))
                continue
            o = {"count": count}
            for out_name in agg.device_outputs:
                if out_name != "count":
                    o[out_name] = outs[f"{i}.{out_name}"]
            states.append(agg.state_from_device(o))
        return SegmentResult("scalar", scalar=states, num_docs_scanned=count)

    # -- host fallback aggregation ---------------------------------------
    def _host_aggregate(self, plan: SegmentPlan) -> SegmentResult:
        seg = plan.segment
        mask = host_filter_mask(plan, seg)
        if plan.valid_docs is not None:
            mask = mask & plan.valid_docs[:len(mask)]
        idx = np.nonzero(mask)[0]
        env = _host_env(plan, seg)

        def arg_values(agg: AggFunc) -> np.ndarray:
            if agg.arg is None or (isinstance(agg.arg, Identifier) and agg.arg.name == "*"):
                return np.zeros(len(idx))
            from ..engine.expr import eval_expr
            return np.asarray(eval_expr(agg.arg, env, np))[idx]

        if not plan.group_exprs:
            states = [a.host_state(arg_values(a)) for a in plan.aggs]
            return SegmentResult("scalar", scalar=states, num_docs_scanned=len(idx))

        from ..engine.expr import eval_expr
        key_arrays = [np.asarray(eval_expr(g, env, np))[idx] for g in plan.group_exprs]
        arg_arrays = [arg_values(a) for a in plan.aggs]

        # multi-value group-by: explode each row into one group row per value
        # (reference: MV group key generators emit one key per value combination).
        # Detected on the EVALUATED key arrays so MV->MV transforms (VALUEIN)
        # explode the same way bare MV identifiers do.
        def _is_mv_keys(arr: np.ndarray) -> bool:
            return (arr.dtype == object and len(arr)
                    and isinstance(arr[0], np.ndarray))
        mv_pos = [j for j, arr in enumerate(key_arrays) if _is_mv_keys(arr)]
        if mv_pos:
            from .context import QueryValidationError
            if len(mv_pos) > 1:
                raise QueryValidationError(
                    "GROUP BY supports at most one multi-value expression")
            j = mv_pos[0]
            rows = key_arrays[j]
            counts = np.fromiter((len(r) for r in rows), dtype=np.int64,
                                 count=len(rows))
            rep = np.repeat(np.arange(len(rows)), counts)
            flat = (np.concatenate(list(rows)) if len(rows)
                    else np.empty(0, dtype=object))
            key_arrays = [flat if k == j else arr[rep]
                          for k, arr in enumerate(key_arrays)]
            arg_arrays = [a[rep] for a in arg_arrays]

        # vectorized grouping: factorize each key column, combine into one dense int
        # key, then split row indices per group — the host-side mirror of the device's
        # DictionaryBasedGroupKeyGenerator dense keys (no pandas: its arrow string
        # backend is not thread-safe for object arrays).
        value_dicts = []
        n_rows = len(key_arrays[0]) if key_arrays else len(idx)  # post-explode size
        combined = np.zeros(n_rows, dtype=np.int64)
        stride = 1
        for arr in key_arrays:
            codes, values = _factorize_keys(arr)
            combined += codes * stride
            value_dicts.append(values)
            stride *= max(len(values), 1)
        uniq_keys, inverse = np.unique(combined, return_inverse=True)
        order = np.argsort(inverse, kind="stable")
        bounds = np.zeros(len(uniq_keys) + 1, dtype=np.int64)
        np.cumsum(np.bincount(inverse, minlength=len(uniq_keys)), out=bounds[1:])

        result = SegmentResult("groups", num_docs_scanned=len(idx))
        for g, dense in enumerate(uniq_keys):
            gidx = order[bounds[g]:bounds[g + 1]]
            key = []
            rem = dense
            for j, values in enumerate(value_dicts):
                card = max(len(values), 1)
                v = values[rem % card]
                key.append(v.item() if isinstance(v, np.generic) else v)
                rem //= card
            result.groups[tuple(key)] = [a.host_state(arg_arrays[i][gidx])
                                         for i, a in enumerate(plan.aggs)]
        return result

    # -- selection --------------------------------------------------------
    MAX_DEVICE_TOPK = 65536

    def _selection(self, plan: SegmentPlan) -> SegmentResult:
        ctx, seg = plan.ctx, plan.segment
        topk = self._topk_candidates(plan)
        if topk is not None:
            idx, scanned = topk
        else:
            mask = self._selection_mask(plan)
            if plan.valid_docs is not None:
                mask = mask & plan.valid_docs[:len(mask)]
            idx = np.nonzero(mask)[0]
            if not ctx.order_by:
                idx = idx[:ctx.offset + ctx.limit]  # early terminate (SelectionOnlyOperator)
            scanned = len(idx)

        needed = set()
        for e, _ in ctx.select_items:
            needed.update(identifiers_in(e))
        for o in ctx.order_by:
            needed.update(identifiers_in(o.expr))
        env = {c: seg.column(c).values()[idx] for c in needed}

        from ..engine.expr import eval_expr
        out_cols = [np.asarray(eval_expr(e, env, np)) if not _is_const(e)
                    else np.full(len(idx), eval_expr(e, env, np), dtype=object)
                    for e, _ in ctx.select_items]

        def _cell(v):
            if isinstance(v, np.generic):
                return v.item()
            if isinstance(v, np.ndarray):  # multi-value cell -> python list
                return v.tolist()
            return v
        rows = [tuple(_cell(c[i]) for c in out_cols) for i in range(len(idx))]
        sort_keys = []
        if ctx.order_by:
            sort_cols = [np.asarray(eval_expr(o.expr, env, np)) for o in ctx.order_by]
            sort_keys = [tuple(c[i].item() if isinstance(c[i], np.generic) else c[i]
                               for c in sort_cols) for i in range(len(idx))]
        return SegmentResult("selection", rows=rows, sort_keys=sort_keys,
                             num_docs_scanned=scanned)

    # slack candidates beyond k so f32 ties at the k-boundary cannot evict a true
    # top-k row (final ordering is exact: candidates re-sort on host in f64)
    TOPK_SLACK = 256

    def _topk_candidates(self, plan: SegmentPlan) -> Optional[Tuple[np.ndarray, int]]:
        """(candidate doc ids, match count) from a DEVICE order-by trim, or None.

        Eligible: single plain-column numeric ORDER BY key, bounded LIMIT, immutable
        segment. Integer keys require known bounds within 2^24 (f32-exact); float
        keys ride with TOPK_SLACK overfetch, since only the candidate set — never the
        final order — is decided in f32. Expression keys (e.g. a*b) can overflow f32
        precision without column bounds revealing it, so they stay on the host."""
        ctx, seg = plan.ctx, plan.segment
        k = ctx.offset + ctx.limit
        if (len(ctx.order_by) != 1 or not self.use_device or k <= 0
                or k > self.MAX_DEVICE_TOPK or getattr(seg, "is_mutable", False)):
            return None
        order = ctx.order_by[0]
        if not topk_order_key_device_ok(seg, order.expr):
            return None
        from .planner import _expr_device_ok
        for leaf in plan.filter_prog.leaves:
            if isinstance(leaf, CmpLeaf) and _expr_device_ok(leaf.expr, seg):
                return None  # mask itself needs the host path
        from ..engine import kernels
        from ..engine.datablock import block_for
        block = block_for(seg)
        spec = kernels.KernelSpec(plan.filter_prog, (), 1, (), {}, block.padded,
                                  mv_cols=_mv_lut_cols(plan, seg),
                                  int_ranges=int_ranges(plan, [order.expr]))
        inputs = self._kernel_inputs(plan, spec, block)
        for c in identifiers_in(order.expr):
            if c not in inputs.vals:
                inputs.vals[c] = block.values(c)
        idx, count, ok = kernels.compute_topk(spec, inputs, order.expr, order.desc,
                                              k + self.TOPK_SLACK)
        keep = min(k + self.TOPK_SLACK, count)
        idx, ok = idx[:keep], ok[:keep]
        idx = idx[ok & (idx < seg.num_docs)]
        if len(idx) < min(k, count):
            return None  # -inf/NaN keys displaced matches; exact host path decides
        return idx, count

    def _selection_mask(self, plan: SegmentPlan) -> np.ndarray:
        seg = plan.segment
        if plan.filter_prog.is_match_all:
            return np.ones(seg.num_docs, dtype=bool)
        use_device = self.use_device and not getattr(seg, "is_mutable", False)
        if use_device:
            from .planner import _expr_device_ok
            for leaf in plan.filter_prog.leaves:
                if isinstance(leaf, CmpLeaf) and _expr_device_ok(leaf.expr, seg):
                    use_device = False
                    break
        if use_device:
            from ..engine import kernels
            from ..engine.datablock import block_for
            block = block_for(seg)
            plan.bitmap_leaves = self._bitmap_leaves(plan, seg)
            spec = kernels.KernelSpec(plan.filter_prog, (), 1, (), {}, block.padded,
                                      mv_cols=_mv_lut_cols(plan, seg),
                                      bitmap_leaves=plan.bitmap_leaves,
                                      int_ranges=int_ranges(plan))
            inputs = self._kernel_inputs(plan, spec, block)
            return kernels.compute_mask(spec, inputs)[:seg.num_docs]
        return host_filter_mask(plan, seg)


def _plan_vals_cols(plan: SegmentPlan) -> set:
    """Columns the kernel reads as *values* (not dict ids): filter compare
    expressions plus non-distinct aggregate arguments. Mirrors the
    vals_cols set `_kernel_inputs` builds — fused eligibility is decided
    over exactly these columns."""
    cols = set()
    for leaf in plan.filter_prog.leaves:
        if isinstance(leaf, CmpLeaf):
            cols.update(identifiers_in(leaf.expr))
    for agg in plan.aggs:
        if "distinct" in agg.device_outputs:
            continue
        if agg.arg is not None and not (isinstance(agg.arg, Identifier)
                                        and agg.arg.name == "*"):
            cols.update(identifiers_in(agg.arg))
    return cols


def _mv_lut_cols(plan: SegmentPlan, seg: ImmutableSegment) -> Tuple[str, ...]:
    """LUT-leaf columns that are multi-value in this segment (KernelSpec.mv_cols)."""
    cols = set()
    for leaf in plan.filter_prog.leaves:
        if isinstance(leaf, LutLeaf) and \
                getattr(seg.column(leaf.col), "is_multi_value", False):
            cols.add(leaf.col)
    return tuple(sorted(cols))


def host_filter_mask(plan: SegmentPlan, seg: ImmutableSegment) -> np.ndarray:
    """Evaluate the compiled filter program with numpy on the host — same LUT semantics as
    the device path, so host and device paths agree by construction."""
    from ..engine.expr import eval_expr

    prog = plan.filter_prog
    n = seg.num_docs
    if prog is None or prog.is_match_all:
        return np.ones(n, dtype=bool)
    env = _host_env(plan, seg)

    def leaf_mask(i: int) -> np.ndarray:
        leaf = prog.leaves[i]
        if isinstance(leaf, LutLeaf):
            reader = seg.column(leaf.col)
            # Mutable (consuming) readers: take ONE dict_snapshot and bind the
            # LUT, the inverted-index view, AND the forward ids to it. Dict
            # ids REMAP as the sorted dictionary grows, so the compile-time
            # LUT paired with a fresh index/fwd read (or vice versa) evaluates
            # the predicate in two different id spaces — the same
            # mixed-growth hazard the immutable reader never has. The LUT is
            # rebuilt from the leaf's source predicate against the snapshot
            # dictionary (LutLeaf.rebuild_lut).
            snap_fn = getattr(reader, "dict_snapshot", None)
            snap = snap_fn() if snap_fn is not None else None
            if snap is not None and snap[1] is None:  # no-dict reader sentinel
                snap = None
            lut = leaf.lut
            if snap is not None and snap[1] is not None and leaf.op is not None:
                lut = leaf.rebuild_lut(snap[1], len(snap[1]))
            if snap is not None:
                iv = getattr(reader, "inverted_view", None)
                inv = iv(snap) if iv is not None else None
            else:
                inv = getattr(reader, "inverted_index", None)
            if inv is not None:
                # index-aware path (reference: BitmapBasedFilterOperator;
                # realtime segments serve it from the incrementally-maintained
                # RealtimeInvertedIndex view): selective predicates
                # materialize the doc set from postings — O(matches) instead
                # of the O(docs) forward gather; dense predicates keep the
                # gather, which is cheaper than concatenating huge postings
                card = min(inv.cardinality, len(lut))
                match_ids = np.nonzero(lut[:card])[0]
                if inv.match_count_for_ids(match_ids) * 8 <= n:
                    mask = np.zeros(n, dtype=bool)
                    docs = inv.doc_ids_for_ids(match_ids)
                    mask[docs[docs < n]] = True
                    return mask
            if getattr(reader, "is_multi_value", False):
                # ANY-value-matches per row (MVScanDocIdIterator semantics); every
                # row has >= 1 value (writer stores [null] for empty), so reduceat
                # over the CSR offsets is well-defined
                if snap is not None:
                    _, _, flat, off = snap
                else:
                    flat = np.asarray(reader.fwd).astype(np.int64)
                    off = np.asarray(reader.mv_offsets)
                if not len(flat):
                    return np.zeros(n, dtype=bool)
                hits = lut[np.asarray(flat).astype(np.int64)].astype(np.int32)
                m = np.add.reduceat(hits, np.asarray(off)[:-1]) > 0
                if len(m) < n:  # snapshot older than the captured row count
                    m = np.pad(m, (0, n - len(m)), constant_values=False)
                return m[:n]
            if snap is not None:
                ids = np.asarray(snap[2]).astype(np.int64)
                m = lut[ids]
                if len(m) < n:
                    m = np.pad(m, (0, n - len(m)), constant_values=False)
                return m[:n]
            ids = np.asarray(reader.fwd).astype(np.int64)
            return lut[ids]
        if isinstance(leaf, NullLeaf):
            nb = seg.column(leaf.col).null_bitmap
            m = nb if nb is not None else np.zeros(n, dtype=bool)
            return ~m if leaf.negated else m
        if isinstance(leaf, DocSetLeaf):
            return leaf.mask[:n]
        assert isinstance(leaf, CmpLeaf)
        v = np.asarray(eval_expr(leaf.expr, env, np))
        ops = leaf.operands
        if leaf.op == "eq":
            return v == ops[0]
        if leaf.op == "gte":
            return v >= ops[0]
        if leaf.op == "lte":
            return v <= ops[0]
        if leaf.op == "gt":
            return v > ops[0]
        if leaf.op == "lt":
            return v < ops[0]
        if leaf.op == "between":
            return (v >= ops[0]) & (v <= ops[1])
        m = v == ops[0]
        for o in ops[1:]:
            m = m | (v == o)
        return m

    def walk_tree(node) -> np.ndarray:
        kind = node[0]
        if kind == "const":
            return np.full(n, node[1], dtype=bool)
        if kind == "leaf":
            return leaf_mask(node[1])
        if kind == "not":
            return ~walk_tree(node[1])
        masks = [walk_tree(c) for c in node[1]]
        out = masks[0]
        for m in masks[1:]:
            out = (out & m) if kind == "and" else (out | m)
        return out

    return walk_tree(prog.tree)


def _host_env(plan: SegmentPlan, seg: ImmutableSegment) -> Dict[str, np.ndarray]:
    """Decoded column environment for host-side expression evaluation."""
    needed = set()
    for g in plan.group_exprs:
        needed.update(identifiers_in(g))
    for a in plan.aggs:
        if a.arg is not None:
            needed.update(identifiers_in(a.arg))
    if plan.filter_prog:
        for leaf in plan.filter_prog.leaves:
            if isinstance(leaf, CmpLeaf):
                needed.update(identifiers_in(leaf.expr))
    return {c: seg.column(c).values() for c in needed}


def topk_order_key_device_ok(seg, order_expr) -> bool:
    """True when `order_expr` is a device-sortable ORDER BY key on `seg`.

    Requires a plain single-value column (expression keys like a*b can
    overflow f32 precision without column bounds revealing it) that the
    device can evaluate; integer keys additionally need known min/max within
    2^24 so the f32 candidate pass cannot misorder them. Shared by the
    per-segment `_topk_candidates` and the served mesh top-k
    (`parallel.combine._prepare_topk`), so serving and library paths agree
    on eligibility."""
    if not isinstance(order_expr, Identifier):
        return False
    from .planner import _expr_device_ok
    if _expr_device_ok(order_expr, seg):
        return False
    reader = seg.column(order_expr.name)
    if getattr(reader, "is_multi_value", False):
        return False
    if reader.data_type.numpy_dtype.kind in "iu":
        mn, mx = reader.min_value, reader.max_value
        if mn is None or mx is None or \
                max(abs(float(mn)), abs(float(mx))) >= (1 << 24):
            return False  # f32 would misorder wide integers
    return True


def group_trim_spec(ctx: QueryContext, plan: SegmentPlan):
    """(agg index or None-for-count, desc, k) when a group-by ORDER BY can be trimmed
    to its top-k groups from device outputs alone; None otherwise.

    Safe only against GLOBAL (fully combined) partials: per-segment partial sums can
    rank groups differently than their cross-segment totals. Requires: single ORDER BY
    key that IS one of the query's aggregations, no HAVING (it could resurrect
    trimmed groups), no DISTINCT rewrite."""
    if (ctx.having is not None or ctx.distinct or len(ctx.order_by) != 1
            or ctx.gapfill is not None):  # gapfill fabricates rows for trimmed groups
        return None
    k = ctx.offset + ctx.limit
    if k <= 0 or k > ServerQueryExecutor.MAX_DEVICE_TOPK:
        return None
    o = ctx.order_by[0]
    for i, fn_expr in enumerate(ctx.aggregations):
        if repr(o.expr) == repr(fn_expr):
            outs = plan.aggs[i].device_outputs
            if outs in (("count",), ("sum",), ("min",), ("max",), ("sum", "count")):
                return (i, o.desc, k)
    return None


def sort_trim_spec(ctx: QueryContext, plan: SegmentPlan,
                   devices: int = 1) -> Tuple:
    """`KernelSpec.trim` of a sort-regime GROUP BY whose partial is the whole
    answer, on a mesh of `devices`: (k, ((source, desc), ...)) with k =
    OFFSET + LIMIT, a source ("key", j) for group column j or ("out", name)
    for the output that orders an aggregate as the broker does (COUNT, SUM,
    MIN, MAX); () where the cut cannot be made on the device and the partial
    stays untrimmed and exact: HAVING (it could keep a group the cut
    dropped), DISTINCT, gapfill, NULLS FIRST/LAST, an ORDER BY item that is
    neither, or k out of (0, MAX_DEVICE_TOPK]. Several keys, aggregates and
    group columns are fine.

    The sorted groups past `dense_keys` (`plan.sparse`) are cut so wherever
    the rule holds. The sort regime between `chunk_cap` and `dense_keys`
    keys is answered from its sorted groups and cut (`kernels.
    _grouped_sparse`, `every_row`) on one device, with no DISTINCT
    aggregate, and where k is at most `kernels.PICK_REDUCE_MAX`: the k groups
    are then moved by a masked reduce over the sorted rows, and past it by a
    scatter of every sorted row (67M at SF10), which costs the device more
    than the fetch of the table it saves (at most 2^21 keys, 8 MB a value
    row). A mesh of several devices adds the chips' tables after the
    kernel: a cut inside it would be one chip's."""
    from ..engine.caps import get_caps
    from ..engine.kernels import PICK_REDUCE_MAX
    if plan.group_cols:
        build_device_geometry(plan)     # the padded key count
    dense = (bool(plan.group_cols) and not plan.sparse
             and plan.num_keys_pad + 1 > get_caps().chunk_cap)
    if not plan.sparse and not (dense and devices == 1 and not any(
            "distinct" in a.device_outputs for a in plan.aggs)):
        return ()
    if (ctx.having is not None or ctx.distinct or ctx.gapfill is not None
            or not ctx.order_by or len(ctx.group_by) != len(plan.group_cols)):
        return ()
    k = ctx.offset + ctx.limit
    if k <= 0 or k > (ServerQueryExecutor.MAX_DEVICE_TOPK if plan.sparse
                      else PICK_REDUCE_MAX):
        return ()
    groups = {repr(g): j for j, g in enumerate(ctx.group_by)}
    calls = {repr(c): i for i, c in enumerate(ctx.aggregations)}
    order = []
    for o in ctx.order_by:
        r = repr(o.expr)
        if o.nulls_last is not None:
            return ()
        if r in groups:
            order.append((("key", groups[r]), o.desc))
            continue
        outs = plan.aggs[calls[r]].device_outputs if r in calls else ()
        if outs == ("count",):
            order.append((("out", "count"), o.desc))
        elif outs in (("sum",), ("min",), ("max",)):
            order.append((("out", f"{calls[r]}.{outs[0]}"), o.desc))
        else:
            return ()
    return (k, tuple(order))


def _trim_occupied(plan: SegmentPlan, outs, occupied: np.ndarray) -> np.ndarray:
    """Exact top-k subset of occupied dense keys by the ORDER BY aggregation."""
    trim = group_trim_spec(plan.ctx, plan)
    if trim is None or len(occupied) <= trim[2]:
        return occupied
    i, desc, k = trim
    outs_names = plan.aggs[i].device_outputs
    if outs_names == ("count",):
        score = outs["count"][:plan.num_keys_real][occupied].astype(np.float64)
    elif outs_names == ("sum", "count"):  # AVG
        s = outs[f"{i}.sum"][:plan.num_keys_real][occupied].astype(np.float64)
        c = outs["count"][:plan.num_keys_real][occupied].astype(np.float64)
        score = s / np.maximum(c, 1)
    else:
        score = np.asarray(outs[f"{i}.{outs_names[0]}"][:plan.num_keys_real][occupied],
                           dtype=np.float64)
    top = np.argpartition(-score if desc else score, k - 1)[:k]
    return occupied[top]


def _factorize_keys(arr: np.ndarray):
    """Null-aware dense codes for host group-by keys.

    SQL groups all nulls (None in object arrays, NaN in float arrays — e.g. a
    LOOKUP miss, `LookupTransformFunction.java:65` semantics) into ONE group whose
    key surfaces as None; np.unique alone cannot sort None against str. Returns
    (codes, values) where nulls get the trailing code len(values)-1 -> None."""
    n = len(arr)
    if arr.dtype == object:
        isnull = np.fromiter((v is None for v in arr), dtype=bool, count=n)
        if isnull.any():
            fill = next((v for v in arr if v is not None), "")
            tmp = arr.copy()
            tmp[isnull] = fill
        else:
            tmp = arr
        uniq, inv = np.unique(tmp, return_inverse=True)
    elif arr.dtype.kind == "f":
        isnull = np.isnan(arr)
        uniq, inv = np.unique(np.where(isnull, 0.0, arr), return_inverse=True)
    else:
        isnull = np.zeros(n, dtype=bool)
        uniq, inv = np.unique(arr, return_inverse=True)
    codes = inv.astype(np.int64).reshape(n)
    values = list(uniq)
    if isnull.any():
        codes[isnull] = len(values)
        values.append(None)
    return codes, values


def _is_const(e: Expr) -> bool:
    return not identifiers_in(e)


def execute_query(segments: Sequence[ImmutableSegment], sql: str,
                  schema=None, use_device: bool = True) -> ResultTable:
    """One-call convenience: SQL over loaded segments (the BaseQueriesTest harness shape)."""
    return ServerQueryExecutor(use_device).execute(segments, sql, schema)
