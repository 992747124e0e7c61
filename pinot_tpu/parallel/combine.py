"""Sharded multi-segment execution: scatter segments over a mesh, psum-combine partials.

The TPU-native analog of the reference's entire distributed query data plane for
aggregations (SURVEY.md §2.11): where the reference scatters segments to servers over
Netty (`QueryRouter.submitQuery`), runs per-segment operator trees on thread pools
(`BaseCombineOperator`), and merges DataTables on the broker
(`GroupByDataTableReducer`), here the segment axis IS a mesh axis:

    stacked columns [S, P] --shard_map--> per-device fused scan --psum/pmin/pmax--> result

Dense group keys and LUT ids must agree across devices so partial aggregates combine
with one ICI collective and no host-side value merge. Two ways a segment set qualifies:

* *aligned dictionaries* (`dictHash` equal — built via `segment.writer.
  build_aligned_segments` or a shared ingestion dictionary): ids already agree;
* anything else — including consuming (mutable) segments — rides the **merged-
  dictionary path** (`parallel/merged.py`): a global sorted dictionary per referenced
  column, per-segment ids remapped host-side once at block-build time, after which the
  set is aligned by construction.

What is STAGED and PLANNED is the set a server holds of a table (its RESIDENT
set): one `SegmentSetBlock`, one merged view, one plan a query shape. The
segments a query was ROUTED to (the broker prunes by time, range, partition)
are a runtime input of the launch: a per-slot mask ANDed into `valid` and, on
a mesh of one device, a window of slots of static length (a ladder of three
significant bits) whose start is a runtime scalar (`_route_window`), so a
pruned query reads the rows of its window and builds no block, no view and no
dictionary shape of its own.

JSON_MATCH/TEXT_MATCH/geo doc-set bitmaps stack [S, rows] into the kernel's
`docsets` input (cached per predicate on the block), and multi-value LUT filter
columns stack as [S, rows, W] padded id matrices — both on the ALIGNED immutable
path; unaligned or mutable sets with those shapes keep the per-segment fallback.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Any
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.datablock import lut_size, padded_rows
from ..engine.kernels import (KernelSpec, _fence_first_call, dense_trimmed,
                              gather_free, masked, slabbed, sorted_minmax,
                              sparse, tree_bytes, trimmed, widened)
from ..query import stats as qstats
from ..query.aggregates import make_agg
from ..query.context import SOLE_SERVER, QueryContext, compile_query
from ..query.executor import ServerQueryExecutor, sort_trim_spec
from ..query.planner import (build_device_geometry, int_ranges,
                             plan_segment)
from ..query.predicate import CmpLeaf, LutLeaf, NullLeaf
from ..query.reduce import merge_segment_results, reduce_to_result
from ..query.result import ResultTable
from ..segment.reader import ImmutableSegment
from ..sql.ast import Expr, Function, Identifier, identifiers_in
from ..utils.metrics import get_registry
from ..utils.trace import stage
from .merged import MergedSegmentView, set_facts, view_key
from .mesh import (SEGMENT_AXIS, default_mesh, pad_slots, placement_slots,
                   skew_pct)


def _has_docset_filter(ctx: QueryContext) -> bool:
    """JSON_MATCH/TEXT_MATCH resolve to per-segment doc bitmaps (DocSetLeaf):
    on the ALIGNED immutable path they stack into the mesh kernel's `docsets`
    input (_stacked_docsets); unaligned/mutable sets keep the fallback."""
    def walk(e) -> bool:
        if isinstance(e, Function):
            if e.name in ("json_match", "text_match"):
                return True
            return any(walk(a) for a in e.args)
        return False
    return ctx.filter is not None and walk(ctx.filter)

_SHARD_KERNEL_CACHE: Dict[Tuple, object] = {}

# what a sparse launch's decode returns where more rows passed than the launch
# could hold (`kernels.sparse_cap`): the host answers that query
_HOST_ANSWERS = object()

# Dense grouped outputs at or above this key count combine with a reduce-
# scatter (`psum_scatter`, each device keeping 1/n of the key space) instead
# of a full psum: the all-gather half of the psum is pure waste when the host
# fetch reassembles the shards anyway, so the collective moves half the bytes.
# Below it the savings don't cover the sharded-layout bookkeeping.
SCATTER_MIN_KEYS = 4096


def device_topk_screen(ctx: QueryContext) -> bool:
    """Cheap handler-thread pre-screen: could this SELECTION ride the device
    top-k path? (single plain-column ORDER BY, bounded LIMIT). The full
    eligibility check (numeric dtype, int bounds, dictionary alignment,
    device-safe filter) runs in `prepare_partial`; a miss there resolves to
    the host fallback. Without this screen every orderless selection would
    wait out the pipeline's batch window just to learn it must fall back."""
    k = ctx.offset + ctx.limit
    return (len(ctx.order_by) == 1
            and isinstance(ctx.order_by[0].expr, Identifier)
            and 0 < k <= ServerQueryExecutor.MAX_DEVICE_TOPK)


@dataclass
class PreparedDispatch:
    """A planned-but-not-launched device dispatch (pipeline tentpole unit).

    The pipeline groups prepared items before launching: items with equal
    `dedupe_key` are byte-identical dispatches (same executable, same runtime
    operands) and share ONE kernel launch + ONE fetched result; items with
    equal `stack_key` (same `KernelSpec.signature()` executable over the same
    block, differing only in runtime scalars) stack into ONE batched kernel
    launch (`lax.scan` over the stacked scalar streams) instead of N
    sequential dispatches."""

    kind: str                    # "agg" | "topk"
    spec: Any                    # KernelSpec ("agg") or static key tuple ("topk")
    inputs: dict
    s_pad: int
    rows: int
    stack_key: Tuple             # same traced executable + same device operands
    dedupe_key: Optional[Tuple]  # fully identical dispatch (None = never dedupe)
    stackable: bool
    decode: Any                  # decode(host outs dict) -> partial | DEVICE_FALLBACK
    iscal_np: Optional[np.ndarray] = None  # host scalar streams (stacking)
    fscal_np: Optional[np.ndarray] = None
    trim_keys: Tuple[int, int] = (0, 0)  # (num_keys_pad, num_keys_real) device trim
    launch: Any = None           # "topk": () -> outs_dev (pre-bound kernel)
    window: int = 0              # slots the program reads; 0 = no routing input
    slot_stats: Optional[dict] = None  # routed/resident/scanned slots, merged


def _record_fused(p: PreparedDispatch) -> None:
    """Count one launch of a spec that decodes compressed forms in-kernel, and
    whether it does so with no gather (the table widths are shapes of its
    staged inputs)."""
    if p.spec.fused_cols:
        qstats.record(qstats.FUSED_LAUNCHES)
        if gather_free(p.spec, p.inputs["vals"]):
            qstats.record(qstats.GATHER_FREE_LAUNCHES)


class DocsetPlanDivergence(Exception):
    """Segments in one set compile to different doc-set leaf structures (e.g.
    a geo index present on some segments only): the stacked mesh dispatch
    cannot serve them — callers fall back to per-segment execution."""


def _refs_multi_value(ctx: QueryContext, seg) -> bool:
    """True when any column the query touches is multi-value."""
    from ..sql.ast import identifiers_in
    names = set()
    if ctx.filter is not None:
        names.update(identifiers_in(ctx.filter))
    for e in ctx.group_by:
        names.update(identifiers_in(e))
    for f in ctx.aggregations:
        names.update(identifiers_in(f))
    for e, _ in ctx.select_items:
        names.update(identifiers_in(e))
    for name in names:
        try:
            if getattr(seg.column(name), "is_multi_value", False):
                return True
        except KeyError:
            continue  # '*' / alias — not a physical column
    return False


# below this many combined star-tree records, the per-segment host loop beats
# any device dispatch (host round trip >> microseconds of numpy); above it the
# stacked device star path wins (high-cardinality split dimensions)
STAR_DEVICE_MIN_RECORDS = 1 << 16


@dataclass
class StarSetPlan:
    """Stacked device star-tree execution: one slot plan over every segment's
    record-table view + the per-segment traversal masks."""
    plans: list       # per-segment StarTreePlan (masks + reassembly)
    views: list       # per-segment StarTreeView (the stacked mini-segments)
    plan2: Any        # device SegmentPlan of the slot query over views[0]
    kind = "star"


def aligned_dictionaries(segments: Sequence[ImmutableSegment], cols: Sequence[str]) -> bool:
    """True iff every column in `cols` has identical dictionaries across segments."""
    for col in cols:
        hashes = set()
        for seg in segments:
            reader = seg.column(col)
            if not reader.has_dictionary:
                return False
            h = reader.meta.get("dictHash")
            if h is None:
                return False
            hashes.add((h, reader.cardinality))
        if len(hashes) > 1:
            return False
    return True


def _path(segment) -> str:
    """A member's identity in the set caches: its directory, or its name."""
    return getattr(segment, "path", segment.name)


def _route_window(slots: Sequence[int], s_pad: int, n_devices: int):
    """(window, start): the slots a launch routed to `slots` reads. On a mesh
    of one device the run of slots that covers them, its length rounded up to
    three significant bits (1 .. 8, 10, 12, 14, 16, 20, ...): a ladder of
    four static lengths an octave, so a query shape owns a bounded set of
    programs, and a launch reads less than a quarter more slots than the run
    it was routed (nothing more up to 8; 14 of 16 read 14, 15 read 16).
    Time-pruned subsets are contiguous in push order; a subset that is not
    takes the window that covers it. The window is placed so that it ends
    inside the block. Where the slot axis is sharded every device reads
    its slots and the routed ones are a mask alone (ROADMAP S13)."""
    lo, hi = min(slots), max(slots)
    if n_devices > 1:
        return s_pad, 0
    span = hi - lo + 1
    step = 1 << max(span.bit_length() - 3, 0)
    window = min(-(-span // step) * step, s_pad)
    return window, min(lo, s_pad - window)


class SegmentSetBlock:
    """Stacked device columns of one segment set: [S_pad, P] arrays.

    The set is what a server holds of a table (its resident set), staged once:
    `MeshQueryExecutor._set_blocks` keys a block by the set's segment paths
    (and whether it is in a merged id space), with the members' `view_key` and
    `s_pad` as the value's subkey, so a set that changes (a segment added,
    replaced or dropped, a consuming member grown) restages and its
    predecessor is dropped. The segments a query is routed to never key a
    block: they are `routed_slots()`, a per-slot mask of the launch.

    Arrays are `device_put` once with their final mesh sharding (segment axis sharded,
    decode tables replicated) so repeated queries dispatch with zero re-shard copies —
    the analog of the reference's segment-resident mmap buffers being scan-ready.
    What is put is counted (`setBlocksStaged`, `setBlockBytes`) and timed
    under a `pinot:mesh.stage` span.
    """

    def __init__(self, segments: Sequence[ImmutableSegment], s_pad: int,
                 mesh: jax.sharding.Mesh, view=None):
        self.segments = list(segments)
        self.s_pad = s_pad
        self.view = view  # MergedSegmentView for unaligned sets, else None
        self.seg_docs = view.seg_docs if view is not None \
            else tuple(s.num_docs for s in segments)
        self.rows = max(padded_rows(n) for n in self.seg_docs)
        self.n_devices = mesh.devices.size
        # chip-aware placement (mesh.placement_slots): slots[i] is segment i's
        # row in the stacked block. Aligned immutable sets reorder freely; a
        # merged view keeps identity order — its remap tables and mutable
        # snapshots are rebuilt per growth step, so the conservative identity
        # placement keeps block reuse simple there.
        if self.n_devices > 1 and view is None:
            self.slots, self.device_loads = placement_slots(
                self.seg_docs, s_pad, self.n_devices)
        else:
            self.slots = list(range(len(segments)))
            k = max(s_pad // max(self.n_devices, 1), 1)
            loads = [0] * self.n_devices
            for i, d in enumerate(self.seg_docs):
                loads[i // k] += int(d)
            self.device_loads = loads
        self.slot_to_seg = np.full(s_pad, -1, dtype=np.int64)
        for i, sl in enumerate(self.slots):
            self.slot_to_seg[sl] = i
        self.skew_pct = skew_pct(self.device_loads)
        cells = s_pad * self.rows
        self.pad_waste_pct = \
            (1.0 - sum(self.seg_docs) / cells) * 100.0 if cells else 0.0
        P = jax.sharding.PartitionSpec
        self._sharded = jax.sharding.NamedSharding(mesh, P(SEGMENT_AXIS))
        self._replicated = jax.sharding.NamedSharding(mesh, P())
        self._cache: Dict[Tuple[str, str], jnp.ndarray] = {}
        self.slot_of = {_path(seg): sl
                        for seg, sl in zip(self.segments, self.slots)}
        qstats.record(qstats.SET_BLOCKS_STAGED)

    def _put(self, key: Tuple[str, str], build) -> jnp.ndarray:
        """The block's array `key`, built on the host by `build()` and put on
        the mesh (segment axis sharded) the first time it is asked for."""
        if key not in self._cache:
            with stage("mesh.stage", cols=1) as st:
                host = build()
                self._cache[key] = jax.device_put(host, self._sharded)
                st.note(bytes=int(host.nbytes))
            qstats.record(qstats.SET_BLOCK_BYTES, int(host.nbytes))
        return self._cache[key]

    def routed_slots(self, segments) -> Tuple[int, ...]:
        """The slots of the members a query was routed to, in slot order."""
        return tuple(sorted(self.slot_of[_path(s)]
                            for s in segments))

    def _stack(self, kind: str, col: str, fill, per_seg) -> jnp.ndarray:
        def build():
            first = np.asarray(per_seg(0, self.segments[0]))
            # 1-D per-segment arrays stack to [S, rows]; 2-D (padded MV id
            # matrices [rows, W]) stack to [S, rows, W]
            shape = (self.s_pad, self.rows) + first.shape[1:]
            out = np.full(shape, fill, dtype=first.dtype)
            for i, seg in enumerate(self.segments):
                # slice to the view's snapshot row count: mutable members may have
                # grown since the view (and its remap tables) were built
                arr = np.asarray(per_seg(i, seg))[:self.seg_docs[i]]
                out[self.slots[i], :len(arr)] = arr
            return out
        return self._put((kind, col), build)

    def ids(self, col: str) -> jnp.ndarray:
        """Dict ids in the space the plan was made in: segment-local ids for aligned
        sets, remapped GLOBAL ids (merged.py) for unaligned ones. Multi-value
        columns stack as [S, rows, W] left-justified id matrices (W = the
        set-wide max values per row), out-of-dictionary fill = cardinality —
        exactly the single-device MV layout with a segment axis in front."""
        remaps = self.view.remap(col) if self.view is not None else None
        if remaps is None:
            r0 = self.segments[0].column(col)
            card = r0.cardinality
            if getattr(r0, "is_multi_value", False):
                w = max(max(s.column(col).max_num_values, 1)
                        for s in self.segments)

                def per_seg_mv(i, s):
                    reader = s.column(col)
                    flat = np.asarray(reader.fwd).astype(np.int32)
                    off = np.asarray(reader.mv_offsets)
                    counts = np.diff(off)
                    n = len(counts)
                    mat = np.full((n, w), card, dtype=np.int32)
                    rows = np.repeat(np.arange(n), counts)
                    within = np.arange(len(flat)) - np.repeat(off[:-1], counts)
                    mat[rows, within] = flat
                    return mat
                return self._stack("ids", col, np.int32(card), per_seg_mv)
            return self._stack("ids", col, np.int32(card),
                               lambda i, s: np.asarray(s.column(col).fwd).astype(np.int32))
        mc = self.view.column(col)
        return self._stack("ids", col, np.int32(mc.cardinality),
                           lambda i, s: mc.local_ids(i).astype(np.int32)
                           if remaps[i] is None
                           else remaps[i][mc.local_ids(i)])

    def raw(self, col: str) -> jnp.ndarray:
        from ..engine.datablock import _narrow
        return self._stack("raw", col, 0,
                           lambda i, s: _narrow(np.asarray(s.column(col).fwd)))

    def decoded(self, col: str) -> jnp.ndarray:
        """Decoded numeric values regardless of encoding, host-materialized ONCE.

        This staged form reads pre-decoded HBM columns with no device gather
        (the `DataFetcher.java:47` value-buffer analog). Decode uses each
        segment's OWN dictionary, so it is alignment-independent."""
        from ..engine.datablock import _narrow

        def per_seg(i, s):
            reader = s.column(col)
            arr = np.asarray(reader.fwd)
            if reader.has_dictionary:
                vals = _narrow(np.asarray(reader.dictionary.values))
                return vals[arr.astype(np.int64)]
            return _narrow(arr)

        return self._stack("decoded", col, 0, per_seg)

    def dict_luts(self, col: str) -> jnp.ndarray:
        """Per-segment padded decode tables stacked [S_pad, Lmax], sharded on
        the segment axis like every other block array.

        Row i is segment i's OWN dictionary zero-padded to the set-wide max
        lut_size, so the fused kernel (`kernels._fused_env`) decodes
        segment-local ids itself: by selects fused into the scan where Lmax
        is at most `kernels.SELECT_DECODE_CAP` (the decoded [S_pad, rows]
        column never exists in HBM), by a `take_along_axis` gather where it
        is wider — which the v5e runs as a pass of its own that DOES write
        the decoded column (PR 26's traces), saving only its residency.
        Aligned sets only: merged views remap ids into the global dictionary
        space, which a per-segment LUT stack cannot decode."""
        def build():
            from ..engine.datablock import _narrow, lut_size
            tables = []
            for s in self.segments:
                reader = s.column(col)
                vals = _narrow(np.asarray(reader.dictionary.values))
                t = np.zeros(lut_size(reader.cardinality), dtype=vals.dtype)
                t[:len(vals)] = vals
                tables.append(t)
            lmax = max(len(t) for t in tables)
            out = np.zeros((self.s_pad, lmax),
                           dtype=np.result_type(*[t.dtype for t in tables]))
            for i, t in enumerate(tables):
                out[self.slots[i], :len(t)] = t
            return out
        return self._put(("dictlut", col), build)

    def null_mask(self, col: str) -> jnp.ndarray:
        def per_seg(i, s):
            nb = s.column(col).null_bitmap
            return nb if nb is not None else np.zeros(s.num_docs, dtype=bool)
        return self._stack("null", col, False, per_seg)

    @property
    def valid(self) -> jnp.ndarray:
        def per_seg(i, s):
            return np.ones(s.num_docs, dtype=bool)
        return self._stack("valid", "", False, per_seg)


def _pack_kernel(meta: Tuple, trim_keys: Tuple[int, int], batched: bool):
    """Cached jit of `MeshQueryExecutor._pack`'s device-side concatenation for
    one output layout `meta` = sorted (name, shape, dtype str) triples."""
    key = ("pack", meta, trim_keys, batched)
    fn = _SHARD_KERNEL_CACHE.get(key)
    if fn is None:
        pad, real = trim_keys

        def pack_impl(outs):
            by_dt: Dict[str, list] = {}
            with jax.named_scope("pinot.pack"):
                for name, shape, dts in meta:
                    v = outs[name]
                    core = shape[1:] if batched else shape
                    if pad and real < pad and core \
                            and core[0] in (pad, pad + 1):
                        v = v[:, :real] if batched else v[:real]
                    flat = v.reshape((v.shape[0], -1)) if batched \
                        else v.reshape(-1)
                    by_dt.setdefault(dts, []).append(flat)
                return {dt: (jnp.concatenate(parts, axis=-1)
                             if len(parts) > 1 else parts[0])
                        for dt, parts in by_dt.items()}
        pack_impl.__name__ = "pinot_pack"
        fn = jax.jit(pack_impl)
        _SHARD_KERNEL_CACHE[key] = fn
    return fn


def _drop_superseded(cache: dict, new_key: Tuple) -> None:
    """Drop from `_set_blocks` / `_views` (both keyed (segment paths, in a
    merged id space)) every other entry of the same id space that holds one
    of `new_key`'s paths: a set that grew, shrank or had a member replaced
    leaves its predecessor's device arrays to the garbage collector (launches
    in flight hold their own references). What stays is one entry a resident
    set and id space, so neither cache needs a bound of its own."""
    paths, merged = set(new_key[0]), new_key[1]
    for key in [k for k in cache if k != new_key and k[1] == merged]:
        if paths.intersection(key[0]):
            del cache[key]


class MeshQueryExecutor:
    """Executes aggregation queries over segment sets sharded across a device mesh."""

    def __init__(self, mesh: Optional[jax.sharding.Mesh] = None,
                 fused_enabled: bool = True):
        self.mesh = mesh if mesh is not None else default_mesh()
        self.n_devices = self.mesh.devices.size
        # fused in-register dict decode over the stacked block; no
        # production caller passes False (tests' decoded-column reference)
        self.fused_enabled = fused_enabled
        self._fallback = ServerQueryExecutor(fused_enabled=fused_enabled)
        self._set_blocks: Dict[Tuple, SegmentSetBlock] = {}
        self._views: Dict[Tuple, MergedSegmentView] = {}
        self._replicated = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
        # content-addressed cache of replicated query constants (LUTs, scalars, strides):
        # repeated queries dispatch with zero host->device transfers
        self._const_cache: Dict[bytes, jnp.ndarray] = {}

    def _const(self, arr: np.ndarray) -> jnp.ndarray:
        # shape is part of identity: equal bytes at different shapes (e.g.
        # an empty [0] scalar stream vs its stacked [B, 0] form) are
        # different device constants
        key = arr.dtype.str.encode() + repr(arr.shape).encode() + arr.tobytes()
        dev = self._const_cache.get(key)
        if dev is None:
            if len(self._const_cache) > 4096:
                self._const_cache.clear()
            dev = jax.device_put(arr, self._replicated)
            self._const_cache[key] = dev
        return dev

    # ------------------------------------------------------------------
    def execute(self, segments: Sequence[ImmutableSegment],
                query: Union[str, QueryContext], schema=None) -> ResultTable:
        ctx = compile_query(query, schema or segments[0].schema) \
            if isinstance(query, str) else query
        plan, view = self._plan_for_set(ctx, segments)
        if isinstance(plan, StarSetPlan):
            outs_dev, decode = self._dispatch_star(ctx, plan)
            return decode(jax.device_get(outs_dev))
        if plan is None or plan.kind != "device" or not self._fits(plan):
            return self._fallback.execute(segments, ctx)
        try:
            return self._execute_sharded(ctx, plan, segments, view)
        except DocsetPlanDivergence:
            return self._fallback.execute(segments, ctx)

    def _plan_for_set(self, ctx: QueryContext, segments, routed=None):
        """Choose the planning surface for a segment set.

        Returns (plan, view): view is None for the aligned fast path (ids agree by
        dictHash), a MergedSegmentView when ids must be remapped to a global
        dictionary, and plan is None when the set must take the per-segment
        fallback. Either way the plan is the SET's: a leaf is folded by the
        set's facts (min of mins, max of maxes, `hasNulls` of any, the
        cardinality of the id space the plan is in), never by the first
        member's (`merged.set_facts`). `routed` (members of `segments`) is
        what a star-tree answer is made over: its record masks are the
        query's own, so it stacks the routed members alone."""
        star_plans = self._star_fit_plans(
            ctx, segments if routed is None else routed)
        if star_plans is not None:
            # every segment answers from a pre-aggregated star-tree record
            # table. SMALL tables (~100s of records): the per-segment host
            # executor beats any device dispatch outright, so the mesh
            # planner yields to it (reference: StarTreeUtils.isFitForStarTree
            # gating in the leaf plan). LARGE record tables (high-cardinality
            # split dimensions, 1e5+ records): stack the record tables like
            # base segments and run the fused kernel over them — the
            # split-dim predicates compile into the kernel mask as LUT/
            # interval leaves and the tree-traversal record masks ride the
            # kernel's valid input (BASELINE config 3 as designed).
            star = self._plan_star_device(
                ctx, segments if routed is None else routed, star_plans)
            if star is not None:
                return star, "star"
            return None, None
        # doc-set filters (JSON/TEXT_MATCH bitmaps, stacked per segment) and
        # MV LUT filters ([S, rows, W] padded id matrices) ride the mesh
        # kernel on the ALIGNED immutable path only: the merged view has no
        # aux indexes to match against and no MV remap, so those sets keep
        # the per-segment fallback
        special = _has_docset_filter(ctx) or _refs_multi_value(ctx, segments[0])
        total_docs = sum(s.num_docs for s in segments)
        any_mutable = any(getattr(s, "is_mutable", False) for s in segments)
        if not any_mutable:
            plan = plan_segment(ctx, set_facts(segments),
                                scan_docs=total_docs)
            # the first member's dictionaries speak for the set only where
            # they agree: a literal its dictionary lacks folds the plan to
            # "empty", which time-ordered members do not share
            if self._alignable(plan, segments):
                return plan, None
        if special:
            return None, None
        view = self._merged_view(segments)
        return plan_segment(ctx, view, scan_docs=total_docs), view

    def _star_fit_plans(self, ctx: QueryContext, segments):
        """Per-segment StarTreePlans when EVERY segment answers this query
        from a star-tree, else None (a mixed set keeps the mesh scan: one
        full-scan segment would serialize the whole query behind the host
        fallback). Computed ONCE — both the device decision and the stacked
        dispatch reuse these plans (the traversal mask is the expensive part
        for large trees)."""
        if not all(getattr(s, "star_trees", None) for s in segments):
            return None
        if any(getattr(s, "is_mutable", False) for s in segments):
            return None
        from ..query.startree_exec import try_star_tree
        plans = []
        for s in segments:
            p = try_star_tree(ctx, s)
            if p is None:
                return None
            plans.append(p)
        return plans

    def _plan_star_device(self, ctx: QueryContext, segments, plans=None):
        """StarSetPlan when the stacked device star path applies: every tree
        fits, the combined record tables are big enough to beat the host
        loop, the slot plan is device-feasible, and the views' dictionaries
        (the parents') align across segments."""
        if plans is None:
            plans = self._star_fit_plans(ctx, segments)
        if plans is None:
            return None
        total = sum(p.tree.num_records for p in plans)
        if total < STAR_DEVICE_MIN_RECORDS:
            return None
        views = [p.tree.view for p in plans]
        plan2 = plan_segment(plans[0].ctx2, views[0], scan_docs=total)
        if plan2.kind != "device" or plan2.sparse \
                or not self._alignable(plan2, views):
            return None
        return StarSetPlan(plans, views, plan2)

    def _dispatch_star(self, ctx: QueryContext, sp: "StarSetPlan",
                       partial=False):
        """Dispatch the stacked star-tree kernel: per-segment tree-traversal
        record masks stack into the kernel's valid input (the split-dim LUT
        predicates are already fused into the mask by the slot plan)."""
        p = self._prepare_star(ctx, sp, partial=partial)
        fn = self._get_shard_kernel(p.spec, p.s_pad, p.rows)
        return fn(p.inputs), p.decode

    def _stacked_docsets(self, ctx: QueryContext, plan, segments,
                         block: SegmentSetBlock) -> Tuple:
        """Per-segment JSON/TEXT_MATCH (or id-set) doc bitmaps, stacked
        [S_pad, rows] in leaf order and sharded on the segment axis — the
        `docsets` kernel input. The masks come from each segment's OWN aux
        index (a filter compile per segment IS the index lookup); the leaf
        structure is deterministic for a fixed expression, so leaf order
        agrees with the probe plan's.

        Stacked masks are CACHED on the block keyed by each leaf's
        `cache_token` (kind + every predicate parameter — geo leaves include
        the center point): immutable segments give one index lookup + one
        device transfer per distinct predicate, so repeated TEXT_MATCH
        queries dispatch at the same cost as any other filter (id-set leaves
        are content-addressed by a digest of the serialized set). A leaf
        without a token is never cached; cached entries reuse PER KEY, so one
        uncacheable leaf doesn't defeat the others' cache."""
        from ..query.predicate import DocSetLeaf, compile_filter
        probe_leaves = [l for l in plan.filter_prog.leaves
                        if isinstance(l, DocSetLeaf)]
        cache = block._cache
        keys = [("docset", f"{l.col}\x00{l.cache_token}")
                if l.cache_token else None for l in probe_leaves]
        out: List = [cache.get(k) if k is not None else None for k in keys]
        if any(v is None for v in out):
            per_seg: List[List[np.ndarray]] = []
            for s in segments:
                prog = compile_filter(ctx.filter, s)
                masks = [l.mask for l in prog.leaves
                         if isinstance(l, DocSetLeaf)]
                if len(masks) != len(probe_leaves):
                    raise DocsetPlanDivergence(
                        "doc-set leaf structure diverged across segments")
                per_seg.append(masks)
            n_docset_entries = sum(1 for k in cache if k[0] == "docset")
            if n_docset_entries > 32:
                # bound device memory: each entry is an [S_pad, rows] device
                # array; a stream of distinct search terms must not grow HBM
                # without limit
                for k in [k for k in cache if k[0] == "docset"]:
                    del cache[k]
            for j, key in enumerate(keys):
                if out[j] is not None:
                    continue
                stacked = np.zeros((block.s_pad, block.rows), dtype=bool)
                for i in range(len(segments)):
                    m = np.asarray(per_seg[i][j])
                    stacked[block.slots[i], :len(m)] = m[:block.rows]
                out[j] = jax.device_put(stacked, block._sharded)
                if key is not None:
                    cache[key] = out[j]
        return tuple(out)

    def _merged_view(self, segments) -> MergedSegmentView:
        # keyed by STABLE segment identity; the volatile part (mutable row counts)
        # is the value's subkey, so a grown consuming segment REPLACES its stale
        # view instead of accumulating one per growth step
        stable = (tuple(_path(s) for s in segments), True)
        vkey = view_key(segments)
        entry = self._views.get(stable)
        if entry is None or entry[0] != vkey:
            _drop_superseded(self._views, stable)
            entry = (vkey, MergedSegmentView(segments))
            self._views[stable] = entry
        return entry[1]

    def _alignable(self, plan, segments) -> bool:
        """Dictionary alignment is only needed where dict IDS are shared across
        devices: dense group keys, id-interval/LUT filters, and the
        distinct-family presence vectors (DISTINCTCOUNT/HLL/theta — HLL moved
        onto the presence path for the ~15x matmul-vs-scatter kernel win, at
        the cost of now needing alignment; unaligned sets take the merged-view
        global-dictionary remap instead). Decoded value columns (CmpLeaf
        expressions, SUM/MIN/MAX args) are materialized per segment against its
        OWN dictionary, so mixed segment sets still ride the mesh kernel for them."""
        cols = set(plan.group_cols)
        for leaf in plan.filter_prog.leaves:
            if isinstance(leaf, LutLeaf):
                cols.add(leaf.col)
        for agg in plan.aggs:
            if "distinct" in agg.device_outputs:
                cols.add(agg.arg.name)
        return aligned_dictionaries(segments, cols)

    # ------------------------------------------------------------------
    def _execute_sharded(self, ctx: QueryContext, plan, segments, view=None) -> ResultTable:
        outs_dev, decode = self._dispatch_sharded(ctx, plan, segments, view)
        return decode(self.fetch(outs_dev))  # one host sync for all partials

    def execute_many(self, segments: Sequence[ImmutableSegment],
                     queries: Sequence[Union[str, QueryContext]],
                     schema=None) -> List[ResultTable]:
        """Pipelined batch execution: dispatch every query's kernel asynchronously,
        then fetch ALL results with ONE device_get round trip.

        Every synchronization is one host round trip no matter how much work
        it covers, so a serving loop that drains its queue
        through this path amortizes the round trip across the batch — the TPU analog
        of the reference broker pipelining queries over its Netty channels."""
        pending: List = []  # (index, outs_dev, decode) | (index, ResultTable)
        for qi, query in enumerate(queries):
            ctx = compile_query(query, schema or segments[0].schema) \
                if isinstance(query, str) else query
            plan, view = self._plan_for_set(ctx, segments)
            if isinstance(plan, StarSetPlan):
                outs_dev, decode = self._dispatch_star(ctx, plan)
                pending.append((qi, outs_dev, decode))
            elif plan is None or plan.kind != "device" \
                    or not self._fits(plan):
                pending.append((qi, self._fallback.execute(segments, ctx)))
            else:
                try:
                    outs_dev, decode = self._dispatch_sharded(ctx, plan,
                                                              segments, view)
                    pending.append((qi, outs_dev, decode))
                except DocsetPlanDivergence:
                    pending.append((qi, self._fallback.execute(segments, ctx)))
        fetched = self.fetch([p[1] for p in pending if len(p) == 3])
        results: List[Optional[ResultTable]] = [None] * len(queries)
        it = iter(fetched)
        for p in pending:
            results[p[0]] = p[1] if len(p) == 2 else p[2](next(it))
        return results

    def dispatch_partial(self, ctx: QueryContext, segments):
        """Plan + asynchronously dispatch a SERVER-LEVEL partial for the set.

        Returns (device outputs, decode) where decode(host_outs) ->
        SegmentResult — the pre-broker-reduce partial a server ships to the
        broker (reference: ServerQueryExecutorV1Impl returning a DataTable,
        not a reduced result) — or None when the set cannot ride the device
        path (selection/host plans, doc-set divergence). Group partials are
        NOT order-by trimmed: the broker merges partials from every server
        before trimming, exactly like the CPU per-segment path."""
        plan, view = self._plan_for_set(ctx, segments)
        if isinstance(plan, StarSetPlan):
            return self._dispatch_star(ctx, plan, partial=True)
        if plan is None or plan.kind != "device" or not self._fits(plan):
            return None
        try:
            return self._dispatch_sharded(ctx, plan, segments, view,
                                          partial=True)
        except DocsetPlanDivergence:
            return None

    # -- prepared dispatch (the serving pipeline's unit of work) -------
    def prepare_partial(self, ctx: QueryContext, segments, resident=None):
        """Plan + build (but do NOT launch) a server-level partial dispatch.

        `segments` are the members the query was routed to; `resident`, where
        the caller knows it, is the set the server holds of the table, in
        push order. Block, view and plan are the resident set's; the routed
        members are a runtime input of the launch (`_prepare_sharded`).

        Returns a PreparedDispatch or None (host fallback). The pipeline
        groups prepared items by dedupe/stack key and launches them through
        `dispatch_prepared`, so N same-shape queries pay one traced
        executable and — where only runtime scalars differ — one batched
        kernel launch."""
        routed = None
        if resident is not None:
            held = {_path(s) for s in resident}
            if all(_path(s) in held for s in segments):
                # the set in the resident's order, whatever order the broker
                # named its members in: one key, one block
                routed = list(segments) if len(segments) < len(held) else None
                segments = list(resident)
        # the two halves of a prepare, timed where they run: the plan, then
        # the kernel spec, decode tables and runtime inputs
        with stage("prepare.plan") as planned:
            plan = self._plan_partial(ctx, segments, routed)
        qstats.record(qstats.DEVICE_PLAN_MS, planned.ms)
        if plan is None:
            return None
        with stage("prepare.inputs") as built:
            try:
                p = self._build_partial(ctx, plan, segments, routed)
            except DocsetPlanDivergence:
                p = None
        qstats.record(qstats.DEVICE_INPUTS_MS, built.ms)
        return p

    def _plan_partial(self, ctx: QueryContext, segments, routed):
        """`prepare_partial`'s plan: (plan, view) of the set, or None where the
        host answers."""
        if not ctx.aggregations and not ctx.distinct:
            # selection: only the immutable top-k path rides the device (no
            # merged-view remap — a fallback verdict must stay cheap)
            if not segments or any(getattr(s, "is_mutable", False)
                                   for s in segments):
                return None
            plan = plan_segment(ctx, set_facts(segments),
                                scan_docs=sum(s.num_docs for s in segments))
            if plan.kind != "selection":
                return None  # empty/pruned: the host path answers trivially
            return plan, None
        plan, view = self._plan_for_set(ctx, segments, routed)
        if not isinstance(plan, StarSetPlan) and (
                plan is None or plan.kind != "device" or not self._fits(plan)):
            return None
        return plan, view

    def _fits(self, plan) -> bool:
        """Whether this mesh runs the plan's kernel: a GROUP BY past the
        dense key space (`plan.sparse`) answers from one device's sorted
        groups; merging several chips' groups is not built, so on a mesh of
        more than one the host answers it."""
        return not (plan.sparse and self.n_devices > 1)

    def _build_partial(self, ctx: QueryContext, planned, segments, routed):
        """`prepare_partial`'s inputs: the PreparedDispatch of a plan."""
        plan, view = planned
        if plan.kind == "selection":
            return self._prepare_topk(ctx, plan, segments, routed)
        if isinstance(plan, StarSetPlan):
            return self._prepare_star(ctx, plan)
        return self._prepare_sharded(ctx, plan, segments, view,
                                     partial=True, routed=routed)

    def fetch(self, trees):
        """One host sync for a batch of dispatched output trees (the
        pipeline's fetch hook; fakes in tests override this). The wall spent
        blocking here is the batch's device-exec + transfer time."""
        t0 = time.perf_counter()
        out = jax.device_get(trees)
        ms = (time.perf_counter() - t0) * 1000
        get_registry().histogram("pinot_mesh_fetch_ms").observe(ms)
        qstats.record(qstats.DEVICE_FETCH_MS, ms)
        qstats.record(qstats.BYTES_FETCHED, tree_bytes(out))
        return out

    def dispatch_prepared(self, reps: Sequence[PreparedDispatch]):
        """Launch a deduped batch of prepared dispatches.

        `reps` are dedupe-group representatives. Returns a list of launches
        `(outs_dev, finish, indices, recorded)`: `indices` are positions into
        `reps` covered by that launch, `finish(host_fetched)` -> list of
        decoded host outs dicts aligned with `indices`, and `recorded` is what
        the kernel cache and the first-call fence recorded during THAT launch
        (`compileMs`, `compileCacheMisses`, ...: the pipeline folds it into
        the queries the launch answers). Stackable reps sharing a `stack_key`
        collapse into ONE batched kernel launch."""
        groups: Dict[Tuple, List[int]] = {}
        order: List[Tuple] = []
        for i, p in enumerate(reps):
            key = p.stack_key if (p.kind == "agg" and p.stackable) \
                else ("solo", i)
            if key not in groups:
                groups[key] = []
                order.append(key)
            groups[key].append(i)
        launches = []
        for key in order:
            idxs = groups[key]
            ps = [reps[i] for i in idxs]
            # a launch's two pieces: the executable's lookup (a build and
            # compile on a miss), then the jitted calls that enqueue it
            with qstats.scoped() as recorded:
                if len(ps) == 1:
                    p = ps[0]
                    if p.kind == "topk":
                        with stage("launch.call"):
                            outs = p.launch()
                        if self.n_devices > 1:
                            # a plain jit over the sharded block: the
                            # compiler's own collectives are not counted
                            qstats.record(qstats.MESH_LAUNCHES)
                    else:
                        with stage("launch.kernel"):
                            fn = self._get_shard_kernel(
                                p.spec, p.s_pad, p.rows, window=p.window)
                        _record_fused(p)
                        with stage("launch.call"):
                            outs = fn(p.inputs)
                    with stage("launch.call"):
                        packed, unpack = self._pack(outs, p.trim_keys,
                                                    batched=0)
                    finish = (lambda host, u=unpack: [u(host)])
                else:
                    outs, b_real = self._launch_stacked(ps)
                    with stage("launch.call"):
                        packed, unpack = self._pack(outs, ps[0].trim_keys,
                                                    batched=b_real)
                    finish = (lambda host, u=unpack, n=b_real:
                              [u(host, b) for b in range(n)])
                # what the launch read of the resident set (a stacked launch
                # shares its members' block and routing: `stack_key`)
                for k, v in (ps[0].slot_stats or {}).items():
                    qstats.record(k, v)
            launches.append((packed, finish, idxs, recorded.to_wire()))
        return launches

    def _launch_stacked(self, ps: List[PreparedDispatch]):
        """ONE batched kernel launch for same-executable prepared dispatches
        differing only in runtime scalars: scan the fused body over stacked
        [B, n] scalar streams (columns/LUTs/valid broadcast). B pads to the
        next power of two (repeating the last scalars) so the jit cache holds
        log2 variants, not one per concurrency level."""
        b = len(ps)
        b_pad = 1 << (b - 1).bit_length()
        iscal = np.stack([p.iscal_np for p in ps]
                         + [ps[-1].iscal_np] * (b_pad - b))
        fscal = np.stack([p.fscal_np for p in ps]
                         + [ps[-1].fscal_np] * (b_pad - b))
        inputs = dict(ps[0].inputs)
        inputs["iscal"] = self._const(iscal)
        inputs["fscal"] = self._const(fscal)
        with stage("launch.kernel"):
            fn = self._get_shard_kernel(ps[0].spec, ps[0].s_pad, ps[0].rows,
                                        batch=b_pad, window=ps[0].window)
        # one persistent launch carries every stacked query's fused scan
        _record_fused(ps[0])
        with stage("launch.call"):
            return fn(inputs), b

    def _pack(self, outs_dev: Dict[str, jnp.ndarray], trim_keys: Tuple[int, int],
              batched: int):
        """Device-resident combine of a launch's outputs before the fetch.

        Concatenates every output leaf (raveled, grouped by dtype, key axis
        trimmed from num_keys_pad to num_keys_real) into one flat array per
        dtype ON DEVICE, so the batched `device_get` ships a couple of
        combined arrays per launch instead of per-output (and, stacked,
        per-item) leaves. Returns (packed device dict, unpack) where
        unpack(host_packed[, b]) rebuilds the named outs dict."""
        meta = tuple(sorted((k, tuple(v.shape), v.dtype.str)
                            for k, v in outs_dev.items()))
        pad, real = trim_keys
        fn = _pack_kernel(meta, trim_keys, bool(batched))

        # grouped outputs carry the key axis at either `pad` (reduce-scattered
        # dense outputs, overflow bucket dropped on device) or `pad + 1` (the
        # psum/pmin/pmax path keeps the masked-row overflow bucket at index
        # pad); both trim to `real` — every partial decoder reads only
        # [:num_keys_real]
        def _core(shape):
            core = shape[1:] if batched else shape
            if pad and real < pad and core and core[0] in (pad, pad + 1):
                core = (real,) + tuple(core[1:])
            return core

        def unpack(host: Dict[str, np.ndarray], b: Optional[int] = None):
            out = {}
            offs: Dict[str, int] = {}
            for name, shape, dts in meta:
                core = _core(shape)
                n = int(np.prod(core)) if core else 1
                flat = host[dts]
                row = flat[b] if batched else flat
                o = offs.get(dts, 0)
                out[name] = np.asarray(row[o:o + n]).reshape(core)
                offs[dts] = o + n
            return out

        return fn(outs_dev), unpack

    def _block_for(self, segments, view, s_pad: int) -> SegmentSetBlock:
        # stable key + volatile subkey: growth of a consuming segment frees the
        # superseded block's device arrays instead of pinning up to 64 dead copies
        stable = (tuple(_path(s) for s in segments),
                  view is not None)
        vkey = (view_key(segments), s_pad)
        entry = self._set_blocks.get(stable)
        if entry is None or entry[0] != vkey:
            _drop_superseded(self._set_blocks, stable)
            entry = (vkey, SegmentSetBlock(segments, s_pad, self.mesh, view))
            self._set_blocks[stable] = entry
            # padding-waste accounting: fraction of the stacked [s_pad, rows]
            # block that is fill (ragged tails + pow2 slot quantization), the
            # scan overhead uneven segment sets pay for mesh rectangularity
            get_registry().histogram("pinot_mesh_pad_waste_pct").observe(
                entry[1].pad_waste_pct)
        return entry[1]

    def _finish_mesh_stats(self, res, block: SegmentSetBlock, outs=None):
        """Attach to a decoded result what only the launch knows: its worst
        per-device doc-load skew (`deviceSkewPct`, max-merged upstream; a mesh
        of one has none) and which decode branch its sort regimes ran
        (`qstats.decode_branch` of the fetched `outs`). Partials carry both in
        `SegmentResult.stats` (riding the wire to the broker merge); full
        results record into the request thread's active stats."""
        took = qstats.decode_branch(outs)
        skewed = self.n_devices > 1
        if not (took or skewed):
            return res
        from ..query.reduce import SegmentResult
        if isinstance(res, SegmentResult):
            st = dict(res.stats or {})
            if skewed:
                st[qstats.DEVICE_SKEW_PCT] = max(
                    st.get(qstats.DEVICE_SKEW_PCT, 0.0), block.skew_pct)
            for key in took:
                st[key] = st.get(key, 0) + 1
            res.stats = st
        else:
            if skewed:
                qstats.record_max(qstats.DEVICE_SKEW_PCT, block.skew_pct)
            for key in took:
                qstats.record(key)
        return res

    def _dispatch_sharded(self, ctx: QueryContext, plan, segments, view=None,
                          valid_override=None, star=None, partial=False):
        """Dispatch the fused mesh kernel asynchronously.

        Returns (device outputs, decode) where decode(host_outs) -> ResultTable
        (or a SegmentResult partial when `partial=True`); the
        caller chooses when to pay the fetch round trip (one query vs a batch).
        `valid_override` replaces the block's all-true validity (stacked
        star-tree record masks); `star` = (original ctx, StarSetPlan) makes
        decode reassemble slot states into the original aggregations."""
        p = self._prepare_sharded(ctx, plan, segments, view, valid_override,
                                  star, partial)
        fn = self._get_shard_kernel(p.spec, p.s_pad, p.rows, window=p.window)
        return fn(p.inputs), p.decode

    def _prepare_star(self, ctx: QueryContext, sp: "StarSetPlan",
                      partial=True):
        s_pad = pad_slots(len(sp.views), self.n_devices)
        # build (or fetch) the block FIRST so the stacked record masks land in
        # the same placement slots as the record-table columns
        block = self._block_for(sp.views, None, s_pad)
        valid = np.zeros((s_pad, block.rows), dtype=bool)
        for i, p in enumerate(sp.plans):
            m = np.asarray(p.record_mask, dtype=bool)
            valid[block.slots[i], :len(m)] = m[:block.rows]
        valid_dev = jax.device_put(valid, block._sharded)
        return self._prepare_sharded(sp.plans[0].ctx2, sp.plan2, sp.views,
                                     valid_override=valid_dev,
                                     star=(ctx, sp), partial=partial)

    def _mesh_fused_cols(self, plan, segments,
                         view) -> Tuple[Tuple[str, str], ...]:
        """Dict value columns the stacked kernel decodes in-register
        ((col, "dict") KernelSpec routing) instead of reading a
        host-materialized decoded HBM column.

        Aligned sets only — a merged view remaps ids into the GLOBAL
        dictionary space, which the per-segment LUT stack cannot decode.
        FOR forms stay single-device: per-segment bases cannot ride the
        replicated iscal stream. Ineligible columns (multi-value, raw, or
        over `fused_lut_cap`) simply keep the decoded path — there is no
        separate staged mode on the mesh, fusion here only removes the
        decode materialization."""
        from ..engine.caps import get_caps
        from ..query.executor import _plan_vals_cols
        caps = get_caps()
        if not self.fused_enabled or view is not None:
            return ()
        fused = []
        for c in sorted(_plan_vals_cols(plan)):
            readers = [s.column(c) for s in segments]
            if all(r.has_dictionary
                   and not getattr(r, "is_multi_value", False)
                   for r in readers) \
                    and max(lut_size(r.cardinality)
                            for r in readers) <= caps.fused_lut_cap:
                fused.append((c, "dict"))
        return tuple(fused)

    def _routing(self, block: SegmentSetBlock, routed) -> Tuple[int, dict,
                                                                 dict]:
        """(window, inputs, slot stats) of a launch over `block` for a query
        routed to `routed` of its members (None: to all). A launch routed to
        every member has no routing input and is the program it always was;
        a subset adds the per-slot mask `route` and the window's start
        `route_start`, both runtime operands, and reads `window` slots."""
        resident = len(block.segments)
        slots = block.routed_slots(routed) if routed is not None else None
        window, inputs = 0, {}
        if slots is not None and len(slots) < resident:
            window, start = _route_window(slots, block.s_pad, self.n_devices)
            mask = np.zeros(block.s_pad, dtype=bool)
            mask[list(slots)] = True
            inputs = dict(route=self._const(mask),
                          route_start=self._const(np.asarray(start, np.int32)))
        stats = {qstats.ROUTED_SLOTS: len(slots) if window else resident,
                 qstats.RESIDENT_SLOTS: resident,
                 qstats.SCANNED_SLOTS: window if 0 < window < block.s_pad
                 else resident,
                 qstats.MERGED_LAUNCHES:
                 int(isinstance(block.view, MergedSegmentView))}
        return window, inputs, stats

    def _prepare_sharded(self, ctx: QueryContext, plan, segments, view=None,
                         valid_override=None, star=None,
                         partial=False, routed=None) -> PreparedDispatch:
        """Plan-shape + runtime-input construction WITHOUT the kernel launch
        (the separable front half of `_dispatch_sharded`). `segments` is the
        set that is staged and planned; `routed`, its members the query was
        routed to (None: all of them)."""
        build_device_geometry(plan)
        agg_specs = []
        distinct_lut_sizes: Dict[int, int] = {}
        agg_luts: Dict[str, jnp.ndarray] = {}

        s_pad = pad_slots(len(segments), self.n_devices)
        block = self._block_for(segments, view, s_pad)

        for i, agg in enumerate(plan.aggs):
            agg_specs.append((agg, agg.device_outputs))
            if "distinct" in agg.device_outputs:
                # plan.segment is the merged view on the unaligned path, so this is
                # the GLOBAL cardinality there (ids arrive remapped)
                distinct_lut_sizes[i] = lut_size(plan.segment.column(agg.arg.name).cardinality)

        from ..query.executor import _mv_lut_cols
        # star-tree record tables dispatch pre-decoded (their views are not
        # plain segment readers); everything else may fuse
        fused_cols = () if star is not None \
            else self._mesh_fused_cols(plan, segments, view)
        # a full result, or a partial the broker routed here alone, is the
        # whole answer: a sort-regime GROUP BY cuts its ORDER BY ... LIMIT
        # on the device, and its partial is then the sorted groups' form
        whole = not partial or bool(ctx.options.get(SOLE_SERVER))
        trim = sort_trim_spec(ctx, plan, self.n_devices) \
            if whole and star is None else ()
        spec = KernelSpec(plan.filter_prog, plan.group_cols, plan.num_keys_pad,
                          tuple(agg_specs), distinct_lut_sizes, block.rows,
                          mv_cols=_mv_lut_cols(plan, plan.segment),
                          fused_cols=fused_cols,
                          int_ranges=int_ranges(plan), trim=trim)

        # -- gather runtime inputs ------------------------------------
        # ids only where dict ids are semantically needed (group keys, interval/LUT
        # filters, distinct); everything value-like reads pre-decoded HBM columns.
        ids_cols, vals_cols, nulls_cols = set(plan.group_cols), set(), set()
        luts, iscal, fscal = [], [], []
        has_docsets = False
        for leaf in plan.filter_prog.leaves:
            if isinstance(leaf, LutLeaf):
                ids_cols.add(leaf.col)
                if leaf.intervals is not None:
                    for lo, hi in leaf.intervals:
                        iscal.extend((lo, hi))
                else:
                    luts.append(self._const(leaf.lut))
            elif isinstance(leaf, CmpLeaf):
                vals_cols.update(identifiers_in(leaf.expr))
                (iscal if leaf.is_int else fscal).extend(leaf.operands)
            elif isinstance(leaf, NullLeaf):
                nulls_cols.add(leaf.col)
            else:
                has_docsets = True
        docsets: Tuple = ()
        if has_docsets:
            docsets = self._stacked_docsets(ctx, plan, segments, block)
        for i, agg in enumerate(plan.aggs):
            if "distinct" in agg.device_outputs:
                ids_cols.add(agg.arg.name)
            elif agg.arg is not None and not (isinstance(agg.arg, Identifier)
                                              and agg.arg.name == "*"):
                vals_cols.update(identifiers_in(agg.arg))

        iscal_np = np.asarray(iscal, dtype=np.int32)
        fscal_np = np.asarray(fscal, dtype=np.float32)
        # fused dict columns ship their per-segment LUT stack via vals and
        # their id column via ids; the kernel decodes them itself, so the
        # decoded HBM column is never built for them
        fused = dict(fused_cols)
        for c in vals_cols:
            if fused.get(c) == "dict":
                ids_cols.add(c)
        inputs = dict(
            ids={c: block.ids(c) for c in ids_cols},
            vals={c: block.dict_luts(c) if fused.get(c) == "dict"
                  else block.decoded(c) for c in vals_cols},
            luts=tuple(luts),
            iscal=self._const(iscal_np),
            fscal=self._const(fscal_np),
            nulls={c: block.null_mask(c) for c in nulls_cols},
            valid=block.valid if valid_override is None else valid_override,
            strides=self._const(np.asarray(plan.strides, dtype=np.int32)),
            agg_luts=agg_luts,
            docsets=docsets,
        )
        window, route_inputs, slot_stats = self._routing(block, routed)
        inputs.update(route_inputs)

        def decode(outs):
            res = _decode_impl(outs)
            if res is _HOST_ANSWERS:
                # more rows passed than the sparse launch held
                if partial:
                    from ..cluster.device_server import DEVICE_FALLBACK
                    return DEVICE_FALLBACK
                return self._fallback.execute(list(segments), ctx)
            return self._finish_mesh_stats(res, block, outs)

        def _decode_impl(outs):
            # replicated outputs decode exactly like the single-segment path;
            # plan.segment's dictionaries (segment[0] when aligned, the merged global
            # dictionaries otherwise) decode the dense keys.
            if star is not None:
                # stacked star-tree path: decode SLOT states (no trim — the
                # order-by refers to the ORIGINAL aggregations), reassemble
                # them into original-agg states, reduce with the original ctx
                from ..query.aggregates import make_agg
                from ..query.startree_exec import reassemble
                orig_ctx, sp = star
                if plan.group_cols:
                    seg_result = self._fallback._decode_group_partials(
                        plan, outs, trim_global=False)
                else:
                    seg_result = self._fallback._decode_scalar_partials(plan,
                                                                        outs)
                reassemble(sp.plans[0], seg_result)
                if partial:
                    return seg_result
                orig_aggs = [make_agg(f) for f in orig_ctx.aggregations]
                merged = merge_segment_results([seg_result], orig_aggs)
                group_exprs = ([e for e, _ in orig_ctx.select_items]
                               if orig_ctx.distinct else list(orig_ctx.group_by))
                return reduce_to_result(orig_ctx, merged, orig_aggs,
                                        group_exprs)
            if plan.sparse or trim:
                seg_result = self._fallback._decode_sparse_partial(
                    plan, outs, bool(trim))
                if seg_result is None:
                    return _HOST_ANSWERS
                if partial:
                    return seg_result
                merged = merge_segment_results([seg_result], plan.aggs)
                return reduce_to_result(
                    ctx, merged, plan.aggs,
                    [e for e, _ in ctx.select_items] if ctx.distinct
                    else list(ctx.group_by))
            if plan.group_cols:
                if not partial:
                    # vectorized dense decode for the common agg shapes:
                    # post-psum outputs are GLOBAL, so groups finalize
                    # straight to rows with no state dicts (the decode half
                    # of the high-cardinality group-by redesign — the Python
                    # per-group loop costs more than the fused kernel past
                    # ~10k groups; query/dense_reduce.py)
                    from ..query.dense_reduce import try_dense_decode
                    dense = try_dense_decode(ctx, plan, outs)
                    if dense is not None:
                        return dense
                if partial:
                    # high-cardinality server partial: keep the kernel's dense
                    # arrays as-is (reduce.DensePartial) instead of densifying
                    # 100k+ Python state dicts that the broker would re-hash
                    dense_partial = self._fallback._decode_dense_partial(
                        plan, outs)
                    if dense_partial is not None:
                        return dense_partial
                # an order-by trim is exact for a FULL result; a server
                # partial stays untrimmed — the broker merges every server's
                # groups before trimming
                seg_result = self._fallback._decode_group_partials(
                    plan, outs, trim_global=not partial)
            else:
                seg_result = self._fallback._decode_scalar_partials(plan, outs)
            if partial:
                return seg_result
            merged = merge_segment_results([seg_result], plan.aggs)
            group_exprs = ([e for e, _ in ctx.select_items] if ctx.distinct
                           else list(ctx.group_by))
            return reduce_to_result(ctx, merged, plan.aggs, group_exprs)

        sig = spec.signature()
        shape_key = ("agg", sig, id(block), s_pad, block.rows, id(self.mesh),
                     window)
        # device operands are content-addressed (`_const`) or block-cached, so
        # object identity == content identity: two queries stack iff the same
        # executable reads the same device arrays (scalars ride the stack)
        operands = (tuple(id(a) for a in inputs["luts"]),
                    id(inputs["valid"]), id(inputs["strides"]),
                    tuple(id(d) for d in docsets), id(inputs.get("route")))
        stackable = (star is None and valid_override is None and not docsets)
        stack_key = shape_key + operands
        dedupe_key = None if valid_override is not None else \
            stack_key + (iscal_np.tobytes(), fscal_np.tobytes())
        # device-side key-axis trim: a grouped server partial only ever decodes
        # the first num_keys_real entries, so padding rows are never fetched
        trim_keys = (plan.num_keys_pad, plan.num_keys_real) \
            if (partial and plan.group_cols and star is None
                and not plan.sparse and not trim) else (0, 0)
        return PreparedDispatch(
            kind="agg", spec=spec, inputs=inputs, s_pad=s_pad,
            rows=block.rows, stack_key=stack_key, dedupe_key=dedupe_key,
            stackable=stackable, decode=decode, iscal_np=iscal_np,
            fscal_np=fscal_np, trim_keys=trim_keys, window=window,
            slot_stats=slot_stats)

    # ------------------------------------------------------------------
    def _prepare_topk(self, ctx: QueryContext, plan, segments, routed=None):
        """Prepared device top-k for a served ORDER-BY-limit selection over
        the set `segments`; `routed` (None: all) are the members the query
        was routed to, the others masked out of `valid`.

        Mirrors `ServerQueryExecutor._topk_candidates` eligibility over the
        STACKED segment set, dispatching the same fused `compute_topk` kernel
        (`kernels.topk_kernel`) over the block's [S_pad, rows] arrays so the
        candidate trim happens on device and only k+slack doc ids ship in the
        pipeline's batched fetch. Returns None -> host fallback."""
        from ..query.planner import _expr_device_ok
        from ..query.predicate import DocSetLeaf
        if not device_topk_screen(ctx):
            return None
        order = ctx.order_by[0]
        k = ctx.offset + ctx.limit
        seg0 = segments[0]
        from ..query.executor import topk_order_key_device_ok
        if any(not topk_order_key_device_ok(s, order.expr)
               for s in segments):
            return None
        col = order.expr.name
        if _refs_multi_value(ctx, seg0):
            return None  # MV select/filter cells keep the per-segment path
        lut_cols = []
        for leaf in plan.filter_prog.leaves:
            if isinstance(leaf, CmpLeaf) and _expr_device_ok(leaf.expr, seg0):
                return None  # mask itself needs the host path
            if isinstance(leaf, DocSetLeaf):
                return None  # per-segment aux-index bitmaps: host path
            if isinstance(leaf, LutLeaf):
                lut_cols.append(leaf.col)
        if lut_cols and not aligned_dictionaries(segments, lut_cols):
            return None  # plan's id intervals only valid set-wide when aligned

        from ..engine.kernels import topk_kernel
        s_pad = pad_slots(len(segments), self.n_devices)
        block = self._block_for(segments, None, s_pad)
        spec = KernelSpec(plan.filter_prog, (), 1, (), {}, block.rows,
                          int_ranges=int_ranges(plan, [order.expr]))

        ids_cols, vals_cols, nulls_cols = set(), {col}, set()
        luts, iscal, fscal = [], [], []
        for leaf in plan.filter_prog.leaves:
            if isinstance(leaf, LutLeaf):
                ids_cols.add(leaf.col)
                if leaf.intervals is not None:
                    for lo, hi in leaf.intervals:
                        iscal.extend((lo, hi))
                else:
                    luts.append(self._const(leaf.lut))
            elif isinstance(leaf, CmpLeaf):
                vals_cols.update(identifiers_in(leaf.expr))
                (iscal if leaf.is_int else fscal).extend(leaf.operands)
            elif isinstance(leaf, NullLeaf):
                nulls_cols.add(leaf.col)
        iscal_np = np.asarray(iscal, dtype=np.int32)
        fscal_np = np.asarray(fscal, dtype=np.float32)
        inputs = dict(
            ids={c: block.ids(c) for c in ids_cols},
            vals={c: block.decoded(c) for c in vals_cols},
            luts=tuple(luts),
            iscal=self._const(iscal_np),
            fscal=self._const(fscal_np),
            nulls={c: block.null_mask(c) for c in nulls_cols},
            valid=block.valid,
        )
        route = self._routing(block, routed)[1].get("route")
        if route is not None:
            # a plain jit over the block: the routed slots as one more pass
            inputs["valid"] = block.valid & route[:, None]
        slack = ServerQueryExecutor.TOPK_SLACK
        fn, kk = topk_kernel(spec, order.expr, order.desc, k + slack,
                             total_rows=s_pad * block.rows)

        def launch(inp=inputs):
            return fn(inp["ids"], inp["vals"], inp["luts"], inp["iscal"],
                      inp["fscal"], inp["nulls"], inp["valid"], ())

        decode = self._make_topk_decode(ctx, plan, segments, block, k, kk)
        static = ("topk", plan.filter_prog.signature(), repr(order.expr),
                  order.desc, kk, id(block), s_pad, block.rows)
        return PreparedDispatch(
            kind="topk", spec=static, inputs=inputs, s_pad=s_pad,
            rows=block.rows, stack_key=static,
            dedupe_key=static + (tuple(id(a) for a in luts), id(route),
                                 iscal_np.tobytes(), fscal_np.tobytes()),
            stackable=False, decode=decode, launch=launch)

    def _make_topk_decode(self, ctx: QueryContext, plan, segments, block,
                          k: int, kk: int):
        """decode(host outs) for the served top-k: gather the few candidate
        rows from the segments on host and ship a 'selection' partial whose
        exact sort keys the broker re-sorts (f32 only decided the CANDIDATE
        set, same contract as the single-segment `_topk_candidates`)."""
        from ..cluster.device_server import DEVICE_FALLBACK
        from ..engine.expr import eval_expr as _eval
        from ..query.executor import _is_const
        from ..query.reduce import SegmentResult

        def decode(outs):
            count = int(outs["count"])
            if int(outs["nanMatches"]) > 0:
                # NaN sort keys displace candidates unpredictably vs the
                # Python sort: parity demands the host path decide
                return DEVICE_FALLBACK
            idx = np.asarray(outs["idx"])
            ok = np.asarray(outs["ok"])
            keep = min(kk, count)
            idx, ok = idx[:keep], ok[:keep]
            idx = idx[ok]
            # block rows are placement SLOTS (chip-aware, not identity order):
            # map back to segment indices before the per-segment gather
            seg_i = block.slot_to_seg[idx // block.rows]
            row_i = idx % block.rows
            if len(idx) < min(k, count):
                return DEVICE_FALLBACK  # -inf ties displaced matches
            # gather candidates per segment (order is irrelevant: the broker
            # sorts the merged partial by the exact sort keys below)
            perm = np.lexsort((row_i, seg_i))
            seg_i, row_i = seg_i[perm], row_i[perm]
            needed = set()
            for e, _ in ctx.select_items:
                needed.update(identifiers_in(e))
            for o in ctx.order_by:
                needed.update(identifiers_in(o.expr))
            env = {}
            for c in needed:
                parts = []
                for s in np.unique(seg_i):
                    rows_in = row_i[seg_i == s]
                    parts.append(np.asarray(
                        segments[s].column(c).values()[rows_in]))
                env[c] = np.concatenate(parts) if parts else np.empty(0)
            n = len(row_i)
            out_cols = [np.asarray(_eval(e, env, np)) if not _is_const(e)
                        else np.full(n, _eval(e, env, np), dtype=object)
                        for e, _ in ctx.select_items]

            def _cell(v):
                if isinstance(v, np.generic):
                    return v.item()
                if isinstance(v, np.ndarray):
                    return v.tolist()
                return v
            rows = [tuple(_cell(c[i]) for c in out_cols) for i in range(n)]
            sort_cols = [np.asarray(_eval(o.expr, env, np))
                         for o in ctx.order_by]
            sort_keys = [tuple(c[i].item() if isinstance(c[i], np.generic)
                               else c[i] for c in sort_cols)
                         for i in range(n)]
            return SegmentResult("selection", rows=rows, sort_keys=sort_keys,
                                 num_docs_scanned=count)

        return decode

    # ------------------------------------------------------------------
    def _get_shard_kernel(self, spec: KernelSpec, s_pad: int, rows: int,
                          batch: int = 0, window: int = 0):
        cache_key = (spec.signature(), self.n_devices, s_pad, rows,
                     id(self.mesh), batch, window)
        fn = _SHARD_KERNEL_CACHE.get(cache_key)
        if fn is None:
            qstats.record(qstats.COMPILE_CACHE_MISSES)
            get_registry().counter("pinot_kernel_cache_misses").inc()
            # same first-call compile fence as the single-device cache: the
            # cold call's wall (trace + compile + first run) lands in the
            # compile histogram, not in whichever query drew the short straw
            fn = _fence_first_call(self._build_shard_kernel(spec, batch,
                                                            window))
            _SHARD_KERNEL_CACHE[cache_key] = fn
        else:
            qstats.record(qstats.COMPILE_CACHE_HITS)
            get_registry().counter("pinot_kernel_cache_hits").inc()
        return fn

    def _build_shard_kernel(self, spec: KernelSpec, batch: int = 0,
                            window: int = 0):
        """jit(shard_map(fused scan body + per-output ICI collective)).

        The body is the SAME gather/scatter-free kernel as the single-device path
        (`kernels.make_kernel_body`); partials agree on dense keys across devices, so
        each output merges with exactly one collective. Low-cardinality (and
        min/max) outputs psum/pmin/pmax to a replicated result as before;
        HIGH-cardinality dense sum outputs (DensePartial group-bys, distinct
        presence matrices) instead reduce-scatter (`psum_scatter`): each device
        keeps 1/n of the key space, the overflow bucket is dropped on device,
        and the fetch reassembles the shards host-side — a pure memcpy, zero
        host-side value merges, at half the collective bandwidth of a psum.

        Output names/shapes are only known from the body, so the shard_map is
        constructed LAZILY at the first invocation: `jax.eval_shape` over the
        per-shard input shapes learns the outputs, which decides each one's
        collective and out_spec. The first call runs inside the compile fence,
        so the extra trace lands in `compileMs` like any cold compile.

        `batch > 0` builds the STACKED variant: iscal/fscal arrive [B, n] and
        the body scans over them — B same-shape queries in one launch, reading
        the HBM columns once per scan step but paying ONE dispatch.

        `window > 0` builds the ROUTED variant for a query routed to some of
        the block's members: two more runtime operands, the per-slot mask
        `route` (ANDed into `valid`) and the scalar `route_start`; where
        `window` is less than the slots a device holds (a mesh of one) the
        body reads `window` slots from `route_start` on, a `dynamic_slice`
        of every per-segment operand over the slot axis, so the rows read are
        the window's and every start shares the program."""
        from ..engine.kernels import (combine_collective, kernel_name,
                                      make_kernel_body)
        body = make_kernel_body(spec)
        P = jax.sharding.PartitionSpec
        ax = SEGMENT_AXIS
        n = self.n_devices
        sharded, repl = P(ax), P()

        in_specs = dict(ids=sharded, vals=sharded, luts=repl, iscal=repl,
                        fscal=repl, nulls=sharded, valid=sharded, strides=repl,
                        agg_luts=sharded, docsets=sharded)
        if window:
            in_specs.update(route=sharded, route_start=repl)
        in_specs = (in_specs,)
        _REPL_KEYS = ("luts", "iscal", "fscal", "strides", "route_start")
        _SLOT_KEYS = ("ids", "vals", "nulls", "valid", "agg_luts", "docsets")

        def routed(inputs):
            """The per-segment operands a routed launch reads: its window of
            slots, `valid` cleared in the slots it was not routed to."""
            if not window:
                return inputs
            with jax.named_scope("pinot.route"):
                out = dict(inputs)
                route = inputs["route"]
                if window < route.shape[0]:         # slots this device holds
                    def cut(x):
                        return jax.lax.dynamic_slice_in_dim(
                            x, inputs["route_start"], window, axis=0)
                    for key in _SLOT_KEYS:
                        out[key] = jax.tree_util.tree_map(cut, inputs[key])
                    route = cut(route)
                out["valid"] = out["valid"] & route[:, None]
            return out

        num_seg = spec.num_keys_pad + 1
        pad = spec.num_keys_pad
        key_dim = 1 if batch else 0  # scan stacks a leading batch axis

        if batch:
            def call_body(inputs):
                inputs = routed(inputs)

                def step(carry, scal):
                    i_s, f_s = scal
                    out = body(inputs["ids"], inputs["vals"], inputs["luts"],
                               i_s, f_s, inputs["nulls"], inputs["valid"],
                               inputs["strides"], inputs["agg_luts"],
                               inputs["docsets"])
                    return carry, out
                _, outs = jax.lax.scan(step, 0,
                                       (inputs["iscal"], inputs["fscal"]))
                return outs
        else:
            def call_body(inputs):
                inputs = routed(inputs)
                return body(inputs["ids"], inputs["vals"], inputs["luts"],
                            inputs["iscal"], inputs["fscal"], inputs["nulls"],
                            inputs["valid"], inputs["strides"],
                            inputs["agg_luts"], inputs["docsets"])

        def scatterable(name, shape) -> bool:
            return (n > 1 and pad >= SCATTER_MIN_KEYS and pad % n == 0
                    and len(shape) > key_dim and shape[key_dim] == num_seg
                    and not name.endswith((".min", ".max")))

        built: Dict[str, Any] = {}

        def jitted_for(inputs):
            """The jit(shard_map(...)) for these inputs (arrays, or
            ShapeDtypeStructs for an AOT lowering), built at first use."""
            compiled = built.get("fn")
            if compiled is None:
                # learn output names/shapes from the per-shard input shapes
                shard_in = {
                    key: jax.tree_util.tree_map(
                        lambda x, sh=(key not in _REPL_KEYS):
                        jax.ShapeDtypeStruct(
                            ((x.shape[0] // n,) + tuple(x.shape[1:]))
                            if sh and x.ndim else tuple(x.shape), x.dtype),
                        val)
                    for key, val in inputs.items()}
                out_shapes = jax.eval_shape(call_body, shard_in)
                scat = {name for name, s in out_shapes.items()
                        if scatterable(name, s.shape)}
                if scat:
                    get_registry().counter(
                        "pinot_kernel_scatter_builds").inc()
                if n > 1:
                    # what one device hands to this program's collectives:
                    # each per-shard output whole, a scattered one without
                    # its overflow row
                    def handed(name, s) -> int:
                        shape = list(s.shape)
                        if name in scat:
                            shape[key_dim] = pad
                        return int(np.prod(shape, dtype=np.int64)) \
                            * s.dtype.itemsize
                    built["mesh"] = {
                        qstats.MESH_LAUNCHES: 1,
                        qstats.SCATTER_LAUNCHES: int(bool(scat)),
                        qstats.COLLECTIVE_BYTES: sum(
                            handed(k, s) for k, s in out_shapes.items())}

                def shard_body(sin):
                    outs = call_body(sin)
                    res = {}
                    for name, v in outs.items():
                        if name in scat:
                            with jax.named_scope("pinot.collective.scatter"):
                                core = v[:, :pad] if batch else v[:pad]
                                res[name] = jax.lax.psum_scatter(
                                    core, ax, scatter_dimension=key_dim,
                                    tiled=True)
                        else:
                            res[name] = combine_collective(name, v, ax)
                    return res

                # what ran, by name, in the trace's "XLA Modules" line
                shard_body.__name__ = kernel_name(spec, batch)

                out_specs = {
                    name: ((P(None, ax) if batch else P(ax))
                           if name in scat else repl)
                    for name in out_shapes}
                built["fn"] = compiled = jax.jit(jax.shard_map(
                    shard_body, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs))
            return compiled

        # from the static plan, once
        is_widened, is_masked = widened(spec), masked(spec)
        is_sparse, is_trimmed = sparse(spec), trimmed(spec)
        is_dense_trimmed, is_sorted_minmax = (dense_trimmed(spec),
                                              sorted_minmax(spec))

        def fn(inputs):
            compiled = jitted_for(inputs)
            for key, v in built.get("mesh", {}).items():
                qstats.record(key, v)
            # the rows one device reads: a shape, like what `mesh` holds
            rows_read = inputs["valid"].size // n
            held = inputs["valid"].shape[0] // n
            if 0 < window < held:
                rows_read = rows_read // held * window
            if slabbed(spec, rows_read):
                qstats.record(qstats.SLABBED_LAUNCHES)
            if is_widened:
                qstats.record(qstats.WIDENED_AGG_LAUNCHES)
            if is_masked:
                qstats.record(qstats.MASKED_GROUPBY_LAUNCHES)
            if is_sparse:
                qstats.record(qstats.SPARSE_GROUPBY_LAUNCHES)
            if is_trimmed:
                qstats.record(qstats.DEVICE_TRIMMED_LAUNCHES)
            if is_dense_trimmed:
                qstats.record(qstats.DENSE_TRIMMED_LAUNCHES)
            if is_sorted_minmax:
                qstats.record(qstats.SORTED_MINMAX_LAUNCHES)
            return compiled(inputs)

        fn.jitted_for = jitted_for
        return fn


