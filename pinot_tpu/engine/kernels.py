"""Fused jit scan kernels: filter mask + group key + aggregation partials in one pass.

This replaces the reference's entire per-segment operator chain
(`FilterPlanNode` -> `DocIdSetOperator` -> `ProjectionOperator` -> `TransformOperator` ->
`AggregationGroupByOrderByOperator`, SURVEY.md §3.1) with ONE XLA program per plan shape:

    mask   = filter_tree(id-interval compares | vector compares | null bitmaps) & valid
    key    = sum(group_ids * strides)        (dense dict-id keys, reference:
                                              DictionaryBasedGroupKeyGenerator.java:62)
    partials = [mask; masked values] @ one_hot(key)   (ONE stacked matmul on the MXU;
                                              a masked reduce up to `masked_cap` key cells)

GATHER-FREE ON THE HOT MASK PATH: the kernel favors compares, selects, reductions and
matmuls —

* dict predicates -> id-interval compares (sorted dictionaries make EQ/RANGE/small-IN
  contiguous id runs, resolved host-side at plan time);
* dict decode -> fused plans: a tree of selects over a small decode table
  (SELECT_DECODE_CAP; the one gather left is a table wider than that); staged
  plans: host-materialized value columns cached in HBM (`datablock.values`);
* group-by partials -> one ladder over the padded key count, its crossovers
  the constants of `engine/caps.py`: a masked VPU reduce a key cell and value
  row (`_masked_sums`: no MXU, no slabs, int32 counts) up to `masked_cap`, a
  handful of keys (a status, a flag, a region: TPC-H Q1); the one-hot matmul
  `[rows, N] @ [N, keys]` from there to `matmul_cap` (XLA fuses the
  iota-compare into the dot's tiles), the CHUNKED 64x64-tile matmul
  `_grouped_chunk64` from there to
  `chunk_cap` (high-cardinality group-by AND the grouped-distinct presence
  product space, bf16 3-part-split operands at full MXU tile utilization),
  past `chunk_cap` the radix-partitioned sort `_grouped_partitioned` (no n-row
  scatter), and past `dense_keys` the same sort answering from its sorted
  groups alone (`_grouped_sparse`: no table of the key space, the ORDER BY
  ... LIMIT cut on the device where the partial is whole). The row count
  chooses nothing: the two matmul regimes contract over the rows that
  passed, compacted tile by tile where few tiles hold many of them
  (`_compacted_sums`), else over every row, past SLAB_ROWS (2^24) rows a
  device slab by slab (`_slab_sums`), int32 counts added across the
  slabs; min/max by per-key broadcast-reduce up to
  `minmax_bcast_cap`, `segment_min` / `segment_max` above.

There is no 10k-doc batching loop (`DocIdSetPlanNode.MAX_DOC_PER_CALL`): the TPU analog of
batching is the grid XLA tiles over the padded row axis. Kernels are cached by structural
signature; literal operands arrive via runtime scalar arrays so changing `WHERE x > 5` to
`x > 7` reuses the compiled program.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query import stats as qstats
from ..query.aggregates import AggFunc
from ..query.predicate import CmpLeaf, DocSetLeaf, FilterProgram, LutLeaf, NullLeaf
from ..sql.ast import Identifier
from ..utils.memledger import get_ledger
from ..utils.metrics import get_registry
from .caps import get_caps
from .expr import eval_expr, widen_marks

_INT_MIN_IDENT = np.iinfo(np.int32).max  # identity for masked-out min over int
_INT_MAX_IDENT = np.iinfo(np.int32).min

# kernel outputs that are masked sums of integer powers of the argument
_POWER_SUMS = {"sum": 1, "sum2": 2, "sum3": 3, "sum4": 4}

# The crossovers of the GROUP BY, min/max, bitmap, fused-decode and join
# ladders are the fields of `engine/caps.py`'s `KernelCaps`, the only place
# those numbers are written; `_make_body` reads them through `get_caps()`.
DENSE_LUT_MATMUL_CAP = 8192  # scattered-LUT membership via one-hot matmul
PRESENCE_MATMUL_CAP = 8192   # _presence_2d chunked presence counts
# A fused dict column whose padded decode table has at most this many entries
# (`W`, the static last dimension of the table: a shape, so the choice is made
# at trace time from the input) is decoded by a balanced tree of selects over
# the table's entries (`_decode_select`), which fuses into the consuming scan;
# a wider table keeps the gather (`_decode_gather`), which the v5e runs as a
# fusion of its own that writes the whole decoded column at ~100M rows/s
# whatever `W`. Measured on the v5e (PERF.md, PR 27, chip call c1: a
# Q1.1-shaped scan of four int32 columns, solo; ms at 16Mi / 64Mi rows):
#      W    gather       select tree    the tree's compile, s (gather's: ~1)
#     16    210 / 665    0.6 /  1.7      0.3 /  0.4
#     64    210 / 665    1.0 /  1.8      0.9 /  1.0
#    256    208 / 789    3.6 /  6.7      8.5 / 13.4
#   1024    208 / 789   15.2 / 27.7     30.4 / 43.8
# The run times do not cross by 1024 (extrapolated: near W = 24,000). What
# bounds the cap is the unrolled tree's compile: at 1024 it is longer than any
# program this server compiles today (the 64Mi-row sorts, 22-37 s) and a cold
# shape stalls the serial device pipeline that long; 256 stays under them.
# A constant of the program, not a `KernelCaps` field: no test needs another
# value through `set_caps`, so it has no place in `signature()`.
SELECT_DECODE_CAP = 256


@dataclass
class KernelSpec:
    """Static description of one fused kernel (the jit cache key is `signature()`)."""

    filter: FilterProgram
    group_cols: Tuple[str, ...]            # dict-encoded group-by columns
    num_keys_pad: int   # >= product of real cardinalities (pow2 to 4096, then 4096-multiples)
    aggs: Tuple[Tuple[AggFunc, Tuple[str, ...]], ...]  # (func, device outputs)
    distinct_lut_sizes: Dict[int, int] = field(default_factory=dict)  # agg idx -> lut size
    padded_rows: int = 0
    # LUT-leaf columns that are multi-value: their ids arrive as [rows, W] matrices
    # and leaf masks reduce any(-1). Static (not shape-inferred): the mesh path's
    # stacked [segments, rows] arrays are also 2-D but are NOT multi-value.
    mv_cols: Tuple[str, ...] = ()
    # leaf indices the planner routed to the packed-word bitmap index: the leaf
    # evaluates as an OR-reduce over `bitmap_words` rows instead of an id
    # gather/one-hot, with the boolean LUT riding along as the runtime row
    # selector. When EVERY leaf is a bitmap leaf the whole tree stays in the
    # word domain (fused AND/OR/NOT over uint32 words, one unpack at the end).
    bitmap_leaves: Tuple[int, ...] = ()
    # value columns the kernel decodes from their COMPRESSED resident form
    # in-register instead of reading a decoded HBM column: (col, form) pairs,
    # form "dict" (vals[col] is the padded decode table, ids[col] the dict
    # ids; `_fused_env` decodes a table of at most SELECT_DECODE_CAP entries
    # by selects inside the scan's fusion, so nothing is materialized, and a
    # wider one by a gather, which the v5e runs as a fusion of its own that
    # writes the decoded column to HBM) or "for"
    # (vals[col] is a narrow unsigned delta column; the frame-of-reference
    # base rides the int scalar stream at `for_offset[col]`). Empty = the
    # staged layout (vals[col] is the decoded column), so the flag is part of
    # `signature()` — fused and staged plans never share a compiled kernel.
    fused_cols: Tuple[Tuple[str, str], ...] = ()
    # what the plan knows of the value columns' integers (`expr.int_bounds`):
    # column -> (lo, hi), or None for a column that is not of integers. `+`,
    # `-` and `*` whose result can leave int32 are evaluated in float32
    # (`expr.widens`); `signature()` holds the choices (`_widen_marks`), never
    # the ranges, so segments whose min/max differ share a program.
    int_ranges: Dict[str, Optional[Tuple[int, int]]] = field(
        default_factory=dict)
    # the ORDER BY ... LIMIT a sparse GROUP BY (`sparse`) cuts on the device
    # where its partial is the whole answer: (k, ((source, desc), ...)), a
    # source ("out", an output's name: "count", "<i>.sum", "<i>.min", ...)
    # or ("key", group column j); () = no cut (`executor.sparse_trim_spec`)
    trim: Tuple = ()

    # per-leaf runtime input routing, computed in __post_init__
    lut_index: Dict[int, int] = field(default_factory=dict)       # dense (scattered) LUTs
    lut_interval: Dict[int, Tuple[int, int]] = field(default_factory=dict)  # (ioff, n)
    cmp_offset: Dict[int, Tuple[str, int]] = field(default_factory=dict)
    docset_index: Dict[int, int] = field(default_factory=dict)
    bitmap_index: Dict[int, int] = field(default_factory=dict)
    for_offset: Dict[str, int] = field(default_factory=dict)  # FOR base in iscal

    def __post_init__(self):
        luts = docsets = 0
        ioff = foff = 0
        for i, leaf in enumerate(self.filter.leaves):
            if isinstance(leaf, LutLeaf):
                if i in self.bitmap_leaves:
                    # word-matrix input + runtime row-selector LUT
                    self.bitmap_index[i] = len(self.bitmap_index)
                    self.lut_index[i] = luts
                    luts += 1
                elif leaf.intervals is not None:
                    # interval bounds ride the int scalar stream: [lo0,hi0,lo1,hi1,...]
                    self.lut_interval[i] = (ioff, len(leaf.intervals))
                    ioff += 2 * len(leaf.intervals)
                else:
                    self.lut_index[i] = luts
                    luts += 1
            elif isinstance(leaf, DocSetLeaf):
                self.docset_index[i] = docsets
                docsets += 1
            elif isinstance(leaf, CmpLeaf):
                if leaf.is_int:
                    self.cmp_offset[i] = ("iscal", ioff)
                    ioff += len(leaf.operands)
                else:
                    self.cmp_offset[i] = ("fscal", foff)
                    foff += len(leaf.operands)
        # FOR bases ride the int scalar stream AFTER every filter scalar, in
        # fused_cols order (input staging appends them in the same order)
        for col, form in self.fused_cols:
            if form == "for":
                self.for_offset[col] = ioff
                ioff += 1

    def signature(self) -> Tuple:
        return (
            self.filter.signature(),
            self.group_cols,
            self.num_keys_pad,
            tuple((a.name, repr(a.arg), outs) for a, outs in self.aggs),
            tuple(sorted(self.distinct_lut_sizes.items())),
            self.padded_rows,
            self.mv_cols,
            self.bitmap_leaves,
            self.fused_cols,
            _filter_widen_marks(self), _agg_widen_marks(self),
            # regime caps change the traced program for the same plan shape
            get_caps().token(),
            self.trim,
        )


@dataclass
class KernelInputs:
    """Runtime (traced) inputs for one segment execution."""

    ids: Dict[str, jnp.ndarray]
    vals: Dict[str, jnp.ndarray]
    luts: Tuple[jnp.ndarray, ...]
    iscal: jnp.ndarray
    fscal: jnp.ndarray
    nulls: Dict[str, jnp.ndarray]
    valid: jnp.ndarray
    strides: jnp.ndarray  # i32[G] (empty for scalar aggregation)
    agg_luts: Dict[str, jnp.ndarray] = field(default_factory=dict)  # "<i>.bucket"/"<i>.rank"
    docsets: Tuple[jnp.ndarray, ...] = ()  # padded bool[P] per DocSetLeaf
    bitmaps: Tuple[jnp.ndarray, ...] = ()  # uint32[k_pow2, P//32] per bitmap leaf
    # packed `valid` (uint32[P//32], same bit layout as bitmap rows) for the
    # popcount fast path; None when a runtime valid-doc intersection (upsert)
    # makes the packed form stale — the count path then packs `valid` itself
    valid_words: Optional[jnp.ndarray] = None


_KERNEL_CACHE: Dict[Tuple, Any] = {}


def kernel_cache_size() -> int:
    return len(_KERNEL_CACHE)


def _block_tree(out):
    """Fence: wait until every leaf of a device output tree is ready."""
    fence = getattr(jax, "block_until_ready", None)
    if fence is not None:
        return fence(out)
    for leaf in jax.tree_util.tree_leaves(out):  # jax < 0.4 compat
        getattr(leaf, "block_until_ready", lambda: None)()
    return out


def _fence_first_call(fn):
    """jax.jit is LAZY — trace + compile happen at the first invocation. Fence
    that call with block_until_ready so its wall time (trace + compile + first
    run) lands in the compile histogram / per-query `compileMs` instead of
    silently inflating whichever query hits the cold cache; every invocation
    counts one device launch."""
    state: Dict[str, Any] = {"cold": True}

    def call(*args, **kwargs):
        qstats.record(qstats.DEVICE_LAUNCHES)
        get_registry().counter("pinot_kernel_launches").inc()
        if state["cold"]:
            state["cold"] = False
            t0 = time.perf_counter()
            out = _block_tree(fn(*args, **kwargs))
            ms = (time.perf_counter() - t0) * 1000
            get_registry().histogram("pinot_kernel_compile_ms").observe(ms)
            qstats.record(qstats.COMPILE_MS, ms)
            return out
        return fn(*args, **kwargs)

    call.__wrapped__ = fn  # the jitted callable (AOT lowering in tests)
    return call


def _cached_kernel(key: Tuple, build) -> Any:
    """Single gate for the compiled-kernel cache: counts hits/misses into the
    process registry AND the active per-query ExecutionStats, and wraps fresh
    entries with the first-call compile fence."""
    fn = _KERNEL_CACHE.get(key)
    if fn is None:
        qstats.record(qstats.COMPILE_CACHE_MISSES)
        get_registry().counter("pinot_kernel_cache_misses").inc()
        fn = _fence_first_call(build())
        _KERNEL_CACHE[key] = fn
    else:
        qstats.record(qstats.COMPILE_CACHE_HITS)
        get_registry().counter("pinot_kernel_cache_hits").inc()
    return fn


def fetch_outputs(outs_dev):
    """`jax.device_get` with execution accounting: dispatch is async, so the
    wall spent blocking HERE is the kernel's device-exec + transfer time —
    observed into the exec histogram and the per-query `deviceExecMs` /
    `bytesFetched`."""
    t0 = time.perf_counter()
    out = jax.device_get(outs_dev)
    ms = (time.perf_counter() - t0) * 1000
    get_registry().histogram("pinot_kernel_exec_ms").observe(ms)
    qstats.record(qstats.DEVICE_EXEC_MS, ms)
    fetched = tree_bytes(out)
    qstats.record(qstats.BYTES_FETCHED, fetched)
    get_ledger().note_transient(fetched)
    return out


def tree_bytes(tree) -> int:
    """Total host bytes of a fetched output tree."""
    return sum(int(np.asarray(leaf).nbytes)
               for leaf in jax.tree_util.tree_leaves(tree))


def _bitmap_leaf_words(spec: KernelSpec, i: int, bitmaps) -> jnp.ndarray:
    """One bitmap leaf in the word domain: OR-fold of the PRE-SELECTED word
    rows. Input staging (`_kernel_inputs`) gathers only the dict-id rows the
    leaf's LUT selects and pads the row count to a power of two by repeating
    one selected row — OR is idempotent, so the padding never changes the
    result, and the pow2 shapes bound retraces to log2(card) variants. Word
    traffic is k * P/32 (k = selected ids), proportional to the leaf's
    selectivity instead of the column's cardinality."""
    bm = bitmaps[spec.bitmap_index[i]]            # uint32 [k_pow2, P//32]
    out = bm[0]
    for j in range(1, bm.shape[0]):
        out = out | bm[j]
    return out


def _unpack_words(words: jnp.ndarray) -> jnp.ndarray:
    """uint32[W] packed bits -> bool[32 * W] row mask (shift + reshape, no gather)."""
    bits = (words[:, None] >> jnp.arange(32, dtype=jnp.uint32)[None, :]) \
        & jnp.uint32(1)
    return bits.reshape(-1) != 0


def _pack_valid(valid: jnp.ndarray) -> jnp.ndarray:
    """bool[P] -> uint32[P//32] packed words (P is always a multiple of 32:
    padded rows are pow2 >= ROW_TILE)."""
    v = valid.ravel().astype(jnp.uint32).reshape(-1, 32)
    return jnp.sum(v << jnp.arange(32, dtype=jnp.uint32)[None, :], axis=1,
                   dtype=jnp.uint32)


def _make_word_fn(spec: KernelSpec):
    """words(bitmaps) -> uint32[P//32] evaluating the WHOLE filter tree
    in the packed word domain, or None unless every leaf is a bitmap leaf.
    NOT sets padding bits; callers AND the result with the packed valid mask."""
    leaves = spec.filter.leaves
    if spec.filter.is_match_all or not leaves:
        return None
    if set(spec.bitmap_index) != set(range(len(leaves))):
        return None

    def tree_words(node, bitmaps):
        kind = node[0]
        if kind == "leaf":
            return _bitmap_leaf_words(spec, node[1], bitmaps)
        if kind == "not":
            return ~tree_words(node[1], bitmaps)
        words = [tree_words(c, bitmaps) for c in node[1]]
        out = words[0]
        for w in words[1:]:
            out = (out & w) if kind == "and" else (out | w)
        return out

    tree = spec.filter.tree
    if tree[0] == "const":  # _simplify folds consts away except all/none
        return None
    return lambda bitmaps: tree_words(tree, bitmaps)


def _decode_gather(lut, idx):
    """Dictionary decode as one LUT gather over the ids: a 1-D table, or the
    stacked mesh form's one table PER SEGMENT ([s, W] against [s, rows]). On
    the v5e the gather does NOT fuse into the scan: it is a fusion of its own
    whose output is the whole decoded column (PR 26's traces: `s32[16,16]` ->
    `s32[67108864]` in 665 ms, about 100M rows a second at any `W`)."""
    if lut.ndim == 2 and idx.ndim == 2:
        return jnp.take_along_axis(lut, idx, axis=1)
    return lut[idx]


def _decode_select(lut, idx):
    """The same decode with no gather, for a small table: a balanced tree of
    selects over the table's `W` entries (a power of two, `lut_size`), level
    `b` choosing between neighbours by bit `b` of the id — `W - 1` selects and
    `log2 W` bit tests a row, all elementwise, so XLA fuses them into the
    consuming reduction and the decoded column never exists in HBM. Entry `k`
    is a scalar for a 1-D table and a per-segment column `[s, 1]` for the
    stacked form. Exact: every id in `[0, W)` selects its own entry in the
    table's dtype (the fill id `cardinality` < `W` reads the padded zero, as
    the gather does); ids are never outside `[0, W)`."""
    stacked = lut.ndim == 2
    level = [lut[:, k][:, None] if stacked else lut[k]
             for k in range(lut.shape[-1])]
    bit = 0
    while len(level) > 1:
        odd = (idx & (1 << bit)) != 0
        level = [jnp.where(odd, level[k + 1], level[k])
                 for k in range(0, len(level), 2)]
        bit += 1
    return jnp.broadcast_to(level[0], idx.shape)


def gather_free(spec: KernelSpec, vals) -> bool:
    """Whether a launch of `spec` over the staged `vals` decodes compressed
    forms in-kernel and none of them by a gather: every dict column's table is
    small enough for the selects (what `gatherFreeLaunches` counts)."""
    return bool(spec.fused_cols) and all(
        vals[col].shape[-1] <= SELECT_DECODE_CAP
        for col, form in spec.fused_cols if form == "dict")


def _filter_widen_marks(spec: KernelSpec) -> Tuple:
    return tuple(widen_marks(leaf.expr, spec.int_ranges)
                 for leaf in spec.filter.leaves if isinstance(leaf, CmpLeaf))


def _agg_widen_marks(spec: KernelSpec) -> Tuple:
    return tuple(widen_marks(agg.arg, spec.int_ranges)
                 for agg, outs in spec.aggs if "distinct" not in outs)


def widened(spec: KernelSpec) -> bool:
    """Whether a launch of `spec` evaluates an aggregate's argument widened
    (what `widenedAggLaunches` counts): some `+`, `-` or `*` of integers in it
    can leave int32 by the plan's ranges and is computed in float32."""
    return any(any(marks) for marks in _agg_widen_marks(spec))


def masked(spec: KernelSpec) -> bool:
    """Whether a launch of `spec` runs its GROUP BY's counts and sums as the
    masked VPU reduce (what `maskedGroupByLaunches` counts): at most
    `masked_cap` padded keys + 1, the bottom rung of `_make_body`'s ladder."""
    return (bool(spec.group_cols) and not sparse(spec)
            and spec.num_keys_pad + 1 <= get_caps().masked_cap)


def sparse(spec: KernelSpec) -> bool:
    """Whether a launch of `spec` answers its GROUP BY from its sorted groups
    (what `sparseGroupByLaunches` counts): a padded key space past
    `KernelCaps.dense_keys`, the ladder's top rung (`_grouped_sparse`)."""
    return (bool(spec.group_cols)
            and spec.num_keys_pad > get_caps().dense_keys)


def trimmed(spec: KernelSpec) -> bool:
    """Whether such a launch also cuts the ORDER BY ... LIMIT on the device
    (what `deviceTrimmedLaunches` counts): its partial is the whole answer."""
    return sparse(spec) and bool(spec.trim)


def slabbed(spec: KernelSpec, rows: int) -> bool:
    """Whether a launch of `spec` over `rows` rows a device (a static shape of
    its inputs) runs a program that HOLDS a matmul regime of its GROUP BY over
    more than one slab (what `slabbedLaunches` counts): past SLAB_ROWS rows,
    more than `masked_cap` keys (the masked reduce counts in int32 and builds
    no slab) and at most `chunk_cap`. Where the group-by's own sums are
    compacted in front of the rung (`_compacted_sums`) the loop is the
    fall-back's alone: the launch may not have run it (`fullMatmulLaunches`
    says it did). A grouped distinct's product space is the wider of the
    two, so past `masked_cap` it is on the matmul side only where the
    group-by itself is; under it the presence product alone decides."""
    if not spec.group_cols or slab_count(rows) == 1:
        return False
    num_seg, caps = spec.num_keys_pad + 1, get_caps()
    if not masked(spec):
        return num_seg <= caps.chunk_cap
    return any(num_seg * size <= caps.chunk_cap
               for _, size in spec.distinct_lut_sizes.items())


def _fused_env(spec: KernelSpec, ids, vals, iscal):
    """The expression env over COMPRESSED resident forms: for every fused
    column, synthesize the decoded values at trace time — a dict column from
    its ids and its decode table, by selects where the table is small
    (`_decode_select`: in-register, fused into the scan) and by a gather where
    it is not (`_decode_gather`: a pass of its own that writes the decoded
    column), a FOR column as delta + base. Non-fused columns pass through
    (staged layout: already decoded). The stacked mesh form carries one decode
    table PER SEGMENT ([s, W] sharded on the segment axis, like every other
    per-segment operand)."""
    if not spec.fused_cols:
        return vals
    env = dict(vals)
    for col, form in spec.fused_cols:
        if form == "dict":
            lut, idx = vals[col], ids[col]
            if lut.shape[-1] <= SELECT_DECODE_CAP:
                with jax.named_scope("pinot.decode.select"):
                    env[col] = _decode_select(lut, idx)
            else:
                with jax.named_scope("pinot.decode.gather"):
                    env[col] = _decode_gather(lut, idx)
        else:  # "for": narrow unsigned deltas + scalar-stream base
            with jax.named_scope("pinot.decode"):
                env[col] = (vals[col].astype(jnp.int32)
                            + iscal[spec.for_offset[col]])
    return env


def _make_mask_fn(spec: KernelSpec):
    """Returns mask(ids, vals, luts, iscal, fscal, nulls, valid) -> bool[P] closure."""
    leaves = spec.filter.leaves
    word_fn = _make_word_fn(spec)

    def leaf_mask(i, ids, vals, luts, iscal, fscal, nulls, docsets, bitmaps):
        leaf = leaves[i]
        if isinstance(leaf, LutLeaf):
            if i in spec.bitmap_index:
                # mixed tree: unpack this leaf's words to a row mask and
                # combine with the other leaves in the row domain
                return _unpack_words(_bitmap_leaf_words(spec, i, bitmaps))
            col_ids = ids[leaf.col]
            # multi-value column: [P, W] id matrix; a row matches if ANY of its
            # values does (reference: MVScanDocIdIterator), so per-value masks
            # reduce with any(-1). The fill id (= cardinality) maps to False in
            # every LUT and lies above every interval hi.
            mv = leaf.col in spec.mv_cols

            def _reduce(m):
                return m.any(axis=-1) if mv else m
            if i in spec.lut_interval:
                # id-interval membership: OR of range compares, zero gathers
                off, n = spec.lut_interval[i]
                if n == 0:
                    return _reduce(jnp.zeros(col_ids.shape, dtype=bool))
                m = (col_ids >= iscal[off]) & (col_ids <= iscal[off + 1])
                for j in range(1, n):
                    m = m | ((col_ids >= iscal[off + 2 * j])
                             & (col_ids <= iscal[off + 2 * j + 1]))
                return _reduce(m)
            lut = luts[spec.lut_index[i]]
            if len(lut) <= DENSE_LUT_MATMUL_CAP:
                # scattered-set membership as a one-hot matvec (gather-free; the
                # one-hot fuses into the dot's tiles, it is never materialized)
                oh = jax.nn.one_hot(col_ids.ravel(), len(lut), dtype=jnp.float32)
                return _reduce((oh @ lut.astype(jnp.float32) > 0.5)
                               .reshape(col_ids.shape))
            return _reduce(lut[col_ids])  # huge scattered LUT: gather (rare)
        if isinstance(leaf, DocSetLeaf):
            return docsets[spec.docset_index[i]]
        if isinstance(leaf, NullLeaf):
            m = nulls[leaf.col]
            return ~m if leaf.negated else m
        assert isinstance(leaf, CmpLeaf)
        v = eval_expr(leaf.expr, vals, jnp, spec.int_ranges)
        arr_name, off = spec.cmp_offset[i]
        sc = iscal if arr_name == "iscal" else fscal
        if leaf.op == "eq":
            return v == sc[off]
        if leaf.op == "gte":
            return v >= sc[off]
        if leaf.op == "lte":
            return v <= sc[off]
        if leaf.op == "gt":
            return v > sc[off]
        if leaf.op == "lt":
            return v < sc[off]
        if leaf.op == "between":
            return (v >= sc[off]) & (v <= sc[off + 1])
        if leaf.op == "in":
            m = v == sc[off]
            for j in range(1, len(leaf.operands)):
                m = m | (v == sc[off + j])
            return m
        raise AssertionError(f"bad cmp op {leaf.op}")

    def tree_mask(node, env, valid):
        kind = node[0]
        if kind == "const":
            # _simplify folds consts away except a top-level all/none
            return valid if node[1] else jnp.zeros_like(valid)
        if kind == "leaf":
            return leaf_mask(node[1], *env)
        if kind == "not":
            return ~tree_mask(node[1], env, valid)
        masks = [tree_mask(c, env, valid) for c in node[1]]
        out = masks[0]
        for m in masks[1:]:
            out = (out & m) if kind == "and" else (out | m)
        return out

    def mask_fn(ids, vals, luts, iscal, fscal, nulls, valid, docsets=(),
                bitmaps=()):
        if spec.filter.is_match_all:
            return valid
        with jax.named_scope("pinot.filter"):
            if word_fn is not None:
                # every leaf is a bitmap leaf: the tree evaluates as fused
                # bitwise ops over packed words, one unpack for the row mask
                # at the end
                return _unpack_words(word_fn(bitmaps) & _pack_valid(valid))
            env = (ids, vals, luts, iscal, fscal, nulls, docsets, bitmaps)
            return tree_mask(spec.filter.tree, env, valid) & valid

    return mask_fn


def _presence_2d(fmask: jnp.ndarray, col_ids: jnp.ndarray, size: int) -> jnp.ndarray:
    """Per-dict-id masked row counts as a REAL MXU matmul (~1.0B rows/s
    measured CSE-proof at size=4096, ~15x the one-hot matvec it replaces;
    an earlier 28B figure came from a repeat-and-divide harness XLA could
    dedupe and overstated it ~15x — r5 re-measured with data-dependent
    chaining: 16.5ms per 16M rows).

    A [1, N] @ one_hot[N, K] histogram has zero operand reuse — XLA streams
    N*K compare-accumulate work through the VPU (~66ms for N=16M, K=4096).
    Decomposing the id into digits, id = 64*hi + lo, turns the same histogram
    into `one_hot(hi)^T @ (fmask * one_hot(lo))`: a [64, N] @ [N, 64] matmul
    whose output cell (hi, lo) is exactly count(id == 64*hi+lo, mask) — a
    64x64-output contraction is a full MXU tile (both one-hots fuse into the
    dot's operand tiles, nothing is materialized), and the remaining cost is
    the contraction stream itself: the N-length contraction walks at ~8
    elements/cycle/MXU whatever the output size, ~2ms per output tile per
    16M rows on v5e. bf16 operands are EXACT here: every input is 0/1 or a
    0/1-masked 0/1. Sizes above 4096 split into 4096-wide chunks, one dot
    per chunk, rows routed to their chunk by zeroing fmask elsewhere.
    Returns f32 counts[size] (exact to 2^24 per cell per device)."""
    bf = jnp.bfloat16
    if size >= 4096:
        hi_w = lo_w = 64
    else:
        lo_w = min(64, size)
        hi_w = -(-size // lo_w)
    low = col_ids & 4095
    chunks = []
    for c in range(max(1, -(-size // 4096))):
        fm = fmask if size <= 4096 else \
            jnp.where((col_ids >> 12) == c, fmask, 0.0)
        oh_hi = jax.nn.one_hot(low // lo_w, hi_w, dtype=bf)
        oh_lo = jax.nn.one_hot(low % lo_w, lo_w, dtype=bf) \
            * fm[:, None].astype(bf)
        chunks.append(jax.lax.dot_general(
            oh_hi, oh_lo, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(-1))
    counts = jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0]
    return counts[:size]


def _masked_sums(key: jnp.ndarray, num_seg: int, rows):
    """[int32 counts[num_seg], f32 sums[num_seg]...] of a GROUP BY over a
    handful of key cells, on the VPU: for each real key cell `k` the compare
    `key == k`, the count an int32 sum of it (exact at any row count: no f32
    cell, so no slabs and no loop) and each value row's sum one f32 reduce of
    the rows the compare selects. `rows[0]` is the count row (the 0/1 mask),
    which the compare stands in for: masked-out rows carry the overflow key
    `num_seg - 1`, whose cell is filled with the zero it would sum to, as
    `_grouped_chunk64` fills it.

    FLAT, one reduce a cell and row, which XLA fuses into few passes over the
    rows (TPC-H Q1 at 67M rows on the v5e, 9 cells and 7 value rows: 8.1 ms
    and 1.6e-7 of the int64 sums, where the one-hot regime in four slabs read
    66.2 ms and 9.3e-6; PERF.md, PR 36; served, PR 37: Q1 alone 8.88 ms for
    72.08). NOT two levels (blocks of 2^12 rows
    summed first): XLA did not fold them, 14.9 ms and five times wider. No
    bf16 parts: the f32 rows are added as they are. Linear in cells x rows,
    so it is the ladder's bottom rung alone (`KernelCaps.masked_cap`)."""
    hits = [key == k for k in range(num_seg - 1)]
    cells = lambda per_hit: jnp.pad(jnp.stack(per_hit), (0, 1))  # noqa: E731
    return [cells([jnp.sum(h, dtype=jnp.int32) for h in hits])] + [
        cells([jnp.sum(jnp.where(h, r, 0.0)) for h in hits])
        for r in rows[1:]]


def _onehot_sums(key: jnp.ndarray, num_seg: int, rows) -> jnp.ndarray:
    """f32[len(rows), num_seg] per-key sums of `rows`: the skinny one-hot matmul
    [R, N] @ one_hot(key)[N, num_seg], its f32 value rows carried as three
    exact bf16 parts (`_bf16_parts`) against a bf16 one-hot, f32 accumulation
    — the operand form `_grouped_chunk64` uses.

    NOT one f32 `Precision.HIGHEST` dot: on the v5e that contraction loses
    with its length — every sum 6e-6 LOW over 1Mi rows and 6e-4 LOW over 16Mi
    (chip probe and served group-by, PR 22; the CPU backend is exact) — and
    blocking it does not help, because XLA folds a batched dot plus the sum
    over its batch back into the one long contraction. The three-part form
    measured 1.6e-6 over 16Mi rows on the chip. The one-hot is not
    materialized: its iota-compare fuses into the dot's operand tiles."""
    oh = jax.nn.one_hot(key, num_seg, dtype=jnp.bfloat16)
    parts = zip(*[_bf16_parts(r) for r in rows])     # 3 x [R rows of bf16]
    return sum(jax.lax.dot(jnp.stack(p), oh,
                           preferred_element_type=jnp.float32)
               for p in parts)


def _bf16_parts(v: jnp.ndarray):
    """f32 `v` as three bf16 parts v1 + v2 + v3, each the bf16 rounding of the
    remaining residual (3 x 8 mantissa bits = full f32 per-element precision).

    Rounded with `lax.reduce_precision`, NOT an f32 -> bf16 -> f32 convert
    round trip: the TPU compiler may carry a convert pair inside a fusion in
    excess (f32) precision, which zeroes the residuals and leaves the sums
    with ONE bf16 rounding per element (seen on the v5e: 20k-key sums off by
    ~3e-5 relative). `reduce_precision` is the op XLA must not elide."""
    def bf16_round(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    p1 = bf16_round(v)
    rem = v - p1
    p2 = bf16_round(rem)
    p3 = bf16_round(rem - p2)
    return tuple(p.astype(jnp.bfloat16) for p in (p1, p2, p3))


def _grouped_chunk64(key: jnp.ndarray, nseg: int, exact_rows, split_rows,
                     real: Optional[int] = None):
    """Per-key sums over a LARGE dense key space as chunked 64x64-tile one-hot
    matmuls — the high-cardinality GROUP BY kernel (8192 < keys <= 128k).

    Each 4096-key chunk c decomposes the in-chunk key into two 64-wide digits
    and computes sums[hi, lo] = one_hot(hi)^T @ (row * one_hot(lo)) — a
    [64, N] @ [N, 64] contraction whose 64x64 output is an MXU tile (the
    `_presence_2d` design, extended from presence counts to value sums).
    Rows NOT exactly representable in bf16 are split into THREE bf16 parts
    v = v1 + v2 + v3 (each part the bf16 rounding of the remaining residual):
    3 x ~8 mantissa bits recovers full f32 per-element precision (2^-24),
    so this path's sums match the skinny f32-HIGHEST matmul's — a two-part
    split (2^-17 per element) was measurably worse on large-magnitude
    integer columns. Three bf16 dots with f32 accumulation per sum row.

    The hard limit of ANY one-hot formulation here is the MXU contraction
    stream, not FLOPs: a [64, N] @ [N, 64] dot walks the N-length contraction
    regardless of its tiny output, once per 4096-key chunk and operand part
    (16.8M rows, count and one sum, on the v5e: 7.8 ms for one chunk, 14.6
    for two; PERF.md section 5, PR 31's probe).

    The contract: `key` routes masked-out rows to the keys from `real` up
    (callers pass the kernel's dense key with the one overflow key nseg-1,
    `real` = nseg-1 by default; a grouped distinct's combined key has an
    overflow BAND), and every row handed in is ALREADY MASKED by the caller:
    zero wherever the key is `real` or more. So the chunks cover the real
    keys [0, real) alone and the cells from there to nseg are filled with the
    zeros they would sum to: nseg 8,193 is two passes over the rows, not a
    third for the overflow cell. f32 accumulator cells are exact to 2^24
    increments: past that many rows callers go slab by slab (`_slab_sums`).
    Returns f32[nseg] per row, exact_rows first.
    """
    bf = jnp.bfloat16
    real = nseg - 1 if real is None else real
    n_chunks = max(1, -(-real // 4096))
    low = key & 4095
    oh_hi = jax.nn.one_hot(low // 64, 64, dtype=bf)
    oh_lo = jax.nn.one_hot(low % 64, 64, dtype=bf)
    dot = lambda a, b: jax.lax.dot_general(          # noqa: E731
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    splits = [_bf16_parts(r) for r in split_rows]
    pieces: list = [[] for _ in range(len(exact_rows) + len(split_rows))]
    for c in range(n_chunks):
        in_c = (key >> 12) == c
        for i, r in enumerate(exact_rows):
            rc = jnp.where(in_c, r.astype(bf), 0)
            pieces[i].append(dot(oh_hi, rc[:, None] * oh_lo).reshape(-1))
        for j, parts in enumerate(splits):
            s = None
            for rp in parts:
                d = dot(oh_hi, (jnp.where(in_c, rp, 0))[:, None] * oh_lo)
                s = d if s is None else s + d
            pieces[len(exact_rows) + j].append(s.reshape(-1))
    short = max(nseg - n_chunks * 4096, 0)   # cells past the chunks: zeros
    return [jnp.pad(jnp.concatenate(p), (0, short))[:nseg] for p in pieces]


# Rows an f32 one-hot cell counts exactly: 2^24 increments (2^24 itself IS
# representable, so a slab of exactly 2^24 rows of one key still reads
# right). A property of f32, not a crossover to tune: no `KernelCaps` field,
# key, variable or argument feeds it; tests patch it as they patch
# COMPACT_RUNGS.
SLAB_ROWS = 1 << 24
# Rows a slab of the COMPACTED rows' contraction (`_compacted_sums`). A
# contraction adds its terms into one f32 accumulator a cell, so a sum's
# error grows with the terms of its group that one accumulator takes: the
# rows that passed, compacted, put a group's rows into one contraction where
# every row put them into four slabs of 2^24. On the v5e at 2^26 rows, 31,944
# rows a group (PERF.md section 6): one contraction 14.4 ms and 2.8e-6 of
# the exact sums, slabs of 2^20 15.3 ms and 2.3e-7, of 2^18 14.4 ms and
# 2.0e-7, every row in four slabs 57.7 ms and 3.8e-7. Tests patch it.
COMPACT_SLAB_ROWS = 1 << 18


def slab_count(rows: int, slab: int = 0) -> int:
    """Slabs the matmul GROUP BY regimes run over `rows` rows a device, in
    slabs of at most `slab` rows (SLAB_ROWS where 0)."""
    return max(1, -(-rows // (slab or SLAB_ROWS)))


def _slab_sums(key: jnp.ndarray, nseg: int, rows, regime, slab: int = 0):
    """[int32 counts[nseg], f32 sums[nseg]...] of a matmul GROUP BY regime at
    ANY row count. `regime(key, rows)` is `_onehot_sums` or `_grouped_chunk64`
    bound to its key count: f32[nseg] per row of `rows`, whose first is the
    0/1 mask (the count row). `slab`: rows a slab at most, SLAB_ROWS where 0
    (`COMPACT_SLAB_ROWS` for the compacted rows).

    An f32 cell stops counting at 2^24 increments, which is a property of one
    accumulator cell and not of the algorithm: past SLAB_ROWS rows the rows
    go in `slab_count` slabs of equal length (the tail padded with the
    overflow key and zero rows, as `_sort_by_key` pads), each slab's count
    row is rounded to int32 BEFORE it is added to the running int32 counts,
    and the slabs' f32 sum rows are added in slab order, as a mesh adds its
    chips' partials. A `fori_loop` whose body slices slab i out of the flat
    rows: XLA folds a batched dot plus the sum over its batch back into one
    long contraction (`_onehot_sums`), and cannot fold a loop; the body is
    traced and compiled once, and its temporaries are one slab's. NOT a
    `lax.scan` over a [slabs, slab] view: it runs the same, but the v5e's
    compiler takes 125-141 s over the 8,193-key program at 67M rows in that
    form against 3.9 s in this one and 8.5 s for a Python loop over static
    slices (compiled for the described chip, PR 31). One slab builds no
    loop."""
    as_int = lambda c: jnp.round(c).astype(jnp.int32)    # noqa: E731
    k = slab_count(key.size, slab)
    if k == 1:
        count, *sums = regime(key, rows)
        return [as_int(count)] + sums
    slab = -(-key.size // k)
    pad = k * slab - key.size
    if pad:
        key = jnp.pad(key, (0, pad), constant_values=nseg - 1)
        rows = [jnp.pad(r, (0, pad)) for r in rows]

    def add_slab(i, total):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(    # noqa: E731
            a, i * slab, slab)
        count, *sums = regime(cut(key), [cut(r) for r in rows])
        return [total[0] + as_int(count)] + [
            t + s for t, s in zip(total[1:], sums)]

    zero = [jnp.zeros((nseg,), jnp.int32)] + [
        jnp.zeros((nseg,), jnp.float32)] * (len(rows) - 1)
    chips = tuple(jax.typeof(key).vma)
    if chips:   # under shard_map the totals vary by chip, as the rows do
        zero = [jax.lax.pcast(z, chips, to="varying") for z in zero]
    return jax.lax.fori_loop(0, k, add_slab, zero)


def _seg_sum_op(a, b):
    """Associative combine for segmented inclusive sums: (head flag, value).
    A set flag on the right element resets the running sum at segment heads."""
    fa, va = a
    fb, vb = b
    return fa | fb, jnp.where(fb, vb, va + vb)


def _sort_by_key(key: jnp.ndarray, nseg: int, value_rows, block: int):
    """Co-sort value rows by group key, padded to a multiple of `block`.

    Pad rows carry the overflow key (nseg-1 — the same bucket masked-out rows
    already route to) and zero values, so sorted-run boundaries for REAL keys
    are unaffected. Returns (sorted keys, sorted value rows, pad rows)."""
    n = key.size
    pad = (-n) % block
    if pad:
        key = jnp.concatenate([key, jnp.full((pad,), nseg - 1, key.dtype)])
        value_rows = [jnp.concatenate([r, jnp.zeros((pad,), r.dtype)])
                      for r in value_rows]
    ops = jax.lax.sort([key] + list(value_rows), num_keys=1)
    return ops[0], list(ops[1:]), pad


def _counts_from_sorted(key_s: jnp.ndarray, nseg: int, pad: int):
    """EXACT int32 per-key counts + run starts from a sorted key column.

    `left[k]` is the first sorted position with key >= k (binary search, no
    scatter), so counts[k] = left[k+1] - left[k] — integer arithmetic with no
    f32 accumulator, hence no 2^24-increment guard on the sort regime. The
    `pad` rows _sort_by_key appended all carry key nseg-1 and are deducted."""
    left = jnp.searchsorted(key_s, jnp.arange(nseg + 1, dtype=key_s.dtype))
    counts = (left[1:] - left[:-1]).astype(jnp.int32)
    if pad:
        counts = counts - jnp.where(
            jnp.arange(nseg) == nseg - 1, jnp.int32(pad), jnp.int32(0))
    return left, counts


def compact_cap(n: int, nseg: int, block: int) -> int:
    """How many sorted rows the compact decode reads at most, from shapes
    alone: n / 64 (the widest SSB template passes n / 630), or 0 where no
    branch is built because today's per-key decode is the cheaper of the two:
    its gathers, nseg x (log2 n + log2(n / block) + 5), against `cap` updates.
    8,193 keys over 67M rows (a shape the ladder sends here only under other
    caps) would decode with 0.4M gathers against 1M updates: one branch."""
    cap = n // 64
    steps = n.bit_length() + max(n // block, 1).bit_length() + 3  # the log2s + 5
    return cap if nseg * steps > cap > 0 else 0


# Shorter prefixes tried before `compact_cap` rows, where they are at most a
# quarter of it: a pass over 2^20 rows is 11.9 ms on the v5e, one over 2^17
# 2.3 ms and one over 2^13 1.0 ms (PR 29's chip probe), and Q3.4 passes 50 rows
COMPACT_RUNGS = (1 << 13, 1 << 17)


SCAN_WIDTH = 256  # rows a block of `_run_totals`


def _run_totals(head: jnp.ndarray, v: jnp.ndarray):
    """Running length (int32 [L]) and running sums (`v` [R, L] -> f32 [R, L])
    of every run of rows, restarting wherever `head` [L] is set; at a run's
    last row they are the run's. L is a multiple of SCAN_WIDTH.

    Two levels. Inside a block of SCAN_WIDTH rows, a masked max over the
    block's [W, W] triangle finds where row w's run began and a masked sum
    adds the rows since: two reduces XLA fuses (nothing of that size is
    written). Across blocks, the blocks' closing lengths and sums go through a
    segmented `associative_scan` over L / SCAN_WIDTH elements, and a block's
    rows before its first head add what the blocks before them carried.
    NOT a flat `associative_scan`, `cumsum` or `cummax` over L: they compute
    the same and run as fast, but the v5e's compiler takes its time over each
    by the row count (2^20 rows: 26-77 s apiece, against 0.8 s for the masked
    reduce and 1.2 s for the scan over 4,096 blocks; PR 29, compiled for the
    described chip). A sum is still added as a tree: a block's reduce, then
    about log2(L / SCAN_WIDTH) levels."""
    r, length = v.shape
    nb, w = length // SCAN_WIDTH, SCAN_WIDTH
    at = jnp.arange(w, dtype=jnp.int32)
    upto = jnp.tri(w, dtype=bool)                               # u <= w
    heads = head.reshape(nb, 1, w)
    # in-block row where w's run began; -1: in a block before this one
    began = jnp.max(jnp.where(heads & upto, at, -1), axis=-1)   # [nb, w]
    seen = began >= 0
    mine = upto & (at >= began[:, :, None])                     # [nb, w, u]
    sums = jnp.sum(jnp.where(mine[None], v.reshape(r, nb, 1, w), 0.0),
                   axis=-1)                                     # [r, nb, w]
    lengths = at - jnp.maximum(began, 0) + 1
    closed = seen[:, -1]
    _, carried = jax.lax.associative_scan(
        _seg_sum_op, (jnp.broadcast_to(closed, (r, nb)), sums[:, :, -1]),
        axis=1)
    _, carried_len = jax.lax.associative_scan(
        _seg_sum_op, (closed, lengths[:, -1]))
    owed = jnp.pad(carried[:, :-1], ((0, 0), (1, 0)))       # by the blocks before
    owed_len = jnp.pad(carried_len[:-1], (1, 0))
    return ((lengths + jnp.where(seen, 0, owed_len[:, None])).reshape(length),
            (sums + jnp.where(seen[None], 0.0, owed[:, :, None])
             ).reshape(r, length))


def _run_extremes(head: jnp.ndarray, v: jnp.ndarray, is_min: bool):
    """The least (`is_min`) or the greatest of `v` [L] over every run of rows
    restarting wherever `head` [L] is set, in `v`'s dtype (an int32 MIN of a
    yyyymmdd date past 2^24 stays exact): at a run's last row, the run's. The
    two levels of `_run_totals`, with a min or a max in place of the sum."""
    length = v.size
    nb, w = length // SCAN_WIDTH, SCAN_WIDTH
    if jnp.issubdtype(v.dtype, jnp.floating):
        ident = jnp.inf if is_min else -jnp.inf
    else:
        info = jnp.iinfo(v.dtype)
        ident = info.max if is_min else info.min
    fold = jnp.minimum if is_min else jnp.maximum
    at = jnp.arange(w, dtype=jnp.int32)
    upto = jnp.tri(w, dtype=bool)
    heads = head.reshape(nb, 1, w)
    began = jnp.max(jnp.where(heads & upto, at, -1), axis=-1)   # [nb, w]
    seen = began >= 0
    mine = upto & (at >= began[:, :, None])                     # [nb, w, u]
    cells = jnp.where(mine, v.reshape(nb, 1, w), ident)
    ext = jnp.min(cells, axis=-1) if is_min else jnp.max(cells, axis=-1)

    def seg_op(a, b):
        (fa, va), (fb, vb) = a, b
        return fa | fb, jnp.where(fb, vb, fold(va, vb))
    _, carried = jax.lax.associative_scan(seg_op, (seen[:, -1], ext[:, -1]))
    owed = jnp.concatenate([jnp.full((1,), ident, v.dtype), carried[:-1]])
    return jnp.where(seen, ext, fold(ext, owed[:, None])).reshape(length)


def _compact_decode(key_c: jnp.ndarray, vals_c, m, nseg: int, rows: int):
    """Dense [nseg] counts and sums from the sorted PREFIX `key_c`, `vals_c`
    (the first rows of the sorted array), of which the first `m` passed the
    filter; rows past m carry the overflow key nseg-1 and zero values. Work is
    set by the prefix's length, not by nseg: `_run_totals` gives every run's
    length and sums at its last row (added as a tree, so a run of a million
    rows keeps f32's per-element precision), and one scatter per output SETS
    the run tails into zeros[nseg] — a run has one tail, so the indices are
    unique, and every other row is sent out of bounds and dropped (on the v5e
    3.8 ms in all for 2^18 rows and 11.9 for 2^20, against 6.7 and 24.3 with
    a scatter-add of the tails among zeros, and 5.6 and 19.3 for a plain
    scatter-add of every row, which adds a run in row order; PR 29's chip
    probe). Counts are run
    lengths, int32 throughout. `rows` is the real (unpadded) row count: the
    overflow bucket holds rows - m, as the dense decode's does.
    Returns [int32 counts[nseg], f32 sums[nseg]...]."""
    short = (-key_c.size) % SCAN_WIDTH
    if short:   # more rows that did not pass
        key_c = jnp.pad(key_c, (0, short), constant_values=nseg - 1)
        vals_c = [jnp.pad(v, (0, short)) for v in vals_c]
    length = key_c.size
    pos = jnp.arange(length, dtype=jnp.int32)
    nxt = jnp.concatenate([key_c[1:], jnp.full((1,), nseg - 1, key_c.dtype)])
    # a live run ends where the next row has another key; past m every row
    # has the overflow key, which no live row has. The prefix's last row
    # ends its run whatever follows it (m <= its length: the next row, if
    # there is one, did not pass)
    tail = (pos < m) & ((key_c != nxt) | (pos == length - 1))
    head = jnp.concatenate([jnp.ones((1,), bool), key_c[1:] != key_c[:-1]])
    v = jnp.stack(vals_c) if vals_c else jnp.zeros((0, length), jnp.float32)
    lengths, totals = _run_totals(head, v)
    idx = jnp.where(tail, key_c, nseg + pos)
    put = lambda zero, upd: zero.at[idx].set(        # noqa: E731
        upd, unique_indices=True, mode="drop")
    counts = put(jnp.zeros((nseg,), jnp.int32), lengths)
    return [counts.at[nseg - 1].set(jnp.int32(rows) - m)] + [
        put(jnp.zeros((nseg,), jnp.float32), t) for t in totals]


def _decode_sorted(key_s, vals_s, m, nseg: int, rows: int, rungs, dense=None):
    """`_compact_decode` over the shortest of the prefixes `rungs` of the
    sorted rows that holds the `m` rows that passed; past the last of them
    `dense` (where there is none the caller knows the last rung holds them).
    One HLO conditional on m, which only the device knows; one branch runs,
    and all share the sort."""
    def compact(length):
        def branch():
            with jax.named_scope("pinot.groupby.partitioned.compact"):
                return _compact_decode(key_s[:length],
                                       [v[:length] for v in vals_s], m, nseg,
                                       rows)
        return branch

    def dense_branch():
        with jax.named_scope("pinot.groupby.partitioned.dense"):
            return dense()

    steps = rungs if dense else rungs[:-1]
    rung = sum((m > c).astype(jnp.int32) for c in steps)
    return jax.lax.switch(rung, [compact(c) for c in rungs]
                          + ([dense_branch] if dense else []))


def compact_rungs(cap: int):
    """The prefixes the compact decode tries: COMPACT_RUNGS where they are at
    most a quarter of `cap`, then `cap`."""
    return tuple(c for c in COMPACT_RUNGS if 4 * c <= cap) + (cap,)


# Rows a tile of the presort compaction, and the slots a tile keeps for its
# rows that passed: the first count that holds every tile's, so the compacted
# rows are n / 64 (`compact_cap`) or n / 16. Two kinds of rung compact so:
# the sort regimes (`_grouped_partitioned`, `_grouped_sparse`), which need
# every tile within its slots to keep the rows in order, and the matmul
# rungs (`_compacted_sums`), which take the few tiles past them whole
# (PRESORT_SPILL). Q3.2, the densest SSB template,
# passes 1.64 rows a tile on average, and the chance that any of 65,536 tiles
# of independent draws holds more than 16 is about 2e-7; rows that are not
# independent draws need the second step (the benchmark's generator opens
# every segment with 2,406 rows that walk every key space, the customer's city
# equal to the supplier's: Q3.2 passes runs of ten of them, up to 50 a tile).
# A slot costs a select over every row, so the steps are few and short: the
# move is 7.5 ms a 2^26 rows at 16 slots (PR 33's chip probe, PERF.md section
# 6). No `KernelCaps` field, key or variable feeds either; tests patch them as
# they patch SLAB_ROWS.
PRESORT_TILE = 1024
PRESORT_SLOTS = (16, 64)
# One tile in this many may hold more rows that passed than a step's slots
# and still take that step in front of a matmul rung: such a tile is taken
# whole, beside the slots. 256 tiles (n / 256 rows) at 2^26 rows a device, 64
# at 2^24; the benchmark's walk rows overfill 3 tiles a segment (48 and 12).
# From the tile count alone, as PRESORT_SLOTS; tests patch it.
PRESORT_SPILL = 256


def compacted_rows(rows: int) -> int:
    """How many rows a matmul rung contracts over at most where it takes a
    step of the compaction (`_compacted_sums`) over `rows` rows a device:
    every tile's slots at the last step and the tiles taken whole. 0 where
    that is not fewer than the rows: no stage is built."""
    tiles = -(-rows // PRESORT_TILE)
    length = tiles * PRESORT_SLOTS[-1] + tiles // PRESORT_SPILL * PRESORT_TILE
    return length if length < rows else 0


def _tile_counts(key: jnp.ndarray, nseg: int):
    """The count in front of every compaction: the key as [T, PRESORT_TILE]
    tiles (the tail padded with the overflow key nseg-1), that padding's
    length, and the rows that passed (key < nseg-1) in each tile."""
    short = (-key.size) % PRESORT_TILE
    key_t = jnp.pad(key, (0, short), constant_values=nseg - 1).reshape(
        -1, PRESORT_TILE)
    return key_t, short, jnp.sum(key_t < nseg - 1, axis=-1, dtype=jnp.int32)


def _presort_compact(key_t, vals_t, nseg: int, slots: int):
    """The rows of every tile that passed the filter (key < nseg - 1), moved
    into the tile's own `slots` slots in row order: `key_t` [T, B] int32,
    `vals_t` f32 [T, B] each, at most that many such rows a tile (the caller
    counted). Returns the flat compacted key
    [T * slots] (a slot no row took carries the overflow key nseg-1) and value
    rows (zero there).

    No n-row scatter and no flat scan. A row's slot is the count of live rows
    before it in its tile: the 0/1 mask against a strict [B, B] triangle on
    the MXU (bf16 operands, f32 accumulation: exact, every cell at most B).
    Slot k's key and values are then a select and a reduce over the tile on
    the VPU, all slots of an operand in one fused pass ([T, B, slots] is
    never written): a slot receives one row, so an int32 key and an f32 value
    arrive as they were, with no digits and no bf16 parts. On the v5e 2.9 ms
    a 2^24 rows and 7.5 a 2^26, key and one value row (PR 33's probe; a
    one-hot contraction on the MXU 3.0 and 15.6, `lax.sort` along the tiles
    6.9 and 27.2)."""
    tile = key_t.shape[1]
    before = jnp.tri(tile, tile, -1, dtype=jnp.bfloat16).T      # [u, b]: u < b
    over = nseg - 1
    live = key_t < over
    slot = jnp.where(live, jax.lax.dot(
        live.astype(jnp.bfloat16), before,
        preferred_element_type=jnp.float32), slots)     # dead rows: no slot
    mine = slot[:, :, None] == jnp.arange(slots, dtype=jnp.float32)
    # less the overflow key, so that a slot no row took sums to it
    key_c = jnp.sum(jnp.where(mine, (key_t - over)[:, :, None], 0), axis=1)

    def zero(v):    # an int32 row (a MIN's values) stays int32
        return 0.0 if jnp.issubdtype(v.dtype, jnp.floating) \
            else jnp.zeros((), v.dtype)
    return ((key_c + over).reshape(-1),
            [jnp.sum(jnp.where(mine, v[:, :, None], zero(v)),
                     axis=1).reshape(-1)
             for v in vals_t])


def _spill_index(flagged: jnp.ndarray, spill: int):
    """The first `spill` tiles set in `flagged` [T], in tile order: their
    indices (int32 [spill]) and which of the `spill` places one took.

    No sort, `top_k`, flat scan or `nonzero` over the tiles (each costs the
    v5e's compiler its time: `_run_totals`, `_trim_groups`). A tile's place is
    the flagged tiles before it: within its group of PRESORT_TILE tiles the
    strict triangle on the MXU counts them, as `_presort_compact` counts
    slots, and the few groups before add theirs by a masked reduce. One
    scatter sets every index in its place; the unflagged are sent out of
    bounds, each to its own index, and dropped."""
    w = PRESORT_TILE
    groups = -(-flagged.size // w)
    f = jnp.pad(flagged, (0, groups * w - flagged.size)).reshape(groups, w)
    before = jnp.tri(w, w, -1, dtype=jnp.bfloat16).T      # [u, b]: u < b
    rank = jax.lax.dot(f.astype(jnp.bfloat16), before,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
    per = jnp.sum(f, axis=1, dtype=jnp.int32)
    ahead = jnp.sum(jnp.where(jnp.tri(groups, groups, -1, dtype=bool),
                              per[None, :], 0), axis=1)   # the groups before
    tile = jnp.arange(groups * w, dtype=jnp.int32).reshape(groups, w)
    place = jnp.where(f, rank + ahead[:, None], groups * w + tile)
    index = jnp.zeros((spill,), jnp.int32).at[place.reshape(-1)].set(
        tile.reshape(-1), unique_indices=True, mode="drop")
    return index, jnp.arange(spill) < jnp.sum(per)


def _compacted_sums(key: jnp.ndarray, nseg: int, rows, regime, took=None,
                    name: str = "pinot.groupby"):
    """`_slab_sums` of a matmul rung (`regime`: `_onehot_sums` or
    `_grouped_chunk64` bound to its key count) over the rows that passed
    where the device finds them few: [int32 counts[nseg], f32 sums[nseg]...].
    `rows[0]` is the count row (the 0/1 mask), the others masked value rows.

    The cost of a matmul rung is the length of its contraction, not its
    FLOPs, and a selective filter leaves most rows contracted as zeros. So one
    count over the key's tiles of PRESORT_TILE rows (`_tile_counts`, the sort
    regimes' count) comes first, and one HLO conditional on what it found:
    the first step of PRESORT_SLOTS whose slots hold the rows that passed of
    every tile but at most T / PRESORT_SPILL. There each other tile moves its
    rows into its slots (`_presort_compact`, the sort regimes' move; the
    overfull tiles' slots keep the overflow key and zeros), the overfull
    tiles are taken whole (`_spill_index`: their key and value rows, the
    places no tile took the overflow key and zeros), and the rung contracts
    over the T x slots + T / PRESORT_SPILL x PRESORT_TILE rows, the count
    row the compacted key's, in slabs of COMPACT_SLAB_ROWS (an accumulator
    that adds fewer terms of a group adds them tighter). No row is
    contracted twice: a row is in its tile's slots or in its whole tile,
    never both. At 2^26 rows a device that is at most n / 16 + n / 256
    rows, 17 slabs of 2^18. Past the last step (an unselective filter, or
    rows that passed clustered in more tiles) the rung runs over every row
    as before, at the cost of the one count.

    Built only where the compacted rows are fewer than the rows
    (`compacted_rows`), else the rung as it was. `took` (a list, or None)
    collects the scalar `qstats.MATMUL_FLAG`: the compacted rows were
    contracted."""
    if not compacted_rows(key.size):
        return _slab_sums(key, nseg, rows, regime)
    over = nseg - 1
    with jax.named_scope(f"{name}.presort"):
        key_t, short, passed = _tile_counts(key, nseg)
        spill = key_t.shape[0] // PRESORT_SPILL
        # the first step whose slots leave at most `spill` tiles overfull
        step = sum((jnp.sum(passed > s, dtype=jnp.int32) > spill).astype(
            jnp.int32) for s in PRESORT_SLOTS)

    def moved(slots):
        def branch():
            with jax.named_scope(f"{name}.presort"):
                whole = passed > slots
                vals_t = [jnp.pad(r, (0, short)).reshape(key_t.shape)
                          for r in rows[1:]]
                key_c, vals_c = _presort_compact(
                    jnp.where(whole[:, None], over, key_t), vals_t, nseg,
                    slots)
                if spill:
                    at, filled = _spill_index(whole, spill)
                    taken = filled[:, None]
                    key_c = jnp.concatenate(
                        [key_c, jnp.where(taken, key_t[at], over).reshape(-1)])
                    vals_c = [jnp.concatenate(
                        [c, jnp.where(taken, v[at], 0.0).reshape(-1)])
                        for c, v in zip(vals_c, vals_t)]
                count = (key_c < over).astype(jnp.float32)
            return _slab_sums(key_c, nseg, [count] + vals_c, regime,
                              COMPACT_SLAB_ROWS)
        return branch

    if took is not None:
        took.append({qstats.MATMUL_FLAG: step < len(PRESORT_SLOTS)})
    return jax.lax.switch(step, [moved(s) for s in PRESORT_SLOTS] + [
        lambda: _slab_sums(key, nseg, rows, regime)])


def _dense_decode(key_s, vals_s, nseg: int, pad: int, block: int):
    """The per-key decode of the sorted rows (`_grouped_partitioned`)."""
    n = key_s.size
    nb = n // block
    with jax.named_scope("pinot.groupby.partitioned.trim"):
        left, counts = _counts_from_sorted(key_s, nseg, pad)
    outs = [counts]
    if not vals_s:
        return outs
    with jax.named_scope("pinot.groupby.partitioned.scan"):
        head = jnp.concatenate([jnp.ones((1,), bool),
                                key_s[1:] != key_s[:-1]])
        rank = jnp.cumsum(head.astype(jnp.int32)) - 1       # nondecreasing
        rank_start = rank.reshape(nb, block)[:, 0]          # [nb]
        j = rank.reshape(nb, block) - rank_start[:, None]   # local id < block
        bf = jnp.bfloat16
        oh_hi = jax.nn.one_hot(j // 64, block // 64, dtype=bf)  # [nb, block, B/64]
        oh_lo = jax.nn.one_hot(j % 64, 64, dtype=bf)            # [nb, block, 64]
        dot = lambda a, b: jax.lax.dot_general(             # noqa: E731
            a, b, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        local = []
        for v in vals_s:
            s = None
            for part in _bf16_parts(v.reshape(nb, block)):
                d = dot(oh_hi, part[:, :, None] * oh_lo)    # [nb, B/64, 64]
                s = d if s is None else s + d
            local.append(s.reshape(nb, block))              # sums per (slab, j)
        # stitch slab-spanning groups: a group continuing into slab b sits at
        # local id 0 there, so a segmented scan over local[:, 0] (heads where
        # rank_start changes) accumulates each continuation chain
        heads_b = jnp.concatenate([jnp.ones((1,), bool),
                                   rank_start[1:] != rank_start[:-1]])
        slab0 = jnp.stack([l[:, 0] for l in local])         # [R, nb]
        flags = jnp.broadcast_to(heads_b[None, :], slab0.shape)
        _, chain = jax.lax.associative_scan(_seg_sum_op, (flags, slab0),
                                            axis=1)
    # dense decode: each key's first sorted row -> (slab g0, local id j0); the
    # last slab of its chain is the last rank_start <= its rank
    with jax.named_scope("pinot.groupby.partitioned.trim"):
        p = jnp.minimum(left[:-1], n - 1)
        r = rank[p]
        g0 = p // block
        j0 = r - rank_start[g0]
        g1 = jnp.searchsorted(rank_start, r, side="right") - 1
        occ = counts > 0
        for li, ci in zip(local, chain):
            start = li[g0, j0]
            tail = ci[g1]
            # j0 == 0: the chain includes slab g0 itself; otherwise the chain
            # (if any: g1 > g0) covers only the continuation slabs after g0
            total = jnp.where(j0 == 0, tail,
                              start + jnp.where(g1 > g0, tail, 0.0))
            outs.append(jnp.where(occ, total, 0.0))
    return outs


def _grouped_partitioned(key: jnp.ndarray, nseg: int, value_rows,
                         block: int = 4096, took=None):
    """Two-level radix-partitioned sort group-by — the one regime past
    `chunk_cap` keys, at any row count.

    The sort IS the radix split: after `jax.lax.sort`, each `block`-row slab is
    one partition whose keys RANK-compress to a dense local id
    j = rank - rank_start (ranks rise by at most 1 per row, so j < block no
    matter how many of the 2^21 global keys land in the slab). That local id
    is exactly the chunked one-hot shape, so each slab reuses the 64x64-tile
    MXU formulation of `_grouped_chunk64` as ONE batched
    [B, block, 64]^T @ [B, block, 64] dot per bf16 part — total MACs
    N * block, i.e. a single chunk64-tile-equivalent per part REGARDLESS of
    key count, where the chunked path pays per 4096 keys and a flat
    `segment_sum` scatter (what PR 1 replaced) paid a K-independent ~248ms.
    Groups spanning slab boundaries always occupy local id 0 of the
    continuation slabs, so a short segmented scan over the [B] slab-head sums
    stitches them. The dense decode has no n-row scatter
    either: `searchsorted` run boundaries give exact int32 counts and each
    key's first sorted position, from which (slab, local id, continuation
    chain) are pure gathers — two binary searches for EVERY dense key, so
    where few rows passed the filter (at most `compact_cap`) the answer comes
    from the sorted prefix of rows that passed instead (`_decode_sorted`)
    and none of the above runs. Value sums use the 3-part bf16 split (full
    f32 precision) with f32 accumulation.

    Where the program has the compact decode at all (`compact_cap` not 0: else
    it is the sort and the dense decode alone), one count over the key's
    tiles of PRESORT_TILE rows comes first, and one HLO conditional on what it
    found. If at most `compact_cap` rows passed and no tile holds more of them
    than the slots of a step of PRESORT_SLOTS (the first that holds them),
    those rows are moved to the front of their tiles (`_presort_compact`), the
    n / 64 or n / 16 compacted rows are sorted in place of all n, and the
    compact ladder answers from the first `compact_cap` of them (one ladder
    for both steps: a step is its move and its sort alone, since what a
    program holds it also loads at every start): the rows keep their order,
    so the sums are the full sort's to the bit. Otherwise (an unselective
    filter, or rows that passed clustered in a few tiles) the full sort and
    its ladder run as before: such a table costs what it did plus the one
    count. `took` (a list, or None) collects the scalars of
    `qstats.DECODE_FLAGS`: a compact decode ran, the compacted sort ran.
    Returns [int32 counts[nseg], f32 sums[nseg]...].
    """
    rows = key.size
    cap = compact_cap(rows + (-rows) % block, nseg, block)

    def full(m=None):
        with jax.named_scope("pinot.groupby.partitioned.sort"):
            key_s, vals_s, pad = _sort_by_key(key, nseg, value_rows, block)
        dense = lambda: _dense_decode(key_s, vals_s, nseg, pad, block)  # noqa: E731
        if m is None:
            return dense()
        return _decode_sorted(key_s, vals_s, m, nseg, rows,
                              compact_rungs(cap), dense)

    if not cap:
        return full()
    with jax.named_scope("pinot.groupby.partitioned.presort"):
        key_t, short, passed = _tile_counts(key, nseg)
        m = jnp.sum(passed)
        most = jnp.max(passed)

    def moved(slots):
        def branch():
            key_c, vals_c = _presort_compact(
                key_t, [jnp.pad(v, (0, short)).reshape(key_t.shape)
                        for v in value_rows], nseg, slots)
            key_s, vals_s, _ = _sort_by_key(key_c, nseg, vals_c, 1)
            # the first `cap` sorted rows hold the m that passed
            more = max(cap - key_s.size, 0)
            return (jnp.pad(key_s, (0, more), constant_values=nseg - 1)[:cap],
                    [jnp.pad(v, (0, more))[:cap] for v in vals_s])
        return branch

    def presorted():
        with jax.named_scope("pinot.groupby.partitioned.presort"):
            # the first step whose slots hold every tile's rows
            step = sum((most > s).astype(jnp.int32)
                       for s in PRESORT_SLOTS[:-1])
            key_s, vals_s = jax.lax.switch(
                step, [moved(s) for s in PRESORT_SLOTS])
        return _decode_sorted(key_s, vals_s, m, nseg, rows, compact_rungs(cap))

    fits = (m <= cap) & (most <= PRESORT_SLOTS[-1])
    if took is not None:
        took.append({qstats.COMPACT_FLAG: m <= cap, qstats.PRESORT_FLAG: fits})
    return jax.lax.cond(fits, presorted, lambda: full(m))


# The fewest rows that passed the sparse regime answers from at any table
# size (n / 64 at the benchmark's): small tables in tests and rehearsals
SPARSE_MIN_ROWS = 1 << 12


def sparse_cap(n: int) -> int:
    """How many sorted rows that passed `_grouped_sparse` reads at most, from
    the row count alone: n / 64 (what the compacted sort's first step holds),
    at least SPARSE_MIN_ROWS, a multiple of SCAN_WIDTH. Past it the launch
    says so (`sparse.groups` -1) and the host answers."""
    cap = max(n // 64, min(n, SPARSE_MIN_ROWS))
    return -(-cap // SCAN_WIDTH) * SCAN_WIDTH

# The sparse regime's outputs beside the per-group ones ("count", "<i>.sum",
# "<i>.min", ...): the groups' keys, how many groups the rows that passed hold
# (-1 where more rows passed than `sparse_cap`), and how many rows passed
SPARSE_KEYS, SPARSE_GROUPS, SPARSE_ROWS = (
    "sparse.keys", "sparse.groups", "sparse.rows")


def _group_id(key, strides, j: int, n_cols: int):
    """Group column j's dictionary id in the mixed-radix key (`strides` are
    the runtime strides, stride 0 first): dictionaries are sorted, so id
    order is value order."""
    q = key // strides[j]
    return q % (strides[j + 1] // strides[j]) if j + 1 < n_cols else q


def _descending(v):
    """`v` with its order reversed, exactly: -v for floats, ~v for ints
    (no overflow at the least int32)."""
    return -v if jnp.issubdtype(v.dtype, jnp.floating) else ~v


def _sorted_groups(key_c, sums_c, ext_c, m, nseg: int):
    """The groups of a sorted prefix whose first `m` rows passed: at every
    row, whether it closes a group (`tail`) and, there, the group's row count,
    sums (`_run_totals`: added as a tree) and MINs / MAXs (`_run_extremes`,
    in the values' dtype)."""
    length = key_c.size
    pos = jnp.arange(length, dtype=jnp.int32)
    nxt = jnp.concatenate([key_c[1:], jnp.full((1,), nseg - 1, key_c.dtype)])
    tail = (pos < m) & ((key_c != nxt) | (pos == length - 1))
    head = jnp.concatenate([jnp.ones((1,), bool), key_c[1:] != key_c[:-1]])
    v = jnp.stack(sums_c) if sums_c else jnp.zeros((0, length), jnp.float32)
    lengths, totals = _run_totals(head, v)
    extremes = [_run_extremes(head, e, is_min) for e, is_min in ext_c]
    return tail, [lengths] + list(totals) + extremes


def _orderable(v, desc: bool):
    """`v` (f32 or int32) as uint32 whose unsigned order is the ORDER BY's:
    the larger, the earlier. A float's sign bit flips it and a negative's
    other bits invert, an int's sign bit flips; ascending inverts all."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    top = jnp.uint32(0x80000000)
    if jnp.issubdtype(v.dtype, jnp.floating):
        u = jnp.where(bits >= top, ~bits, bits | top)
    else:
        u = bits ^ top
    return u if desc else ~u


def _kth_largest(u, active, need):
    """The `need`-th largest of the uint32 `u` over the `active` rows, bit by
    bit from the top (32 counts over the rows: a radix select, no sort); 0
    where fewer than `need` rows are active."""
    def bit(b, t):
        cand = t | (jnp.uint32(1) << (31 - b).astype(jnp.uint32))
        hits = jnp.sum(active & (u >= cand), dtype=jnp.int32)
        return jnp.where(hits >= need, cand, t)
    start = jnp.uint32(0)
    chips = tuple(jax.typeof(u).vma)
    if chips:   # under shard_map the threshold varies by chip, as `u` does
        start = jax.lax.pcast(start, chips, to="varying")
    return jax.lax.fori_loop(0, 32, bit, start)


def _trim_groups(trim, key_c, tail, cols, strides, n_cols: int):
    """ORDER BY ... LIMIT over the groups closed at `tail`: the k that come
    first, by each ORDER BY key in turn and then the key (the broker's stable
    sort of the untrimmed groups, which come in key order, breaks a tie so),
    moved into k slots in key order; the broker orders those k. `trim` is
    `KernelSpec.trim`'s (k, ((source, desc), ...)) with a source ("key", j),
    a group column, or ("col", i), the i-th of `cols` (count, sums, MINs and
    MAXs at the tails).

    No sort: a sort or top-k over the prefix costs the v5e's compiler 18-34 s
    apiece, one of four keys and eight operands 350 s (PERF.md, section 6). Key by key, a
    radix select (`_kth_largest`) finds the value at which the k still owed
    run out: the groups above it are taken, those equal to it go on to the
    next key, and the last key, the group's own, is unique."""
    k, order = trim
    keys = [_orderable(_group_id(key_c, strides, i, n_cols) if kind == "key"
                       else cols[i], desc) for (kind, i), desc in order]
    keys.append(_orderable(key_c, False))
    taken = jnp.zeros_like(tail)
    active, need = tail, jnp.int32(k)
    for u in keys:
        t = _kth_largest(u, active, need)
        above = active & (u > t)
        taken = taken | above
        need = need - jnp.sum(above, dtype=jnp.int32)
        active = active & (u == t)
    taken = taken | (active & (need > 0))
    return _pick_rows(key_c, taken, cols, k)


# The most slots `_pick_rows` fills by a masked reduce over [k, rows]; past
# it a scatter. On the v5e a scatter of the 1M-row prefix into ten slots took
# 5.1 ms an output, a third of TPC-H Q3's device time (PERF.md, section 5)
PICK_REDUCE_MAX = 256


def _pick_rows(key_c, taken, cols, size: int):
    """The rows at `taken` (at most `size`), in key order, in `size` slots:
    a row's slot is the taken rows before it, and each slot one select and
    one reduce over the rows (a slot gets one row, so a value arrives as it
    was), key 0 and zeros in the slots left over."""
    if size > PICK_REDUCE_MAX:
        return _scatter_groups(key_c, taken, cols, 0, size)
    first = jnp.zeros_like(taken).at[0].set(True)
    _, ranked = _run_totals(first, taken.astype(jnp.float32)[None])
    slot = jnp.where(taken, ranked[0].astype(jnp.int32) - 1, size)
    hit = slot[None, :] == jnp.arange(size, dtype=jnp.int32)[:, None]
    return [jnp.sum(jnp.where(hit, v[None, :], jnp.zeros((), v.dtype)),
                    axis=1) for v in [key_c] + list(cols)]


def _scatter_groups(key_c, tail, cols, fill: int, size: int):
    """The rows at `tail`, in key order, at the front of `size` slots (a
    row's slot: the tails before it, a running count of the two levels of
    `_run_totals`); the slots past them hold the key `fill` and zeros."""
    first = jnp.zeros_like(tail).at[0].set(True)
    _, ranked = _run_totals(first, tail.astype(jnp.float32)[None])
    pos = jnp.arange(tail.size, dtype=jnp.int32)
    idx = jnp.where(tail, ranked[0].astype(jnp.int32) - 1, size + pos)

    def put(fill, v):
        return jnp.full((size,), fill, v.dtype).at[idx].set(
            v, unique_indices=True, mode="drop")
    return [put(fill, key_c)] + [put(0, c) for c in cols]


def _grouped_sparse(key: jnp.ndarray, nseg: int, sum_rows, ext_rows,
                    took=None, trim=(), strides=None, n_cols: int = 1):
    """The sort regime past `KernelCaps.dense_keys`: a GROUP BY answered from
    its SORTED GROUPS, the keys that occur and their counts, sums and MINs /
    MAXs, never a table of the key space. Its cost is in rows: TPC-H Q3's
    GROUP BY over 16.8M order ids, of which about 117k occur among 0.5% of
    67M rows.

    The front is `_grouped_partitioned`'s: one count over the key's tiles of
    PRESORT_TILE rows, and where at most `sparse_cap(n)` rows passed and no
    tile holds more than the slots of a step of PRESORT_SLOTS, the rows that
    passed move to the front of their tiles and the compacted rows are
    sorted; else every row is. Either way the first `sparse_cap` sorted rows
    hold the `m` that passed, and the groups are found over that prefix
    (`_sorted_groups`): exact int32 counts (run lengths), tree-added f32
    sums, MINs and MAXs in the values' dtype. No key passes through a float.
    One prefix and no ladder of shorter ones (`compact_rungs`): a rung is one
    more copy of the groups' program, and compile time is what this regime
    is short of (`_trim_groups`).

    `sum_rows` are f32 rows (each SUM's, masked), `ext_rows` (values,
    is_min) pairs. With `trim` (`KernelSpec.trim`: the partial is the whole
    answer) the groups end in `_trim_groups`, k entries; else
    `_scatter_groups`, `sparse_cap` entries in key order. Returns
    [keys, counts, sums..., extremes...] of that length and the scalars
    (groups, rows): groups is -1 where more than `sparse_cap` rows passed
    (the caller's host answers)."""
    rows = key.size
    cap = sparse_cap(rows)
    over = nseg - 1
    vals = list(sum_rows) + [v for v, _ in ext_rows]
    with jax.named_scope("pinot.groupby.sparse.presort"):
        key_t, short, passed = _tile_counts(key, nseg)
        m = jnp.sum(passed)
        most = jnp.max(passed)

    def prefix(key_s, vals_s):
        more = max(cap - key_s.size, 0)
        return (jnp.pad(key_s, (0, more), constant_values=over)[:cap],
                [jnp.pad(v, (0, more))[:cap] for v in vals_s])

    def moved(slots):
        def branch():
            key_c, vals_c = _presort_compact(
                key_t, [jnp.pad(v, (0, short)).reshape(key_t.shape)
                        for v in vals], nseg, slots)
            return prefix(*_sort_by_key(key_c, nseg, vals_c, 1)[:2])
        return branch

    def presorted():
        with jax.named_scope("pinot.groupby.sparse.presort"):
            step = sum((most > s).astype(jnp.int32)
                       for s in PRESORT_SLOTS[:-1])
            return jax.lax.switch(step, [moved(s) for s in PRESORT_SLOTS])

    def full():
        with jax.named_scope("pinot.groupby.sparse.sort"):
            return prefix(*_sort_by_key(key, nseg, vals, 1)[:2])

    fits = (m <= cap) & (most <= PRESORT_SLOTS[-1])
    if took is not None:
        took.append({qstats.COMPACT_FLAG: m <= cap, qstats.PRESORT_FLAG: fits})
    key_p, vals_p = jax.lax.cond(fits, presorted, full)
    with jax.named_scope("pinot.groupby.sparse.groups"):
        tail, cols = _sorted_groups(
            key_p, vals_p[:len(sum_rows)],
            [(v, is_min) for v, (_, is_min)
             in zip(vals_p[len(sum_rows):], ext_rows)], m, nseg)
        groups = jnp.sum(tail, dtype=jnp.int32)
    if trim:
        with jax.named_scope("pinot.trim"):
            out = _trim_groups(trim, key_p, tail, cols, strides, n_cols)
    else:
        with jax.named_scope("pinot.groupby.sparse.groups"):
            out = _scatter_groups(key_p, tail, cols, over, cap)
    return out, jnp.where(m > cap, -1, groups), m


def combine_collective(name: str, v, axis: str):
    """The cross-device combine for one kernel output: partials agree on dense keys
    (aligned dictionaries), so one ICI collective merges them."""
    # the decode flags: a launch took the compact decode, the compacted sort
    # or the compacted matmul rows only if every chip did
    if name.endswith((".min", ".max")) or name in qstats.DECODE_FLAGS:
        with jax.named_scope("pinot.collective.minmax"):
            return (jax.lax.pmax if name.endswith(".max")
                    else jax.lax.pmin)(v, axis)
    with jax.named_scope("pinot.collective.sum"):
        return jax.lax.psum(v, axis)


def make_kernel_body(spec: KernelSpec):
    """The un-jitted fused scan body — shared between the single-device jit kernel and
    the shard_map mesh kernel (which composes it with per-output ICI collectives)."""
    return _make_body(spec)


def _make_body(spec: KernelSpec):
    group = bool(spec.group_cols)
    num_seg = spec.num_keys_pad + 1  # +1 overflow bucket for masked-out rows
    mask_fn = _make_mask_fn(spec)
    caps = get_caps()  # the ladders' crossovers (part of signature())
    scope = jax.named_scope  # each stage of the scan, named in the device trace

    def grouped_distinct(ai, agg, ids, key, mask, took):
        """PER-GROUP presence counts [keys, dict ids] (the grouped
        DISTINCTCOUNT/HLL/theta path, BASELINE config 5): one combined dense
        key over the (group, id) product space — masked rows ride the
        overflow band exactly like `key`. The SKINNY one-hot matmul is ~100x
        slower than a scatter at this width (keys*ids, tens of thousands),
        but the CHUNKED 64x64-tile formulation (_grouped_chunk64) runs the
        same product space at full MXU tile utilization — count-only, so one
        bf16 part per chunk (exact: 0/1 operands, f32 accumulation, int32
        across the slabs of `_slab_sums`, as the sum path). Widths past
        `chunk_cap` take the sort regime with no value rows."""
        size = spec.distinct_lut_sizes[ai]
        col_ids = ids[agg.arg.name].ravel()
        comb = key * size + col_ids
        width = num_seg * size
        if width <= caps.chunk_cap:
            fm = mask.ravel().astype(jnp.float32)
            # the masked rows' band of keys starts where the last group ends
            pres = _slab_sums(comb, width, [fm], lambda k, r: _grouped_chunk64(
                k, width, r, [], real=width - size))[0]
            return pres.reshape(num_seg, size)
        # presence counts over the combined (group, id) space past the chunk
        # cap: the sort regime with no value rows, whose sorted-run boundary
        # counts are exact int32 with no matmul and no scatter
        pres = _grouped_partitioned(comb, width, [], caps.partition_block,
                                    took)[0]
        return pres.reshape(num_seg, size)

    def scalar_distinct(ai, agg, ids, mask, fmask):
        """Exact distinct over a dict column: per-dict-id presence vector.
        Returned as a vector (not a count) because cross-segment merge needs
        the id set — dictionaries differ per segment."""
        size = spec.distinct_lut_sizes[ai]
        col_ids = ids[agg.arg.name].ravel()
        wants_counts = getattr(agg, "wants_id_counts", False)
        # count consumers (t-digest: per-id multiplicities as centroid
        # weights) need the EXACT histogram; f32 matmul cells stop
        # incrementing past 2^24, so blocks that could overflow a cell take
        # the int32 scatter (same guard as the grouped sum path). Presence
        # consumers (>0) are immune to the saturation and keep the matmul.
        counts_exact = mask.size <= (1 << 24)
        if size <= PRESENCE_MATMUL_CAP and (not wants_counts or counts_exact):
            counts = _presence_2d(fmask, col_ids, size)
            if wants_counts:
                return counts.astype(jnp.int32)
            return (counts > 0).astype(jnp.int32)
        return jax.ops.segment_sum(mask.ravel().astype(jnp.int32), col_ids,
                                   num_segments=size)

    def kernel(ids, vals, luts, iscal, fscal, nulls, valid, strides, agg_luts,
               docsets, bitmaps=()):
        vals = _fused_env(spec, ids, vals, iscal)
        mask = mask_fn(ids, vals, luts, iscal, fscal, nulls, valid, docsets,
                       bitmaps)
        out: Dict[str, jnp.ndarray] = {}
        # which decode and sort each sort regime of the scan ran, and which
        # rows each matmul rung contracted
        took: list = []

        if group:
            with scope("pinot.groupby.key"):
                key = jnp.zeros_like(ids[spec.group_cols[0]])
                for gi, gc in enumerate(spec.group_cols):
                    key = key + ids[gc] * strides[gi]
                key = jnp.where(mask, key, spec.num_keys_pad).ravel()
                fmask = mask.ravel().astype(jnp.float32)
            # collect count + every sum row for ONE launch of the ladder's
            # regime (the one-hot matmul: [1 + n_sums, N] @ one_hot(key)
            # [N, num_seg] -> [1 + n_sums, num_seg])
            sum_rows, sum_names = [fmask], ["count"]
            minmax = []  # (out name, values, is_min)
            for ai, (agg, outs) in enumerate(spec.aggs):
                if "distinct" in outs:
                    with scope("pinot.distinct"):
                        out[f"{ai}.distinct"] = grouped_distinct(
                            ai, agg, ids, key, mask, took)
                    continue
                with scope("pinot.groupby.key"):
                    v = _agg_arg(agg, vals, spec.int_ranges)
                    for o in outs:
                        if o in _POWER_SUMS:
                            # sums of powers ride the same stacked matmul
                            # (variance / skewness / kurtosis moments,
                            # VarianceAggregationFunction)
                            row = v.ravel().astype(jnp.float32) \
                                ** _POWER_SUMS[o]
                            sum_rows.append(row * fmask)
                            sum_names.append(f"{ai}.{o}")
                        elif o in ("min", "max"):
                            minmax.append((f"{ai}.{o}", v.ravel(), o == "min"))
            # the ladder: the padded key count against `KernelCaps` alone. The
            # row count chooses nothing: the masked reduce counts in int32,
            # the matmul regimes in int32 across slabs of at most SLAB_ROWS
            # rows (`_slab_sums`).
            # Each regime: [int32 counts[num_seg], f32 sums[num_seg]...]
            if sparse(spec):
                # a key space past the dense table (whatever the other caps
                # say, as the planner reads it): the groups that occur,
                # their MINs and MAXs with them, and where the partial is
                # the whole answer its ORDER BY ... LIMIT
                with scope("pinot.groupby.sparse"):
                    names = (sum_names[1:]
                             + [name for name, _, _ in minmax])
                    cols = ["count"] + names
                    trim = spec.trim and (spec.trim[0], tuple(
                        ((kind, cols.index(src) if kind == "out" else src),
                         desc) for (kind, src), desc in spec.trim[1]))
                    res, groups, passed = _grouped_sparse(
                        key, num_seg, sum_rows[1:],
                        [(v, is_min) for _, v, is_min in minmax], took,
                        trim, strides, len(spec.group_cols))
                out.update(zip([SPARSE_KEYS] + cols, res))
                out[SPARSE_GROUPS], out[SPARSE_ROWS] = groups, passed
                res, minmax = (), []
            elif num_seg <= caps.masked_cap:
                # A HANDFUL of key cells: a compare, a select and a reduce a
                # cell and row on the VPU beat any walk of the contraction
                with scope("pinot.groupby.masked"):
                    res = _masked_sums(key, num_seg, sum_rows)
            elif num_seg <= caps.matmul_cap:
                # the matmul rungs contract over the rows that passed,
                # compacted where the device finds them few
                with scope("pinot.groupby.onehot"):
                    res = _compacted_sums(
                        key, num_seg, sum_rows,
                        lambda k, r: _onehot_sums(k, num_seg, r), took,
                        "pinot.groupby.onehot")
            elif num_seg <= caps.chunk_cap:
                # HIGH-CARDINALITY group-by: chunked 64x64-tile matmuls (the
                # redesigned >cap path — 6.4x the segment_sum scatter at 20k
                # keys; see _grouped_chunk64's measurement + limit analysis)
                with scope("pinot.groupby.chunk64"):
                    res = _compacted_sums(
                        key, num_seg, sum_rows,
                        lambda k, r: _grouped_chunk64(k, num_seg, r[:1],
                                                      r[1:]), took,
                        "pinot.groupby.chunk64")
            else:
                # VERY-HIGH-CARDINALITY group-by (> chunk_cap): the sort
                # regime, with exact int32 counts and no scatter
                with scope("pinot.groupby.partitioned"):
                    res = _grouped_partitioned(key, num_seg, sum_rows[1:],
                                               caps.partition_block, took)
            if res:
                out.update(zip(sum_names, res))
            for name, v, is_min in minmax:
                with scope("pinot.groupby.minmax"):
                    if num_seg <= caps.minmax_bcast_cap:
                        ident = (_INT_MIN_IDENT if is_min else _INT_MAX_IDENT) \
                            if v.dtype.kind == "i" \
                            else (jnp.inf if is_min else -jnp.inf)
                        onehot = key[:, None] == jnp.arange(num_seg)[None, :]
                        cells = jnp.where(onehot, v[:, None], ident)
                        out[name] = (cells.min(axis=0) if is_min
                                     else cells.max(axis=0))
                    else:
                        op = (jax.ops.segment_min if is_min
                              else jax.ops.segment_max)
                        out[name] = op(v, key, num_segments=num_seg)
            # each flag (compact, presorted, compacted matmul) only if every
            # regime of the scan that sets it took its short branch
            for flag in qstats.DECODE_FLAGS:
                mine = [t[flag] for t in took if flag in t]
                if mine:
                    out[flag] = jnp.all(jnp.stack(mine)).astype(jnp.int32)
        else:
            with scope("pinot.agg"):
                fmask = mask.ravel().astype(jnp.float32)
                out["count"] = mask.sum(dtype=jnp.int32)
            for ai, (agg, outs) in enumerate(spec.aggs):
                if "distinct" in outs:
                    with scope("pinot.distinct"):
                        out[f"{ai}.distinct"] = scalar_distinct(
                            ai, agg, ids, mask, fmask)
                    continue
                if outs == ("count",):
                    continue
                with scope("pinot.agg"):
                    v = _agg_arg(agg, vals, spec.int_ranges)
                    for o in outs:
                        if o == "count":
                            continue
                        if o in _POWER_SUMS:
                            row = v.ravel().astype(jnp.float32) \
                                ** _POWER_SUMS[o]
                            out[f"{ai}.{o}"] = (row * fmask).sum()
                        elif o == "min":
                            ident = (_INT_MIN_IDENT if v.dtype.kind == "i"
                                     else jnp.inf)
                            out[f"{ai}.min"] = jnp.where(mask, v, ident).min()
                        elif o == "max":
                            ident = (_INT_MAX_IDENT if v.dtype.kind == "i"
                                     else -jnp.inf)
                            out[f"{ai}.max"] = jnp.where(mask, v, ident).max()
        return out

    kernel.__name__ = kernel_name(spec)
    return kernel


def kernel_name(spec: KernelSpec, batch: int = 0) -> str:
    """What a compiled scan is called in the profiler's trace (the "XLA
    Modules" line, the host's `PjitFunction(...)` events): `pinot_groupby`,
    `pinot_distinct` or `pinot_agg` by the spec's shape, `_fused` where it
    decodes compressed forms in-register, `_b<batch>` for a stacked launch.
    A name only: the kernel caches are keyed by `signature()`."""
    if spec.group_cols:
        name = "pinot_groupby"
    elif any("distinct" in outs for _, outs in spec.aggs):
        name = "pinot_distinct"
    else:
        name = "pinot_agg"
    if spec.fused_cols:
        name += "_fused"
    return name + (f"_b{batch}" if batch else "")


def _build_kernel(spec: KernelSpec):
    return jax.jit(_make_body(spec))


def get_kernel(spec: KernelSpec):
    return _cached_kernel(spec.signature(), lambda: _build_kernel(spec))


def dispatch_kernel(spec: KernelSpec, inputs: KernelInputs):
    """Asynchronously dispatch the fused kernel; returns unfetched device outputs.

    Callers batch several dispatches and fetch them with ONE `jax.device_get`
    (every synchronization is a host round trip, so the fetch count is part of
    the latency floor)."""
    return get_kernel(spec)(inputs.ids, inputs.vals, inputs.luts, inputs.iscal,
                            inputs.fscal, inputs.nulls, inputs.valid, inputs.strides,
                            inputs.agg_luts, inputs.docsets, inputs.bitmaps)


def run_kernel(spec: KernelSpec, inputs: KernelInputs) -> Dict[str, np.ndarray]:
    """Single-launch fused execution: filter + project + aggregate in ONE
    dispatch over the resident forms (compressed when `spec.fused_cols` routes
    them — decode then happens inside the kernel: `_fused_env`)."""
    qstats.record(qstats.FUSED_LAUNCHES)
    if gather_free(spec, inputs.vals):
        qstats.record(qstats.GATHER_FREE_LAUNCHES)
    _record_plan(spec, inputs.valid.size)
    # device_get, never np.asarray: asarray syncs leaf by leaf, device_get
    # fetches the whole tree in one batched round trip
    return _record_decode(fetch_outputs(dispatch_kernel(spec, inputs)))


def _record_plan(spec: KernelSpec, rows: int) -> None:
    """Count what a launch of `spec` over `rows` rows a device is, from the
    static plan: its GROUP BY regime's counters and the widened argument."""
    if masked(spec):
        qstats.record(qstats.MASKED_GROUPBY_LAUNCHES)
    if sparse(spec):
        qstats.record(qstats.SPARSE_GROUPBY_LAUNCHES)
    if trimmed(spec):
        qstats.record(qstats.DEVICE_TRIMMED_LAUNCHES)
    if slabbed(spec, rows):
        qstats.record(qstats.SLABBED_LAUNCHES)
    if widened(spec):
        qstats.record(qstats.WIDENED_AGG_LAUNCHES)


def _record_decode(outs):
    """Count which decode and which sort a fetched sort-regime launch ran."""
    for took in qstats.decode_branch(outs):
        qstats.record(took)
    return outs


def _staged_agg_spec(spec: KernelSpec) -> KernelSpec:
    """The aggregate-only half of the staged pair: same group/agg geometry,
    match-all filter (the mask launch's device output arrives as `valid`),
    no fused columns (staged inputs are decoded HBM columns)."""
    return KernelSpec(FilterProgram(), spec.group_cols, spec.num_keys_pad,
                      spec.aggs, dict(spec.distinct_lut_sizes),
                      spec.padded_rows, mv_cols=spec.mv_cols,
                      int_ranges=spec.int_ranges)


def run_kernel_staged(spec: KernelSpec,
                      inputs: KernelInputs) -> Dict[str, np.ndarray]:
    """The staged (pre-fusion) path: dispatch the filter mask as its own
    launch, then the aggregate kernel over decoded columns with the mask
    riding in as `valid` — two device launches where `run_kernel` takes one.
    `_fused_cols` routes here the two inputs only this path runs: a decode
    table over `KernelCaps.fused_lut_cap` and a multi-value value column.
    Tests hold it as the fused path's reference: results are bit-identical —
    both consume the same decode tables and the same mask semantics, only
    the HBM traffic and launch count differ."""
    if spec.filter.is_match_all:
        mask_dev = inputs.valid     # no filter: the mask launch would be a no-op
        qstats.record(qstats.STAGED_LAUNCHES)
    else:
        mask_dev = dispatch_mask(spec, inputs)
        qstats.record(qstats.STAGED_LAUNCHES, 2)
    agg_spec = _staged_agg_spec(spec)
    _record_plan(agg_spec, inputs.valid.size)
    outs = get_kernel(agg_spec)(inputs.ids, inputs.vals, inputs.luts,
                                inputs.iscal, inputs.fscal, inputs.nulls,
                                mask_dev, inputs.strides, inputs.agg_luts,
                                (), ())
    return _record_decode(fetch_outputs(outs))


def _mask_kernel(spec: KernelSpec):
    """Cached jit of the filter-only kernel (selection queries and the staged
    pair's first launch share it)."""
    key = ("mask", spec.filter.signature(), spec.padded_rows,
           spec.bitmap_leaves, spec.fused_cols, _filter_widen_marks(spec))

    def build():
        mask_fn = _make_mask_fn(spec)

        def body(ids, vals, luts, iscal, fscal, nulls, valid, docsets,
                 bitmaps):
            vals = _fused_env(spec, ids, vals, iscal)
            return mask_fn(ids, vals, luts, iscal, fscal, nulls, valid,
                           docsets, bitmaps)

        body.__name__ = "pinot_mask" + ("_fused" if spec.fused_cols else "")
        return jax.jit(body)

    return _cached_kernel(key, build)


def dispatch_mask(spec: KernelSpec, inputs: KernelInputs):
    """Asynchronously dispatch the filter mask; returns the unfetched device
    bool[P] (already ANDed with `valid`), ready to feed a second launch."""
    return _mask_kernel(spec)(inputs.ids, inputs.vals, inputs.luts,
                              inputs.iscal, inputs.fscal, inputs.nulls,
                              inputs.valid, inputs.docsets, inputs.bitmaps)


def compute_mask(spec: KernelSpec, inputs: KernelInputs) -> np.ndarray:
    """Filter-only kernel for selection queries: returns the boolean match mask."""
    return fetch_outputs(dispatch_mask(spec, inputs))


def compute_filter_count(spec: KernelSpec,
                         inputs: KernelInputs) -> Optional[int]:
    """Popcount fast path: matching-row COUNT for a filter whose every leaf is
    a bitmap leaf — the tree evaluates as fused bitwise ops over packed words
    and `lax.population_count` reduces them, so no per-row mask is ever
    materialized. Returns None when the filter doesn't evaluate fully in the
    word domain (caller falls back to the mask kernel)."""
    fn = filter_count_kernel(spec)
    if fn is None:
        return None
    # the staged packed valid keeps the whole count O(P/32); packing on the
    # fly (upsert valid-doc intersection) is the O(P) exception
    vw = inputs.valid_words
    if vw is None:
        vw = _pack_valid(inputs.valid)
    return int(fetch_outputs(fn(vw, inputs.bitmaps)))


def filter_count_kernel(spec: KernelSpec):
    """Cached jit of the word-domain COUNT kernel, fn(valid_words, bitmaps) ->
    uint32 count; None when the filter doesn't evaluate fully in the word
    domain."""
    if _make_word_fn(spec) is None:
        return None
    key = ("bitcount", spec.filter.signature(), spec.padded_rows,
           spec.bitmap_leaves)

    def build():
        word_fn = _make_word_fn(spec)

        def body(valid_words, bitmaps):
            with jax.named_scope("pinot.filter"):
                words = word_fn(bitmaps) & valid_words
                return jax.lax.population_count(words).sum(dtype=jnp.uint32)

        body.__name__ = "pinot_bitcount"
        return jax.jit(body)

    return _cached_kernel(key, build)


def topk_kernel(spec: KernelSpec, order_expr, desc: bool, k: int,
                total_rows: Optional[int] = None):
    """Cached jit of the fused filter + `lax.top_k` candidate kernel.

    Returns (fn, k) where k is the clamped candidate count and
    fn(ids, vals, luts, iscal, fscal, nulls, valid, docsets) ->
    {"idx": i32[k] flat row indices, "count": i32 match count,
     "ok": bool[k] usable flag per candidate, "nanMatches": i32 matching rows
     whose sort key is NaN (serving falls back to the host when > 0 — NaN
     ordering parity with the Python sort is out of the device contract)}.

    Both the synchronous single-segment path (`compute_topk`) and the served
    mesh path dispatch THIS kernel; the mesh path passes the stacked
    [segments, rows] arrays and `total_rows = segments * rows` and fetches the
    outputs asynchronously in the pipeline's batched device_get."""
    k = min(k, total_rows if total_rows is not None else spec.padded_rows)
    key = ("topk", spec.filter.signature(), repr(order_expr), desc, k,
           spec.padded_rows, total_rows, spec.fused_cols,
           _filter_widen_marks(spec), widen_marks(order_expr, spec.int_ranges))

    def build():
        mask_fn = _make_mask_fn(spec)

        def body(ids, vals, luts, iscal, fscal, nulls, valid, docsets):
            vals = _fused_env(spec, ids, vals, iscal)
            mask = mask_fn(ids, vals, luts, iscal, fscal, nulls, valid, docsets).ravel()
            with jax.named_scope("pinot.topk"):
                v = eval_expr(order_expr, vals, jnp,
                              spec.int_ranges).ravel().astype(jnp.float32)
                # NaN keys sink to the bottom (numpy sorts NaN last ascending;
                # exact parity for NaN keys is out of contract either way)
                nan = jnp.isnan(v)
                usable = mask & ~nan
                score = jnp.where(usable, v if desc else -v, -jnp.inf)
                _, idx = jax.lax.top_k(score, k)
                return {"idx": idx.astype(jnp.int32),
                        "count": mask.sum(dtype=jnp.int32),
                        "ok": usable[idx],
                        "nanMatches": (mask & nan).sum(dtype=jnp.int32)}

        body.__name__ = "pinot_topk" + ("_fused" if spec.fused_cols else "")
        return jax.jit(body)

    return _cached_kernel(key, build), k


def compute_topk(spec: KernelSpec, inputs: KernelInputs, order_expr,
                 desc: bool, k: int) -> Tuple[np.ndarray, int]:
    """Device top-k for `SELECT ... ORDER BY <numeric expr> LIMIT k` (SURVEY hard-part 3).

    Fuses the filter mask with a single `lax.top_k` over the (sign-adjusted) sort key,
    so only k doc indices cross back to the host instead of every matching row — the
    TPU analog of the reference's per-segment `TableResizer` trim before broker merge.
    Returns (doc indices, match count, match flag per index); indices whose flag is
    False are filtered-out rows that tied with a legitimate -inf/NaN sort key and must
    be dropped by the caller. The caller re-sorts candidates exactly on the host, so
    f32 here only decides the CANDIDATE SET (callers overfetch slack for boundary
    ties); final ordering is exact.
    """
    fn, _ = topk_kernel(spec, order_expr, desc, k)
    outs = fetch_outputs(fn(inputs.ids, inputs.vals, inputs.luts,
                            inputs.iscal, inputs.fscal, inputs.nulls,
                            inputs.valid, inputs.docsets))
    return (np.asarray(outs["idx"]), int(outs["count"]),
            np.asarray(outs["ok"]))


def _agg_arg(agg: AggFunc, vals, ranges) -> Optional[jnp.ndarray]:
    if agg.arg is None or (isinstance(agg.arg, Identifier) and agg.arg.name == "*"):
        return None
    return eval_expr(agg.arg, vals, jnp, ranges)
