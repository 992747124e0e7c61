"""Merged-dictionary segment view: the device path for UNALIGNED segment sets.

Real segment sets — anything committed at different times without a shared ingestion
dictionary, including consuming (mutable) segments — have per-segment dictionaries, so
dict ids disagree across segments and the mesh kernel's dense group keys / id-interval
filters / distinct presence vectors cannot combine with one collective.

The reference solves the analogous problem on the broker: every server ships *values*
(DataTable rows) and `GroupByDataTableReducer` re-hashes them. The TPU-native answer is
instead to agree on ids *before* the scan: build one GLOBAL sorted dictionary per
referenced column (sorted union of the per-segment dictionaries) and remap each
segment's local ids to global ids host-side, once, at block-build time. After the remap
the set behaves exactly like an aligned set — dense keys, interval filters and distinct
vectors combine with one psum — and the per-query dispatch stays gather-free on device.

`MergedSegmentView` presents the merged column surface (`ColumnReader`-compatible) so
`plan_segment`/`compile_filter` plan in global-id space unchanged; `remap(col)` hands the
per-segment id translation tables to `SegmentSetBlock` for host-side application while
stacking. Mutable segments participate via their query-time snapshot (dict + ids at a
fixed row count), giving consuming data a device scan path — the view is rebuilt when
any mutable segment grows (cheap: O(sum of cardinalities) host work), the TPU analog of
the reference re-reading the mutable indexes each query
(`MutableSegmentImpl.java:495`).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..schema import DataType
from ..segment.dictionary import Dictionary


def _merge_sorted_values(dicts: List[Dictionary], data_type: DataType):
    """Sorted union of per-segment dictionary values + per-segment remap arrays.

    remap[i][local_id] -> global_id; all inputs are sorted, so the union is one
    np.unique over the concatenation and each remap one vectorized searchsorted.

    A member whose dictionary already equals the union gets remap `None`
    (sorted + unique + same length as the union means its local ids ARE the
    global ids), so the stacker skips the O(rows) remap gather for it —
    mostly-aligned sets (immutable members sharing an ingestion dictionary
    plus one consuming snapshot) pay the remap only where ids actually move.
    """
    if data_type.is_numeric:
        merged = np.unique(np.concatenate([np.asarray(d.values) for d in dicts]))
        remaps = [None if len(d.values) == len(merged) else
                  np.searchsorted(merged, np.asarray(d.values)).astype(np.int32)
                  for d in dicts]
        return Dictionary(merged, data_type), remaps
    arrays = [np.array(list(d.values), dtype=object) for d in dicts]
    merged = np.unique(np.concatenate(arrays)) if arrays else np.array([], dtype=object)
    remaps = [None if len(a) == len(merged) else
              np.searchsorted(merged, a).astype(np.int32) for a in arrays]
    return Dictionary(list(merged), data_type), remaps


def _set_bound(readers, attr: str, combine) -> Any:
    """min/max over members with rows; a NONEMPTY member without stats poisons
    the bound to None (empty members genuinely contribute no values)."""
    vals = []
    for r in readers:
        v = getattr(r, attr)
        if v is None:
            if r.num_docs > 0:
                return None
            continue
        vals.append(v)
    return combine(vals) if vals else None


class MergedColumnReader:
    """ColumnReader-compatible view of one column across a segment set.

    Dict-encoded everywhere -> exposes the merged global dictionary (+ remaps).
    Otherwise -> a metadata proxy (merged min/max/nulls) over the raw columns.
    """

    def __init__(self, name: str, readers: Sequence[Any],
                 mutable_flags: Optional[Sequence[bool]] = None,
                 seg_docs: Optional[Sequence[int]] = None):
        self.name = name
        self._readers = list(readers)
        self.data_type = readers[0].data_type
        self.has_dictionary = all(r.has_dictionary for r in readers)
        self.num_docs = sum(r.num_docs for r in readers)
        self.is_sorted = False
        self._dictionary: Optional[Dictionary] = None
        # per-member local->global tables; an entry is None when that member's
        # ids are already global (dictionary == the merged union)
        self.remaps: Optional[List[Optional[np.ndarray]]] = None
        # Local ids for mutable members are snapshotted TOGETHER with the dictionary
        # the remap table was built from: a mutable reader re-snapshots (new sorted
        # dict, new ids) whenever rows arrive, so reading `fwd` later could pair new
        # ids with a stale remap. Immutable members read their mmap fwd lazily.
        self._fwd_snap: Dict[int, np.ndarray] = {}
        if self.has_dictionary:
            dicts = []
            for i, r in enumerate(readers):
                if mutable_flags and mutable_flags[i]:
                    # atomic (rows, dict, ids): dict and ids from the SAME snapshot
                    _, d, ids = r.dict_snapshot()
                    n = seg_docs[i] if seg_docs else len(ids)
                    dicts.append(d)
                    self._fwd_snap[i] = np.asarray(ids)[:n].astype(np.int64)
                else:
                    dicts.append(r.dictionary)
            self._dictionary, self.remaps = _merge_sorted_values(dicts, self.data_type)

    def local_ids(self, i: int) -> np.ndarray:
        """Member i's local dict ids, consistent with remaps[i]."""
        snap = self._fwd_snap.get(i)
        if snap is not None:
            return snap
        return np.asarray(self._readers[i].fwd).astype(np.int64)

    @property
    def dictionary(self) -> Optional[Dictionary]:
        return self._dictionary

    @property
    def cardinality(self) -> int:
        return len(self._dictionary) if self._dictionary is not None else -1

    @property
    def meta(self) -> Dict[str, Any]:
        if self.has_dictionary:
            fwd_dtype = "int32"  # remapped ids
        else:
            # value dtype across members (a member may still be dict-encoded when
            # others are raw; its fwd dtype would be an id width, not a value dtype)
            def value_dtype(r):
                if r.has_dictionary and r.data_type.is_numeric:
                    return np.asarray(r.dictionary.values).dtype
                return np.dtype(r.meta["fwdDtype"])
            fwd_dtype = str(np.result_type(*[value_dtype(r) for r in self._readers]))
        return {
            "dataType": self.data_type.value,
            "hasDictionary": self.has_dictionary,
            "hasNulls": any(r.meta.get("hasNulls", False) for r in self._readers),
            "fwdDtype": fwd_dtype,
            "cardinality": self.cardinality,
        }

    @property
    def min_value(self) -> Any:
        return _set_bound(self._readers, "min_value", min)

    @property
    def max_value(self) -> Any:
        return _set_bound(self._readers, "max_value", max)

    # aux indexes are per-segment; the mesh path pre-bails on JSON/TEXT_MATCH filters
    inverted_index = None
    range_index = None
    bloom_filter = None
    json_index = None
    text_index = None
    index_types: List[str] = []

    def values(self) -> np.ndarray:
        raise NotImplementedError(
            "MergedColumnReader is a planning surface; row data stays per-segment")


class MergedSegmentView:
    """Virtual segment over an unaligned set, planned against like one segment.

    The set is what a server holds of a table (its resident set), not what a
    query is routed to: `MeshQueryExecutor._views` keys a view by the set's
    segment paths, with the members' `view_key` (a consuming member's row
    count) as the value's subkey, so one view, one set of global dictionaries
    and so one key space a query shape serves every routed subset, and a set
    that changes replaces its view (`combine._drop_superseded`). A column
    whose dictionaries agree across the members keeps every remap `None`.

    Not mutable even when members are: the planner's mutable->host routing is about
    single-segment host scans; here mutable members are snapshotted into the stacked
    device block (see `SegmentSetBlock`), so the device path applies.
    """

    is_mutable = False

    def __init__(self, segments: Sequence[Any]):
        self.segments = list(segments)
        self.schema = segments[0].schema
        self.name = "merged:" + ",".join(s.name for s in segments)
        self.path = self.name
        self.num_docs = sum(s.num_docs for s in segments)
        # row count of each member at view-build time: mutable members may grow
        # concurrently; every consumer slices to this snapshot for consistency
        self.seg_docs: Tuple[int, ...] = tuple(s.num_docs for s in segments)
        self._columns: Dict[str, MergedColumnReader] = {}

    def column(self, name: str) -> MergedColumnReader:
        if name not in self._columns:
            self._columns[name] = MergedColumnReader(
                name, [s.column(name) for s in self.segments],
                mutable_flags=[getattr(s, "is_mutable", False) for s in self.segments],
                seg_docs=self.seg_docs)
        return self._columns[name]

    @property
    def column_names(self) -> List[str]:
        return self.segments[0].column_names

    def remap(self, col: str) -> Optional[List[Optional[np.ndarray]]]:
        """Per-segment local-id -> global-id translation tables (None for raw
        cols; a None ENTRY means that member's ids are already global)."""
        return self.column(col).remaps

    star_trees: List = []

    def __repr__(self) -> str:
        return f"MergedSegmentView({len(self.segments)} segments, docs={self.num_docs})"


class _SetFactsReader:
    """The first member's reader of a column whose dictionaries AGREE across
    the set, with the SET's facts where a plan folds a leaf by them: min of
    mins, max of maxes, `hasNulls` of any. A member's bloom filter speaks for
    that member alone, so the set has none."""

    bloom_filter = None

    def __init__(self, readers: Sequence[Any]):
        self._readers = list(readers)

    def __getattr__(self, name: str) -> Any:
        return getattr(self._readers[0], name)

    @property
    def num_docs(self) -> int:
        return sum(r.num_docs for r in self._readers)

    @property
    def meta(self) -> Dict[str, Any]:
        return dict(self._readers[0].meta, hasNulls=any(
            r.meta.get("hasNulls", False) for r in self._readers))

    @property
    def min_value(self) -> Any:
        return _set_bound(self._readers, "min_value", min)

    @property
    def max_value(self) -> Any:
        return _set_bound(self._readers, "max_value", max)


class SegmentSetFacts:
    """The planning surface of an ALIGNED set: the first member (its
    dictionaries are every member's, so ids stay segment-local and nothing is
    remapped), with each column's metadata the set's (`_SetFactsReader`).
    A plan made on the first member alone folds `WHERE pos < m` by that
    member's min/max, which row-ordered (time-ordered) members do not share."""

    def __init__(self, segments: Sequence[Any]):
        self.segments = list(segments)
        self.num_docs = sum(s.num_docs for s in segments)
        self._columns: Dict[str, _SetFactsReader] = {}

    def __getattr__(self, name: str) -> Any:
        return getattr(self.segments[0], name)

    def column(self, name: str) -> _SetFactsReader:
        if name not in self._columns:
            self._columns[name] = _SetFactsReader(
                [s.column(name) for s in self.segments])
        return self._columns[name]


def set_facts(segments: Sequence[Any]):
    """What an aligned set is planned on: the one member itself, or the
    first member with the set's facts."""
    return segments[0] if len(segments) == 1 else SegmentSetFacts(segments)


def view_key(segments: Sequence[Any]) -> Tuple:
    """Cache key for a segment set; mutable members key on their current row count so
    growth invalidates (and re-stacks) the view — the consuming-buffer device refresh."""
    return tuple((getattr(s, "path", s.name),
                  s.num_docs if getattr(s, "is_mutable", False) else -1)
                 for s in segments)
