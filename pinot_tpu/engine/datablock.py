"""Device-resident segment blocks: padded HBM columns + valid mask.

The TPU analog of the reference's `DataFetcher`/`DataBlockCache`
(`pinot-core/.../common/DataFetcher.java:47`): columns are transferred to device once per
segment, cached, and every query against the segment reuses them. Padding to power-of-two
row counts (min `format.ROW_TILE`) bucketizes shapes so jit kernels are reused across
segments instead of recompiling per row count.

Padding contract:
* dict-encoded columns pad with id = cardinality ("invalid id"); every LUT/decode array is
  sized `pow2(cardinality + 1)` so the invalid id hits a well-defined slot (False / 0).
* raw columns pad with 0; the block's `valid` mask excludes padding rows from every result.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from ..segment.format import ROW_TILE
from ..segment.reader import ColumnReader, ImmutableSegment
from ..utils.memledger import staged


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def _narrow(arr: np.ndarray) -> np.ndarray:
    """Explicitly narrow 64-bit arrays for device transfer (int64->int32, f64->f32)."""
    if arr.dtype == np.int64:
        return arr.astype(np.int32)
    if arr.dtype == np.float64:
        return arr.astype(np.float32)
    return arr


def padded_rows(num_docs: int) -> int:
    return max(ROW_TILE, _pow2(num_docs))


def lut_size(cardinality: int) -> int:
    return _pow2(cardinality + 1)


# Bitmap filter indexes exist only for dict columns up to this cardinality:
# the packed representation costs card * padded/8 bytes of HBM and the fused
# OR-reduce walks card * padded/32 words, so past a few dozen distinct values
# the forward-id gather/one-hot path is both smaller and cheaper.
BITMAP_MAX_CARD = 64


class SegmentBlock:
    """Lazy per-column device cache for one immutable segment."""

    def __init__(self, segment: ImmutableSegment):
        self.segment = segment
        self.num_docs = segment.num_docs
        self.padded = padded_rows(self.num_docs)
        self._ids: Dict[str, jnp.ndarray] = {}
        self._raw: Dict[str, jnp.ndarray] = {}
        self._dict_vals: Dict[str, jnp.ndarray] = {}
        self._decoded: Dict[str, jnp.ndarray] = {}
        self._for: Dict[str, Optional[tuple]] = {}
        self._valid: Optional[jnp.ndarray] = None
        self._valid_words: Optional[jnp.ndarray] = None
        self._null: Dict[str, jnp.ndarray] = {}
        self._bitmaps: Dict[str, Optional[jnp.ndarray]] = {}

    @property
    def valid(self) -> jnp.ndarray:
        if self._valid is None:
            v = np.zeros(self.padded, dtype=bool)
            v[:self.num_docs] = True
            self._valid = staged(jnp.asarray(v), self.segment.name,
                                 "valid")
        return self._valid

    @property
    def valid_words(self) -> jnp.ndarray:
        """Packed `valid`: uint32[padded // 32], same bit layout as the bitmap
        index rows. ANDed onto word-domain filter results so a NOT (which sets
        padding bits) never counts padding docs — keeps the popcount COUNT
        path pure word-domain work."""
        if self._valid_words is None:
            w = np.zeros(self.padded // 32, dtype=np.uint32)
            docs = np.arange(self.num_docs, dtype=np.int64)
            np.bitwise_or.at(w, docs >> 5,
                             np.uint32(1) << (docs & 31).astype(np.uint32))
            self._valid_words = staged(jnp.asarray(w), self.segment.name,
                                       "valid_words")
        return self._valid_words

    def ids(self, col: str) -> jnp.ndarray:
        """Padded int32 dict-id array for a dict-encoded column.

        Multi-value columns come back as a [padded_rows, max_num_values] matrix:
        each row's ids left-justified, the rest (and all padding rows) filled with
        the out-of-dictionary id = cardinality, which every LUT maps to False/0.
        Kernels reduce MV leaf masks with any(axis=-1) ("row matches if ANY value
        matches", reference: MVScanDocIdIterator semantics)."""
        if col not in self._ids:
            reader = self.segment.column(col)
            assert reader.has_dictionary, f"{col} has no dictionary"
            if getattr(reader, "is_multi_value", False):
                w = max(reader.max_num_values, 1)
                flat = np.asarray(reader.fwd).astype(np.int32)
                off = np.asarray(reader.mv_offsets)
                counts = np.diff(off)
                mat = np.full((self.padded, w), reader.cardinality, dtype=np.int32)
                rows = np.repeat(np.arange(self.num_docs), counts)
                within = np.arange(len(flat)) - np.repeat(off[:-1], counts)
                mat[rows, within] = flat
                self._ids[col] = staged(jnp.asarray(mat),
                                        self.segment.name, "ids", name=col)
            else:
                arr = np.asarray(reader.fwd).astype(np.int32)
                padded = np.full(self.padded, reader.cardinality, dtype=np.int32)
                padded[:self.num_docs] = arr
                self._ids[col] = staged(jnp.asarray(padded),
                                        self.segment.name, "ids", name=col)
        return self._ids[col]

    def raw(self, col: str) -> jnp.ndarray:
        """Padded raw-value array for a non-dict numeric column.

        64-bit types narrow to 32-bit explicitly (device compute is int32/float32; the
        planner falls back to host for columns whose min/max exceed int32 — see
        `planner._expr_device_ok`).
        """
        if col not in self._raw:
            reader = self.segment.column(col)
            arr = np.asarray(reader.fwd)
            arr = _narrow(arr)
            padded = np.zeros(self.padded, dtype=arr.dtype)
            padded[:self.num_docs] = arr
            self._raw[col] = staged(jnp.asarray(padded),
                                    self.segment.name, "raw", name=col)
        return self._raw[col]

    def dict_values(self, col: str) -> jnp.ndarray:
        """Decode table: dictionary values padded to `lut_size(card)` (invalid id -> 0).

        Numeric dict decode on device is `dict_values(col)[ids(col)]`: a tree
        of selects for a small table, one gather for a wide one
        (`kernels._fused_env`).
        """
        if col not in self._dict_vals:
            reader = self.segment.column(col)
            vals = _narrow(np.asarray(reader.dictionary.values))
            out = np.zeros(lut_size(reader.cardinality), dtype=vals.dtype)
            out[:len(vals)] = vals
            self._dict_vals[col] = staged(jnp.asarray(out),
                                          self.segment.name, "dict",
                                          name=col)
        return self._dict_vals[col]

    def for_form(self, col: str) -> Optional[tuple]:
        """Frame-of-reference compressed form for a raw integer column:
        `(base, deltas)` where `deltas` is the padded column rebased to its
        metadata minimum in the narrowest unsigned dtype that holds the range
        (uint8/uint16), or None when FOR doesn't pay (non-int, multi-value,
        dict-encoded, unknown min/max, range >= 2^16, or a base outside
        int32 — the base rides the kernel's int32 scalar stream).

        The fused kernel reconstructs values in-register as
        `deltas.astype(int32) + base` (`kernels._fused_env`), so the resident
        form is 1-2 bytes/row instead of the 4-byte decoded column. Padding
        rows hold delta 0 and reconstruct to `base`; they are masked out of
        every result by `valid` exactly like the raw path's 0 padding."""
        if col not in self._for:
            self._for[col] = self._build_for(col)
        return self._for[col]

    def _build_for(self, col: str) -> Optional[tuple]:
        reader = self.segment.column(col)
        if (reader.has_dictionary
                or getattr(reader, "is_multi_value", False)):
            return None
        mn, mx = reader.min_value, reader.max_value
        if not isinstance(mn, (int, np.integer)) \
                or not isinstance(mx, (int, np.integer)):
            return None
        arr = np.asarray(reader.fwd)
        if arr.dtype.kind != "i":
            return None
        rng = int(mx) - int(mn)
        if not 0 <= rng < (1 << 16) or not -(2 ** 31) <= int(mn) < 2 ** 31:
            return None
        dt = np.uint8 if rng < (1 << 8) else np.uint16
        if dt(0).nbytes >= _narrow(arr).dtype.itemsize:
            return None  # deltas would be no narrower than the raw view
        padded = np.zeros(self.padded, dtype=dt)
        padded[:self.num_docs] = (arr.astype(np.int64) - int(mn)).astype(dt)
        return (int(mn), staged(jnp.asarray(padded), self.segment.name,
                                "for", name=col))

    def bitmap_words(self, col: str) -> Optional[jnp.ndarray]:
        """Packed bitmap filter index: uint32[cardinality, padded // 32].

        Row c is the per-doc membership bitmap of dict id c, packed 32 docs per
        word (doc r -> word r >> 5, bit r & 31). Input staging gathers only
        the LUT-selected rows per query and the kernel OR-folds them, so word
        traffic scales with the leaf's selectivity, not cardinality. Built
        host-side once from the forward index and cached in HBM alongside the
        id column; None when the column is ineligible (no dictionary,
        multi-value, or cardinality above BITMAP_MAX_CARD)."""
        if col not in self._bitmaps:
            reader = self.segment.column(col)
            card = reader.cardinality
            if (not reader.has_dictionary or card <= 0
                    or card > BITMAP_MAX_CARD
                    or getattr(reader, "is_multi_value", False)):
                self._bitmaps[col] = None
            else:
                ids = np.asarray(reader.fwd).astype(np.int64)
                words = np.zeros((card, self.padded // 32), dtype=np.uint32)
                docs = np.arange(self.num_docs, dtype=np.int64)
                # star-tree record tables carry the out-of-dictionary star
                # marker (id == cardinality): such rows match no dict value,
                # so they set no bit — same False every LUT gives the id
                keep = ids < card
                np.bitwise_or.at(
                    words, (ids[keep], (docs >> 5)[keep]),
                    (np.uint32(1) << (docs & 31).astype(np.uint32))[keep])
                self._bitmaps[col] = staged(jnp.asarray(words),
                                            self.segment.name, "bitmap",
                                            name=col)
        return self._bitmaps[col]

    def null_mask(self, col: str) -> jnp.ndarray:
        """Padded bool array: True where the stored value is a filled-in null."""
        if col not in self._null:
            reader = self.segment.column(col)
            nb = reader.null_bitmap
            padded = np.zeros(self.padded, dtype=bool)
            if nb is not None:
                padded[:self.num_docs] = nb
            self._null[col] = staged(jnp.asarray(padded),
                                     self.segment.name, "null", name=col)
        return self._null[col]

    def values(self, col: str) -> jnp.ndarray:
        """Decoded numeric values on device regardless of encoding — the
        STAGED layout's value input.

        Dict columns are decoded HOST-side once and the materialized array
        cached in HBM (the TPU analog of the reference's `DataFetcher`
        value-buffer cache, `DataFetcher.java:47`). Fused plans never call
        this: they route `dict_values(col)` + `ids(col)` (or `for_form`)
        into the kernel and decode in-register, so no decoded column is ever
        written back to HBM. The staged path keeps this for the two inputs
        only it runs (decode tables over `fused_lut_cap`, multi-value value
        columns).
        """
        reader = self.segment.column(col)
        if not reader.has_dictionary:
            return self.raw(col)
        if col not in self._decoded:
            vals = _narrow(np.asarray(reader.dictionary.values))
            fwd = np.asarray(reader.fwd).astype(np.int64)
            padded = np.zeros(self.padded, dtype=vals.dtype)
            padded[:self.num_docs] = vals[fwd]
            self._decoded[col] = staged(jnp.asarray(padded),
                                        self.segment.name, "decoded",
                                        name=col)
        return self._decoded[col]


_BLOCK_ATTR = "_device_block"


def has_block(segment) -> bool:
    """True when the segment already holds a cached device block — the
    tiering admission gate's hot-path check (an admitted segment re-touches
    its entry instead of re-predicting bytes)."""
    return getattr(segment, _BLOCK_ATTR, None) is not None


def predicted_block_bytes(segment: ImmutableSegment,
                          fused: bool = False) -> int:
    """Upper bound on the HBM bytes a fully-staged SegmentBlock for this
    segment can occupy, computed from segment metadata alone (no staging, no
    column reads) — what the tiering admission gate charges against ledger
    headroom BEFORE `block_for` stages anything.

    Deliberately conservative: every column is priced as if every lazy cache
    the block can build for it (ids + LUT + decoded + bitmap, or raw) gets
    built. Overestimating only host-tiers a segment early; underestimating
    is how admission OOMs.

    `fused=True` prices the compressed-resident layout instead: fused plans
    decode single-value dict columns in-register (`kernels._fused_env`), so
    no decoded-values cache is ever built for them and admission charges only
    ids + LUT (+ bitmap). Multi-value dict columns keep the decoded term —
    they are staged-only. A segment rejected under the fused price still
    degrades through the staged/host ladder; it is never force-staged past
    headroom."""
    padded = padded_rows(segment.num_docs)
    # valid mask + packed valid words (built for every block)
    total = padded * 1 + (padded // 32) * 4
    for col, meta in segment.metadata.get("columns", {}).items():
        width = max(int(meta.get("maxNumValues", 1) or 1), 1) \
            if meta.get("multiValue") else 1
        if meta.get("hasDictionary"):
            card = int(meta.get("cardinality", 0) or 0)
            total += padded * 4 * width            # int32 ids
            total += lut_size(card) * 4            # dict LUT (narrowed to 32-bit)
            if not fused or width > 1:
                total += padded * 4                # decoded-values cache
            if 0 < card <= BITMAP_MAX_CARD and width == 1:
                total += card * (padded // 32) * 4  # packed bitmap index
        else:
            total += padded * 4                    # raw view (narrowed)
        total += padded * 1                        # null mask
    return total


def block_for(segment: ImmutableSegment) -> SegmentBlock:
    blk = getattr(segment, _BLOCK_ATTR, None)
    if blk is None:
        blk = SegmentBlock(segment)
        setattr(segment, _BLOCK_ATTR, blk)
    return blk


def release_block(segment) -> None:
    """Unload hook: drop a segment's cached device block and deregister its
    ledger entries. Without this the `_device_block` attribute keeps every
    column array alive until the segment object itself is GC'd — exactly the
    leak class the ledger exists to expose."""
    from ..utils.memledger import get_ledger
    if getattr(segment, _BLOCK_ATTR, None) is not None:
        try:
            delattr(segment, _BLOCK_ATTR)
        except AttributeError:
            pass
    get_ledger().release(segment=getattr(segment, "name", str(segment)))
