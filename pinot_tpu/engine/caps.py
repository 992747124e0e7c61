"""The constants that choose the kernel: one frozen table beside the ladder
that reads it.

Which kernel runs for a plan is decided from what the trace can see (padded
key count, decode-table width, whether the plan can fuse) against
the fields of `KernelCaps`, and these defaults are the only place the numbers
are written. There is one supported `device_kind` (the v5e), so the table is
a set of constants: retuning one is an edit of its default here, with the
chip measurement in `PERF.md` and the benchmark's cells as the judge.

`get_caps()` / `set_caps()` are the seam by which TESTS run the chunked and
sort regimes at 16k rows (`set_caps(KernelCaps(chunk_cap=4096))`); nothing in
the served path calls `set_caps`, and no file, environment variable or
cluster key feeds the table. `token()` is part of `KernelSpec.signature()`
and of the join kernels' cache keys, so a program built under other caps is
never reused.
"""

from __future__ import annotations

from dataclasses import astuple, dataclass
from typing import Tuple


@dataclass(frozen=True)
class KernelCaps:
    """Crossovers of the kernel ladders. Key counts are PADDED keys + 1 (the
    overflow bucket), as `_make_body` compares them."""

    # MASKED VPU reduce (`kernels._masked_sums`: a compare, a select and a
    # reduce a key cell and value row, no MXU) up to here. Its cost is linear
    # in cells x rows, the one-hot matmul's flat to 128 cells. Measured v5e,
    # 2^26 rows of uniform keys, ms (one-hot | masked; PERF.md, PR 36, call
    # 2): count + 1 value row 21.1 | 6.9 at 9 cells, 29.2 | 20.1 at 65,
    # 40.7 | 38.5 at 129; count + 7 rows 98.3 | 29.7 at 9, 106.4 | 87.2 at
    # 65, 118.2 | 146.7 at 129: it wins on both sides up to 65 cells and
    # LOSES at 129 with several value rows. Its sums are also tighter (TPC-H
    # Q1 at 67M rows: 1.6e-7 against the one-hot regime's 9.3e-6).
    masked_cap: int = 65
    # SKINNY one-hot matmul ([1+sums, N] @ [N, keys]) from there to here: each
    # 128-wide output column tile re-walks the full contraction, so cost grows
    # linearly in keys and the chunked 64x64 formulation overtakes it at some
    # key count. Where is not yet measured on the directly attached chip
    # (ROADMAP S8).
    matmul_cap: int = 512
    # CHUNKED 64x64 one-hot matmul (`_grouped_chunk64`) up to here: measured
    # v5e 16M rows count+sum 24ms @1024..2048 keys, 30ms @4096, 39ms @20k,
    # 69ms @32k. Its cost is linear in keys (~2.1ms per 4096-key chunk per
    # bf16 part per 16M rows) while a `jax.lax.sort` of 16M keys+payload is
    # ~67ms flat: crossover near 128k keys. Past it the sort regime
    # (`_grouped_partitioned`), at any row count: rows choose no regime
    # (past 2^24 a device the matmul regimes go slab by slab,
    # `kernels._slab_sums`).
    chunk_cap: int = 131072
    # per-key broadcast-reduce min/max up to here (VPU-bound: above it the
    # broadcast does more device work than `segment_min` / `segment_max`)
    minmax_bcast_cap: int = 1024
    # the widest key space (padded keys, the product of the group columns'
    # cardinalities) a GROUP BY answers as a DENSE table of every key: the
    # sort regime's dense decode, a fetch and a partial of one entry a key.
    # Past it the sort regime answers from its SORTED GROUPS alone
    # (`kernels._grouped_sparse`: the keys that occur, at most as many as the
    # rows that passed), whose cost is in rows and not in ids: TPC-H Q3's
    # GROUP BY over 16.8M order ids, of which about 117k occur (PERF.md,
    # section 5). No plan at or under it changes program.
    dense_keys: int = 1 << 21
    # rows a slab of the sort regime (a multiple of 64: its local ids are
    # the chunked matmul's two 64-wide digits)
    partition_block: int = 4096
    # bitmap-vs-gather filter regime: a dict-column filter leaf takes the
    # packed-word bitmap path when its estimated selectivity (matched docs /
    # docs) is at or below this fraction; denser predicates keep the
    # interval-compare / one-hot LUT path
    bitmap_sel_cap: float = 0.25
    # the longest decode table (padded entries) a fused plan may decode
    # in-kernel; a column with a larger dictionary sends its plan down the
    # staged two-launch path (`run_kernel_staged`)
    fused_lut_cap: int = 1 << 16
    # device hash-join regime split (PR 17): a single-integer-key build side
    # whose value span fits under this many direct-address slots takes the
    # scatter-table probe (one gather launch, at most one match per probe
    # row); wider/duplicate-key builds take the sort-merge probe ladder.
    join_scatter_cap: int = 1 << 20

    def token(self) -> Tuple:
        """The caps as a jit cache key: every field changes compiled code."""
        return astuple(self)


_ACTIVE = KernelCaps()


def get_caps() -> KernelCaps:
    return _ACTIVE


def set_caps(caps: KernelCaps) -> None:
    """Install other caps — for tests: nothing in the served path calls this.
    Flushes the compiled kernel caches: a cap change changes dispatch, and
    `KernelSpec.signature()` only protects NEW lookups, not memory held by
    stale entries."""
    global _ACTIVE
    if caps.partition_block <= 0 or caps.partition_block % 64:
        raise ValueError(f"partition_block must be a multiple of 64: {caps}")
    _ACTIVE = caps
    from ..parallel import combine
    from . import kernels
    kernels._KERNEL_CACHE.clear()
    combine._SHARD_KERNEL_CACHE.clear()
