"""Device hash-join build/probe kernels (the PR 17 equi-join fast path).

The multistage `hash_join` was correctness-only host numpy: both sides fetched
to the host, keys factorized through a per-row Python dict, indices expanded
with `np.repeat`. This module moves the heavy part — ordering the build side
and locating each probe row's match range — onto the device as two jitted
launches, in two regimes (mirroring the PR 1 group-by ladder):

* **scatter regime** — a single integer key whose build-side value span fits
  under `KernelCaps.join_scatter_cap` direct-address slots: the build launch
  scatters row indices into a dense table (and counts slot occupancy — any
  duplicate key falls back to sort-merge), the probe launch is ONE gather
  that yields at most one candidate per probe row. This is the dimension-
  table shape: small unique surrogate keys.
* **sort-merge regime** — anything else: build codes (the 64-bit stable
  exchange hashes folded to 32 bits, `fold_codes32`) are sorted on device;
  the probe launch is a pair of `searchsorted`s yielding each probe row's
  [lo, lo+cnt) candidate range in the sorted build order.

Both probe launches also emit a 256-bucket histogram of the probe key hashes
— the JSPIM-style skew detector surfaced as `joinSkewPct` and consumed by the
runtime's hot-key salting.

Device codes are 32-bit (x64 stays disabled); candidates are therefore
*candidates*: the caller re-checks the full 64-bit codes and the actual key
values host-side, so fold collisions cost a few spurious pairs, never a wrong
answer. Padding follows the same rule — build pads sort to the top as
`0xFFFFFFFF` and surface as out-of-range row indices the caller drops.

Kernel shapes pad to powers of two and cache through `_cached_kernel`, so
retraces are bounded to log2 variants per regime.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query import stats as qstats
from .caps import get_caps
from .kernels import _cached_kernel, fetch_outputs

#: probe-hash histogram width for the skew detector (buckets = hash & 255)
SKEW_BUCKETS = 256

#: build-side sentinel code (pads sort to the top of the build order)
_PAD_CODE = np.uint32(0xFFFFFFFF)


def _next_pow2(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def scatter_table_cap() -> int:
    """Direct-address slot budget for the scatter regime."""
    return get_caps().join_scatter_cap


def fold_codes32(codes: np.ndarray) -> np.ndarray:
    """64-bit stable exchange hashes -> well-mixed uint32 device codes.

    x64 is disabled on the device path, so the kernels sort/compare 32-bit
    codes; the murmur-style finalizer keeps the fold collision rate at the
    birthday bound. Callers verify candidates on the full 64-bit codes."""
    x = np.ascontiguousarray(codes, dtype=np.uint64).copy()
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(33)
        x *= np.uint64(0xFF51AFD7ED558CCD)
        x ^= x >> np.uint64(29)
        x *= np.uint64(0xC4CEB9FE1A85EC53)
        x ^= x >> np.uint64(32)
    return (x & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def skew_pct_from_hist(hist: np.ndarray) -> float:
    """Excess mass of the hottest probe-hash bucket over uniform, as a
    percentage: 0 for a flat histogram, approaching 100 when one bucket (one
    hot key, typically) carries everything."""
    total = float(np.sum(hist))
    if total <= 0.0:
        return 0.0
    uniform = 1.0 / len(hist)
    top = float(np.max(hist)) / total
    return max(0.0, 100.0 * (top - uniform) / (1.0 - uniform))


# ---------------------------------------------------------------------------
# sort-merge regime
# ---------------------------------------------------------------------------

def _sort_build_kernel(m_pad: int):
    key = ("join_sort_build", m_pad, get_caps().token())

    def build():
        def fn(codes):
            order = jnp.argsort(codes)
            return codes[order], order.astype(jnp.int32)
        return jax.jit(fn)

    return _cached_kernel(key, build)


def _sorted_probe_kernel(m_pad: int, n_pad: int):
    key = ("join_sorted_probe", m_pad, n_pad, get_caps().token())

    def build():
        def fn(sorted_codes, probe, n_valid):
            valid = jnp.arange(probe.shape[0]) < n_valid
            lo = jnp.searchsorted(sorted_codes, probe, side="left")
            hi = jnp.searchsorted(sorted_codes, probe, side="right")
            cnt = jnp.where(valid, hi - lo, 0)
            hist = jnp.zeros((SKEW_BUCKETS,), jnp.int32).at[
                (probe & np.uint32(SKEW_BUCKETS - 1)).astype(jnp.int32)
            ].add(valid.astype(jnp.int32))
            return lo.astype(jnp.int32), cnt.astype(jnp.int32), hist
        return jax.jit(fn)

    return _cached_kernel(key, build)


def sort_merge_probe(build_codes: np.ndarray, probe_codes: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """Sort the build codes on device, probe with two searchsorted launches.

    Returns `(lo, cnt, order, skew_pct)` over the UNPADDED probe length:
    probe row i's candidate build rows are `order[lo[i] : lo[i] + cnt[i]]` in
    the device sort order. `order` spans the padded build length — entries
    `>= len(build_codes)` are padding the caller must drop. `skew_pct` is the
    probe-hash histogram's hot-bucket excess."""
    m, n = len(build_codes), len(probe_codes)
    t0 = time.perf_counter()
    m_pad, n_pad = _next_pow2(m), _next_pow2(n)
    bc = np.full(m_pad, _PAD_CODE, np.uint32)
    bc[:m] = build_codes
    sorted_dev, order_dev = _sort_build_kernel(m_pad)(bc)
    order = fetch_outputs(order_dev)
    t1 = time.perf_counter()
    qstats.record(qstats.JOIN_BUILD_MS, (t1 - t0) * 1000)

    pc = np.zeros(n_pad, np.uint32)
    pc[:n] = probe_codes
    lo_d, cnt_d, hist_d = _sorted_probe_kernel(m_pad, n_pad)(
        sorted_dev, pc, n)
    lo, cnt, hist = fetch_outputs((lo_d, cnt_d, hist_d))
    qstats.record(qstats.JOIN_PROBE_MS, (time.perf_counter() - t1) * 1000)
    return (lo[:n].astype(np.int64), cnt[:n].astype(np.int64),
            np.asarray(order).astype(np.int64), skew_pct_from_hist(hist))


# ---------------------------------------------------------------------------
# scatter (direct-address) regime
# ---------------------------------------------------------------------------

def _scatter_build_kernel(m_pad: int, size: int):
    key = ("join_scatter_build", m_pad, size, get_caps().token())

    def build():
        def fn(slots):
            # invalid/pad rows carry slot >= size: dropped by the scatter
            counts = jnp.zeros((size,), jnp.int32).at[slots].add(
                1, mode="drop")
            table = jnp.full((size,), -1, jnp.int32).at[slots].set(
                jnp.arange(slots.shape[0], dtype=jnp.int32), mode="drop")
            return table, counts.max()
        return jax.jit(fn)

    return _cached_kernel(key, build)


def _scatter_probe_kernel(n_pad: int, size: int):
    key = ("join_scatter_probe", n_pad, size, get_caps().token())

    def build():
        def fn(table, slots, n_valid):
            valid = ((jnp.arange(slots.shape[0]) < n_valid)
                     & (slots >= 0) & (slots < size))
            safe = jnp.where(valid, slots, 0)
            cand = jnp.where(valid, table[safe], -1)
            hist = jnp.zeros((SKEW_BUCKETS,), jnp.int32).at[
                safe & (SKEW_BUCKETS - 1)].add(valid.astype(jnp.int32))
            return cand, hist
        return jax.jit(fn)

    return _cached_kernel(key, build)


def scatter_probe(build_slots: np.ndarray, probe_slots: np.ndarray,
                  size: int) -> Optional[Tuple[np.ndarray, float]]:
    """Direct-address probe: build slots (key - min, already validated to
    [0, size) for live rows, >= size for null rows) scatter into a dense
    table; each probe row gathers at most one candidate. Returns
    `(cand, skew_pct)` with cand[i] the matching build row or -1 — or None
    when the build side has duplicate keys (caller falls back to
    sort-merge)."""
    m, n = len(build_slots), len(probe_slots)
    size = int(size)
    t0 = time.perf_counter()
    m_pad = _next_pow2(m)
    bs = np.full(m_pad, size, np.int32)
    bs[:m] = build_slots
    table_dev, maxc_dev = _scatter_build_kernel(m_pad, size)(bs)
    max_count = int(fetch_outputs(maxc_dev))
    t1 = time.perf_counter()
    qstats.record(qstats.JOIN_BUILD_MS, (t1 - t0) * 1000)
    if max_count > 1:
        return None   # duplicate build keys: the table can't hold the chain

    n_pad = _next_pow2(n)
    ps = np.full(n_pad, -1, np.int32)
    ps[:n] = probe_slots
    cand_d, hist_d = _scatter_probe_kernel(n_pad, size)(table_dev, ps, n)
    cand, hist = fetch_outputs((cand_d, hist_d))
    qstats.record(qstats.JOIN_PROBE_MS, (time.perf_counter() - t1) * 1000)
    return cand[:n].astype(np.int64), skew_pct_from_hist(hist)
