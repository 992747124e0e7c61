"""Device-memory residency ledger: who owns every byte of HBM.

Device memory is the scarcest resource in the system (ROADMAP "tiered
storage" item; PIMDAL's memory-bottleneck framing in PAPERS.md) and until
now nothing could say *what* is resident, *who* owns it, or *how close to
the edge* a server is. This ledger is the accounting substrate every
promotion/eviction policy will sit on:

* every named device allocation — segment column arrays, bitmap/valid
  words, consuming-segment staging, decoded/dedupe cache outputs —
  registers `(table, segment, kind, nbytes)` at staging time via the
  `staged()` wrapper and deregisters on release (segment unload, table
  drop, consuming retire);
* `reconcile()` checks the ledger total against jax's live-buffer view so
  drift (an allocation path that forgot to register, or a release hook
  that leaked) is *detectable*, not silent;
* residency is exported as `pinot_server_hbm_resident_bytes{table,kind}`
  gauges plus total/watermark/headroom/capacity gauges, and `snapshot()`
  backs the server's `GET /debug/memory` panel.

The ledger is process-global (same idiom as the metrics registry):
registration happens deep in engine code that has no server handle. In
multi-server in-process test clusters the servers therefore share one
ledger — per-server residency from `/debug/memory` is the *process* view
there, which is also what jax reports, so reconciliation stays honest.

Kinds are a bounded enum (`KINDS`): ledger gauges are labeled
`{table, kind}`, and metric label values must stay lifecycle-bounded —
never label by segment or query.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .metrics import get_registry

#: the closed set of allocation kinds the ledger accounts (gauge label values)
KINDS = ("ids", "raw", "dict", "valid", "valid_words", "bitmap", "null",
         "decoded", "consuming", "transient")

#: fallback per-device HBM capacity when jax can't report one (CPU backend);
#: override with PINOT_TPU_HBM_CAPACITY_BYTES
_DEFAULT_CAPACITY = 16 << 30

#: watermark history ring length (matches the metrics Gauge history ring)
_HISTORY_LEN = 240

#: min seconds between gauge publishes on the register hot path. Staging a
#: segment registers one entry per column in a tight loop; publishing every
#: gauge per entry would dominate the (near-free on CPU) device transfer.
#: Deferred updates flush on the next release/snapshot/flush or after this
#: interval — internal accounting is always exact, only gauge freshness is
#: throttled.
_PUBLISH_INTERVAL_S = 0.05


def device_capacity_bytes() -> Tuple[int, bool]:
    """(capacity_bytes, estimated): the device memory budget headroom is
    computed against. Order: env override, jax `memory_stats()["bytes_limit"]`,
    then — on the CPU backend only, which reports no limit — a flagged 16 GiB
    estimate. An accelerator that reports no `bytes_limit` raises: budgeting
    admission against a guess there is a wrong answer that looks right."""
    env = os.environ.get("PINOT_TPU_HBM_CAPACITY_BYTES")
    if env:
        try:
            return max(1, int(env)), False
        except ValueError:
            pass
    import jax
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return _DEFAULT_CAPACITY, True
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit <= 0:
        raise RuntimeError(
            f"{dev.device_kind!r} reports no memory_stats()['bytes_limit']; "
            "set PINOT_TPU_HBM_CAPACITY_BYTES / server.hbm.capacity.bytes")
    return limit, False


def live_device_bytes() -> Optional[int]:
    """Sum of nbytes over jax's live device arrays, or None when the runtime
    can't enumerate them — the reconciliation ground truth."""
    try:
        import jax
        total = 0
        for arr in jax.live_arrays():
            try:
                total += int(arr.nbytes)
            # graftcheck: ignore[exception-hygiene] -- a deleted/donated
            # buffer raising on .nbytes mid-enumeration just drops out of
            # the sum; reconcile() reports the resulting drift
            except Exception:
                pass
        return total
    except Exception:
        return None


class MemoryLedger:
    """Byte-accurate device-residency accounting, keyed
    (table, segment, kind, name); re-registration of the same key replaces
    (idempotent re-staging, e.g. a cache rebuild)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str, str, str], int] = {}
        self._by_table_kind: Dict[Tuple[str, str], int] = {}
        self._segment_tables: Dict[str, str] = {}
        self._total = 0
        self._transient_peak = 0
        self._watermark = 0
        self._watermark_history: deque = deque(maxlen=_HISTORY_LEN)
        self._capacity, self._capacity_estimated = device_capacity_bytes()
        # gauge-handle cache + publish throttle (rebuilt when the registry
        # is swapped out, e.g. a test reset)
        self._reg = None
        self._tk_gauges: Dict[Tuple[str, str], Any] = {}
        self._g_total: Any = None
        self._g_headroom: Any = None
        self._dirty: set = set()
        self._last_publish = float("-inf")

    # -- table attribution ---------------------------------------------------

    def bind_segment(self, table: str, segment: str) -> None:
        """Record that `segment` belongs to `table` so staging sites that
        only know the segment (datablock) still attribute bytes correctly."""
        with self._lock:
            self._segment_tables[segment] = table

    def _table_for_locked(self, segment: str) -> str:
        t = self._segment_tables.get(segment)
        if t is not None:
            return t
        # LLC names embed the table: {table}__{partition}__{seq}__{creation}
        if "__" in segment:
            return segment.split("__", 1)[0]
        return "-"

    # -- write side ----------------------------------------------------------

    def register(self, table: Optional[str], segment: str, kind: str,
                 name: str, nbytes: int) -> None:
        """Account a named device allocation. `table=None` resolves through
        the segment binding (or the LLC name prefix)."""
        nbytes = int(nbytes)
        with self._lock:
            t = table if table is not None else self._table_for_locked(segment)
            key = (t, segment, kind, name)
            prev = self._entries.get(key, 0)
            self._entries[key] = nbytes
            delta = nbytes - prev
            self._total += delta
            tk = (t, kind)
            self._by_table_kind[tk] = self._by_table_kind.get(tk, 0) + delta
            self._publish_locked(dirty=(tk,))

    def release(self, table: Optional[str] = None,
                segment: Optional[str] = None,
                kind: Optional[str] = None) -> int:
        """Drop every entry matching the non-None filters (and the segment's
        table binding when releasing by segment); returns bytes released."""
        with self._lock:
            doomed = [k for k in self._entries
                      if (table is None or k[0] == table)
                      and (segment is None or k[1] == segment)
                      and (kind is None or k[2] == kind)]
            freed = 0
            dirty = set()
            for key in doomed:
                nbytes = self._entries.pop(key)
                freed += nbytes
                tk = (key[0], key[2])
                self._by_table_kind[tk] = self._by_table_kind.get(tk, 0) - nbytes
                dirty.add(tk)
            self._total -= freed
            if segment is not None:
                self._segment_tables.pop(segment, None)
            if table is not None and segment is None:
                stale = [s for s, t in self._segment_tables.items()
                         if t == table]
                for s in stale:
                    self._segment_tables.pop(s, None)
            if doomed:
                self._publish_locked(dirty=tuple(dirty), force=True)
            return freed

    def note_transient(self, nbytes: int) -> None:
        """Track the peak transient launch/fetch buffer footprint — a single
        gauge update, cheap enough for the per-fetch hot path."""
        nbytes = int(nbytes)
        with self._lock:
            if nbytes <= self._transient_peak:
                return
            self._transient_peak = nbytes
            reg = get_registry()
            reg.gauge("pinot_server_hbm_transient_peak_bytes").set(nbytes)
            self._update_watermark_locked()

    def set_capacity(self, nbytes: int, estimated: bool = False) -> None:
        """Override the device-memory budget at runtime (the
        `server.hbm.capacity.bytes` cluster knob; tests/bench pin tiny
        capacities per server with it). Republishes the capacity gauge —
        `_gauges_locked` only publishes it once per registry swap — and
        force-flushes headroom so verdicts see the new budget immediately."""
        nbytes = max(1, int(nbytes))
        with self._lock:
            self._capacity = nbytes
            self._capacity_estimated = bool(estimated)
            reg = self._gauges_locked()
            reg.gauge("pinot_server_hbm_capacity_bytes").set(nbytes)
            self._publish_locked(force=True)

    def capacity_bytes(self) -> Tuple[int, bool]:
        """(capacity_bytes, estimated) — the budget admission/eviction and
        headroom math run against."""
        with self._lock:
            return self._capacity, self._capacity_estimated

    def flush(self) -> None:
        """Publish any throttle-deferred gauge updates now. The register hot
        path defers gauge writes up to `_PUBLISH_INTERVAL_S`; release and
        snapshot flush implicitly — call this before reading gauges straight
        off the registry after a registration burst."""
        with self._lock:
            self._publish_locked(force=True)

    # -- read side -----------------------------------------------------------

    def resident_bytes(self, table: Optional[str] = None,
                       segment: Optional[str] = None,
                       kind: Optional[str] = None) -> int:
        with self._lock:
            if table is None and segment is None and kind is None:
                return self._total
            return sum(n for (t, s, k, _), n in self._entries.items()
                       if (table is None or t == table)
                       and (segment is None or s == segment)
                       and (kind is None or k == kind))

    def snapshot(self) -> Dict[str, Any]:
        """The `GET /debug/memory` payload: totals, kind/table breakdowns,
        top segments by bytes, watermark history, capacity + headroom."""
        with self._lock:
            self._publish_locked(force=True)   # flush throttled gauge updates
            kinds: Dict[str, int] = {}
            tables: Dict[str, int] = {}
            segments: Dict[Tuple[str, str], int] = {}
            for (t, s, k, _), n in self._entries.items():
                kinds[k] = kinds.get(k, 0) + n
                tables[t] = tables.get(t, 0) + n
                segments[(t, s)] = segments.get((t, s), 0) + n
            top = sorted(segments.items(), key=lambda kv: -kv[1])[:10]
            cap = self._capacity
            headroom = max(0.0, 100.0 * (cap - self._total) / cap)
            return {
                "totalBytes": self._total,
                "transientPeakBytes": self._transient_peak,
                "capacityBytes": cap,
                "capacityEstimated": self._capacity_estimated,
                "headroomPct": round(headroom, 3),
                "watermarkBytes": self._watermark,
                "watermarkHistory": list(self._watermark_history),
                "entries": len(self._entries),
                "kinds": kinds,
                "tables": tables,
                "topSegments": [{"table": t, "segment": s, "bytes": n}
                                for (t, s), n in top],
            }

    def reconcile(self, baseline_bytes: int = 0) -> Dict[str, Any]:
        """Ledger total vs jax live-buffer bytes. `baseline_bytes` subtracts
        allocations that predate the measurement window (compile-time
        constants) so drift isolates *tracked* staging.
        driftPct is None when the runtime can't enumerate live arrays."""
        device = live_device_bytes()
        with self._lock:
            ledger = self._total
        out: Dict[str, Any] = {"ledgerBytes": ledger, "deviceBytes": device,
                               "baselineBytes": int(baseline_bytes)}
        if device is None:
            out["driftBytes"] = None
            out["driftPct"] = None
            return out
        tracked = device - int(baseline_bytes)
        drift = tracked - ledger
        denom = max(ledger, tracked, 1)
        out["driftBytes"] = drift
        out["driftPct"] = round(100.0 * abs(drift) / denom, 3)
        return out

    # -- internals -----------------------------------------------------------

    def _update_watermark_locked(self) -> None:
        footprint = self._total + self._transient_peak
        if footprint > self._watermark:
            self._watermark = footprint
            self._watermark_history.append(
                (int(time.time() * 1000), footprint))
            get_registry().gauge(
                "pinot_server_hbm_watermark_bytes").set(footprint)

    def _gauges_locked(self):
        """Registry + cached gauge handles, rebuilt when the process registry
        is swapped (test resets) — handle reuse keeps the flush path off the
        registry's lookup lock."""
        reg = get_registry()
        if self._reg is not reg:
            self._reg = reg
            self._tk_gauges = {}
            self._g_total = reg.gauge("pinot_server_hbm_resident_total_bytes")
            self._g_headroom = reg.gauge("pinot_server_hbm_headroom_pct")
            # capacity is fixed for the process: published once per registry
            reg.gauge("pinot_server_hbm_capacity_bytes").set(self._capacity)
        return reg

    def _publish_locked(self, dirty: Iterable[Tuple[str, str]] = (),
                        force: bool = False) -> None:
        self._dirty.update(dirty)
        now = time.perf_counter()
        if not force and (now - self._last_publish) < _PUBLISH_INTERVAL_S:
            return   # hot staging loop: defer; flushed by release/snapshot
        self._last_publish = now
        reg = self._gauges_locked()
        for tk in self._dirty:
            t, k = tk
            n = self._by_table_kind.get(tk, 0)
            if n <= 0:
                # stale teardown: a dropped table/kind must not keep
                # exporting a zero series forever
                # graftcheck: ignore[lock-unguarded-write] -- _locked suffix:
                # every caller holds self._lock (register/release/note_transient)
                self._by_table_kind.pop(tk, None)
                self._tk_gauges.pop(tk, None)
                reg.remove_gauge("pinot_server_hbm_resident_bytes",
                                 {"table": t, "kind": k})
            else:
                g = self._tk_gauges.get(tk)
                if g is None:
                    g = reg.gauge("pinot_server_hbm_resident_bytes",
                                  {"table": t, "kind": k})
                    self._tk_gauges[tk] = g
                g.set(n)
        self._dirty.clear()
        self._g_total.set(self._total)
        cap = self._capacity
        self._g_headroom.set(
            max(0.0, round(100.0 * (cap - self._total) / cap, 3)))
        self._update_watermark_locked()


# -- process-global singleton (same idiom as utils.metrics.REGISTRY) ---------

_LEDGER = MemoryLedger()


def get_ledger() -> MemoryLedger:
    return _LEDGER


def reset_ledger() -> None:
    """Test hook: fresh ledger (the old one's gauges are left to the test's
    registry reset)."""
    global _LEDGER
    _LEDGER = MemoryLedger()


def staged(arr, segment: str, kind: str, name: Optional[str] = None,
           table: Optional[str] = None):
    """Register a freshly staged device array in the ledger and return it
    unchanged — THE sanctioned wrapper for device staging in engine/segment/
    cluster code (the `memory-untracked-staging` graftcheck rule flags bare
    `jnp.asarray`/`jax.device_put` staging that bypasses it)."""
    try:
        nbytes = int(arr.nbytes)
    except (AttributeError, TypeError):
        nbytes = 0
    _LEDGER.register(table, segment, kind, name or kind, nbytes)
    return arr
