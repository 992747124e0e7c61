"""Server-side segment tier lifecycle: HBM as a managed hot tier.

`block_for()` made HBM an unmanaged cache — every queried segment's columns
stage in and stay until unload, so a table larger than device memory OOMs.
This module turns the PR 14 ledger into policy (Tailwind's
accelerator/framework split: the accelerator tier holds only what keeps it
saturated, the framework tier absorbs the rest):

* **hot (HBM)** — ledger-accounted `SegmentBlock` arrays; bounded by
  `capacity * (1 - server.hbm.target.headroom.pct / 100)`.
* **warm (host RAM)** — the `ImmutableSegment` readers that back the host
  plan. Eviction is just `release_block`: the device arrays drop, the host
  readers still serve; re-promotion is the existing `block_for` path.
* **cold (deepstore)** — segments assigned COLD in the ideal state keep
  their catalog/routing registration but no local copy; the first query
  lazily downloads + loads them (bounded by the query's propagated
  deadline) and they admit like any other segment.

Three ledger-driven mechanisms live here:

1. an **admission gate** (`admit`) that predicts a block's bytes from
   segment metadata BEFORE staging and synchronously evicts colder victims
   until the prediction fits under the target;
2. a **pressure loop** (`run_pressure_sweep`, a server periodic task) that
   evicts past the target using a bytes-times-coldness cost score;
3. **graceful degradation**: when eviction can't free enough, `admit`
   returns False and the caller runs the host plan for that segment
   (`segmentsServedHostTier` in stats) instead of OOMing.

Eviction is refcount-aware: a segment acquired by an in-flight query is
never a victim — its block drop defers until `TableDataManager.release`
drains the refcount (the satellite deferred-release fix), so a running
query never loses device arrays mid-kernel.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Iterable, Optional

from ..engine.datablock import has_block, predicted_block_bytes, release_block
from ..utils.events import emit as emit_event
from ..utils.memledger import get_ledger
from ..utils.metrics import get_registry

#: default percent of capacity the admission gate / pressure loop keep free
#: (the `server.hbm.target.headroom.pct` cluster knob overrides)
DEFAULT_TARGET_HEADROOM_PCT = 10.0

#: pressure-loop cadence (seconds) — frequent enough that a burst of
#: admissions is walked back within a few seconds, rare enough to be noise
PRESSURE_INTERVAL_S = 5.0


# -- join-intermediate pricing (PR 17) ---------------------------------------
# The device hash-join stages both sides' key codes plus the matched output
# in HBM alongside whatever segments are already resident. An exploding join
# (duplicate build keys fanning every probe row out) must degrade to the host
# `hash_join` path — flagged `joinServedHostTier` — instead of OOMing, the
# same graceful-degradation contract the segment admission gate gives scans.

def predicted_join_bytes(build_rows: int, probe_rows: int, ncols: int,
                         dup_factor: float = 1.0) -> int:
    """Metadata-only sizing of a device join's working set: the staged key
    codes for both sides (padded to the kernel's pow2 shapes) plus the
    expanded candidate index pairs. `dup_factor` is the build-side key
    duplication (rows / distinct keys) — the probe match-rate estimate's
    upper bound: every probe row matching `dup_factor` build rows."""
    def pow2(n: int) -> int:
        return 1 << (max(1, int(n)) - 1).bit_length()
    code_bytes = 4 * (pow2(build_rows) * 2 + pow2(probe_rows))
    out_rows = int(max(0.0, float(probe_rows)) * max(1.0, float(dup_factor)))
    # candidate (li, ri) int64 pairs + one gathered output column set
    pair_bytes = out_rows * 16
    out_bytes = out_rows * max(1, int(ncols)) * 8
    return int(code_bytes + pair_bytes + out_bytes)


def join_device_budget_bytes(headroom_pct: float = DEFAULT_TARGET_HEADROOM_PCT
                             ) -> int:
    """HBM bytes a device join may claim right now: target residency budget
    minus what the ledger already holds (0 when scans have HBM pinned)."""
    cap, _ = get_ledger().capacity_bytes()
    target = int(cap * (1.0 - max(0.0, min(99.0, headroom_pct)) / 100.0))
    return max(0, target - get_ledger().resident_bytes())


class _Admitted:
    """Book-keeping for one hot-tier resident: which TableDataManager owns
    it (for the refcount check + the segment handle), when a query last
    touched it (the coldness half of the eviction score), and the predicted
    bytes reserved at admission — counted against the target until the block
    actually stages, so a query admitting N segments back-to-back cannot
    over-commit the gate before any of them hit the ledger."""

    __slots__ = ("mgr", "last_access", "reserved")

    def __init__(self, mgr, reserved: int = 0):
        self.mgr = mgr
        self.last_access = time.monotonic()
        self.reserved = int(reserved)


class TieringManager:
    """Per-server hot-tier admission + eviction policy over the process
    MemoryLedger. One instance per ServerNode; in-process multi-server test
    clusters therefore run several managers against the shared ledger, which
    only makes each manager MORE conservative (it sees the process total)."""

    def __init__(self, catalog=None, node: str = ""):
        self._catalog = catalog
        self._node = node          # event journal label (the server's id)
        self._lock = threading.Lock()
        self._admitted: Dict[str, _Admitted] = {}
        self._counters = {"admissions": 0, "rejections": 0, "evictions": 0,
                          "promotions": 0, "coldLoads": 0}

    # -- policy inputs -------------------------------------------------------

    def _headroom_pct(self) -> float:
        if self._catalog is not None:
            try:
                raw = self._catalog.get_property(
                    "clusterConfig/server.hbm.target.headroom.pct", None)
                if raw is not None:
                    return max(0.0, min(99.0, float(raw)))
            except (TypeError, ValueError):
                pass
        return DEFAULT_TARGET_HEADROOM_PCT

    def target_bytes(self) -> int:
        """The resident-bytes budget: capacity minus the target headroom."""
        cap, _ = get_ledger().capacity_bytes()
        return max(1, int(cap * (1.0 - self._headroom_pct() / 100.0)))

    def _reserved_bytes(self) -> int:
        """Predicted bytes of admitted-but-not-yet-staged blocks. A
        reservation expires the moment the block lands in the ledger (it
        would double-count) or the segment leaves its table manager."""
        total = 0
        with self._lock:
            for name, e in self._admitted.items():
                if not e.reserved:
                    continue
                seg = e.mgr.get(name) if e.mgr is not None else None
                if seg is None or has_block(seg):
                    e.reserved = 0
                else:
                    total += e.reserved
        return total

    # -- admission gate ------------------------------------------------------

    def admit(self, table: str, segment, mgr) -> bool:
        """Decide whether `segment` may stage its device block. Called in the
        query path BEFORE `block_for`; the caller routes rejected segments to
        the host plan. `mgr` is the owning TableDataManager (refcounts)."""
        name = getattr(segment, "name", str(segment))
        with self._lock:
            entry = self._admitted.get(name)
            if entry is not None and has_block(segment):
                entry.last_access = time.monotonic()   # hot-path touch
                return True
        try:
            # the COMPRESSED fused working set: queries skip the decoded HBM
            # cache for single-value dict columns. A segment admitted on
            # fused bytes whose plan takes the staged path stages the decoded
            # cache under the ledger (pressure eviction handles overshoot);
            # pricing decoded bytes would reject segments that fit
            need = predicted_block_bytes(segment, fused=True)
        # graftcheck: ignore[exception-hygiene] -- a segment without sizing
        # metadata (synthetic test doubles) admits defensively; the ledger
        # still accounts whatever it actually stages
        except Exception:
            need = 0
        ledger = get_ledger()
        target = self.target_bytes()
        # in-flight reservations count: a query admits its whole segment set
        # before any block stages, so the ledger alone lags the commitment
        if need and ledger.resident_bytes() + self._reserved_bytes() \
                + need > target:
            self._evict_until(max(0, target - need - self._reserved_bytes()),
                              exclude={name})
        if need and ledger.resident_bytes() + self._reserved_bytes() \
                + need > target:
            with self._lock:
                self._counters["rejections"] += 1
            get_registry().counter(
                "pinot_server_hbm_admission_rejects",
                {"table": table}).inc()
            emit_event("tier.admission.rejected", node=self._node or None,
                       table=table, segment=name, neededBytes=need)
            return False
        with self._lock:
            self._counters["admissions"] += 1
            self._admitted[name] = _Admitted(mgr, reserved=need)
        return True

    def settle(self, names: Iterable[str]) -> None:
        """End-of-query hook: drop in-flight reservations for segments the
        query admitted but never staged (a COUNT(*) touches no columns, so
        no block lands in the ledger) — a reservation that outlives its
        query would starve every later admission against phantom bytes."""
        with self._lock:
            for name in names:
                e = self._admitted.get(name)
                if e is not None:
                    e.reserved = 0

    def note_promotion(self) -> None:
        """A freshly admitted segment actually staged (host→HBM)."""
        with self._lock:
            self._counters["promotions"] += 1

    def note_cold_load(self) -> None:
        """A COLD segment was lazily downloaded + loaded for a query."""
        with self._lock:
            self._counters["coldLoads"] += 1
        get_registry().counter("pinot_server_hbm_cold_loads").inc()

    def forget(self, name: str) -> None:
        """Unload hook: the segment left this server entirely (reconcile
        removal / table drop) — drop its admission entry without counting
        an eviction."""
        with self._lock:
            self._admitted.pop(name, None)

    # -- eviction ------------------------------------------------------------

    def _evict_until(self, budget_bytes: int,
                     exclude: Optional[Iterable[str]] = None) -> int:
        """Evict hot-tier residents, coldest-and-biggest first, until the
        ledger total is at or under `budget_bytes` or no victims remain.
        Residents with a drained refcount only — an in-flight query never
        loses its block. Returns the number of evictions."""
        excluded = set(exclude or ())
        ledger = get_ledger()
        now = time.monotonic()
        with self._lock:
            candidates = [
                (name, e) for name, e in self._admitted.items()
                if name not in excluded]
        # cost score: bytes * coldness — the biggest, least-recently-touched
        # block frees the most HBM per promotion we might regret
        scored = sorted(
            candidates,
            key=lambda ne: -(ledger.resident_bytes(segment=ne[0])
                             * max(now - ne[1].last_access, 1e-3)))
        evicted = 0
        for name, entry in scored:
            if ledger.resident_bytes() <= budget_bytes:
                break
            mgr = entry.mgr
            if mgr is not None and mgr.refcount(name) > 0:
                continue   # in-flight query holds it; the sweep retries later
            seg = mgr.get(name) if mgr is not None else None
            if seg is not None:
                release_block(seg)
            else:
                get_ledger().release(segment=name)
            with self._lock:
                self._admitted.pop(name, None)
                self._counters["evictions"] += 1
            get_registry().counter("pinot_server_hbm_evictions").inc()
            emit_event("tier.evicted", node=self._node or None, segment=name)
            evicted += 1
        return evicted

    def run_pressure_sweep(self) -> int:
        """Periodic-task body: walk residency back under the target. A no-op
        at or under target (the common case), so the loop is cheap."""
        target = self.target_bytes()
        if get_ledger().resident_bytes() <= target:
            return 0
        return self._evict_until(target)

    # -- observability -------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Rides the server's `/debug/memory` payload under `tiering` and is
        summed per table into the controller's memoryStatus verdicts."""
        with self._lock:
            out: Dict[str, Any] = dict(self._counters)
            out["admittedSegments"] = len(self._admitted)
        out["targetBytes"] = self.target_bytes()
        out["targetHeadroomPct"] = self._headroom_pct()
        return out
