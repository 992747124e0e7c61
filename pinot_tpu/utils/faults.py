"""graftfault: the deterministic, seed-driven fault-injection plane.

The failure machinery that makes this a *distributed* store — broker retry
rounds, hedged requests, `FailureDetector` backoff probing, the committer
takeover FSM, `reassign_dead_consuming_segments` — only earns trust when it
runs under actual faults. This module provides the injection side: named
fault sites threaded through the transports, the server execute path, the
stream consumers, the deep store, and the device pipeline, each crossed via
one `fault_point(site)` call.

Design constraints, in order:

1. **Zero overhead when disabled.** `fault_point` is on the mux write loop,
   the consume pump, and the server execute path; disabled it is one module
   global load + a None check. No registry lookups, no dict walks.
2. **Deterministic under a seed.** Every site draws from its own
   `random.Random(f"{seed}:{site}")` stream, so concurrency *between* sites
   never perturbs a site's decision sequence, and two runs of the same
   schedule against the same workload fire the same faults. For strict
   cross-run determinism under multi-threaded traffic use probability 1.0
   with a `count` budget — firing then depends only on the budget, not on
   thread interleaving of draws.
3. **Typed failures.** An injected fault raises `FaultInjected`, a
   `ConnectionError` subclass — the broker's existing failure taxonomy
   (`_is_transport_failure`) classifies it as a transport death, which is
   exactly what the sites simulate (crashed server, reset stream, lost
   partition). Latency-only sites (`*.slow`, `stream.stall`) sleep and
   return.

Activation: `activate(schedule)` / `deactivate()` (or the `active(...)`
context manager) from a test fixture, or cluster-wide via the clusterConfig
knob `fault.schedule` holding the JSON spec — role services call
`activate_from_config(catalog)` at startup. The plane is process-wide (one
module-level slot), mirroring the metrics registry's one-flat-surface idiom.

Spec format (JSON or the equivalent dict)::

    {"seed": 42,
     "sites": {
       "server.slow":  {"p": 0.3, "latencyMs": 50, "count": 10},
       "server.crash": {"p": 1.0, "count": 1},
       "mux.frame.drop": {"p": 0.05}}}

Per-site fields: `p` (fire probability, default 1.0), `count` (total fire
budget, default unlimited), `latencyMs` (sleep before the verdict, default
0), `fail` (raise `FaultInjected`; defaults to true when `latencyMs` is 0,
false otherwise — a latency-only spec is a slowdown, not a failure).
"""

from __future__ import annotations

import json
import random
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, Optional, Union

#: every named injection site threaded through the stack; FaultSchedule
#: validates spec keys against this so a typo'd site fails loudly at parse
#: time instead of silently never firing.
SITES = frozenset((
    "mux.frame.drop",       # mux client write loop: frame vanishes on the wire
    "mux.conn.reset",       # outbound connection mint fails (mux + pooled HTTP)
    "server.crash",         # server partial-execute dies as a transport failure
    "server.slow",          # server partial-execute stalls (straggler)
    "stream.stall",         # stream fetch stalls (slow upstream)
    "stream.partition.lost",  # stream fetch dies (lost partition / rebalance)
    "deepstore.upload.fail",  # segment upload to the deep store fails
    "deepstore.download.fail",  # segment download from the deep store fails
    "device.launch.slow",   # device pipeline dispatch stalls before launch
))


class FaultInjected(ConnectionError):
    """An injected fault. Subclasses ConnectionError deliberately: the
    broker/server failure taxonomy treats it as a transport death, which is
    the behavior the fault sites simulate."""

    def __init__(self, site: str):
        super().__init__(f"graftfault: injected fault at {site!r}")
        self.site = site


class _SiteSpec:
    __slots__ = ("site", "probability", "count", "latency_ms", "fail", "rng")

    def __init__(self, site: str, probability: float = 1.0,
                 count: Optional[int] = None, latency_ms: float = 0.0,
                 fail: Optional[bool] = None, seed: int = 0):
        self.site = site
        self.probability = float(probability)
        self.count = count if count is None else int(count)
        self.latency_ms = float(latency_ms)
        # latency-only specs model slowdowns; anything else is a failure
        self.fail = bool(fail) if fail is not None else self.latency_ms == 0.0
        # per-site stream: cross-site concurrency never perturbs a site's
        # draw sequence, so same seed + same workload => same decisions
        self.rng = random.Random(f"{seed}:{site}")


class FaultSchedule:
    """Seeded, budgeted fault decisions for a set of sites.

    Thread-safe; `fired()` exposes per-site fire counts so tests can
    assert exactly what the schedule did."""

    def __init__(self, sites: Dict[str, dict], seed: int = 0):
        unknown = set(sites) - SITES
        if unknown:
            raise ValueError(
                f"unknown fault site(s) {sorted(unknown)}; known sites: "
                f"{sorted(SITES)}")
        self.seed = int(seed)
        self._specs: Dict[str, _SiteSpec] = {}
        for site, spec in sites.items():
            spec = dict(spec or {})
            self._specs[site] = _SiteSpec(
                site,
                probability=spec.pop("p", spec.pop("probability", 1.0)),
                count=spec.pop("count", None),
                latency_ms=spec.pop("latencyMs", spec.pop("latency_ms", 0.0)),
                fail=spec.pop("fail", None),
                seed=self.seed)
            if spec:
                raise ValueError(
                    f"unknown field(s) {sorted(spec)} in fault spec for "
                    f"{site!r} (known: p, count, latencyMs, fail)")
        self._fired: Dict[str, int] = {}
        self._lock = threading.Lock()

    @classmethod
    def from_json(cls, text: str) -> "FaultSchedule":
        data = json.loads(text)
        return cls(data.get("sites", {}), seed=data.get("seed", 0))

    def fired(self, site: Optional[str] = None) -> Union[int, Dict[str, int]]:
        """Fire count for one site, or the whole per-site map."""
        with self._lock:
            if site is not None:
                return self._fired.get(site, 0)
            return dict(self._fired)

    def check(self, site: str) -> None:
        """One site crossing: decide (seeded, budgeted), then sleep and/or
        raise. Called via `fault_point`, never directly from hook sites."""
        spec = self._specs.get(site)
        if spec is None:
            return
        with self._lock:
            if spec.count is not None and \
                    self._fired.get(site, 0) >= spec.count:
                return
            if spec.probability < 1.0 and \
                    spec.rng.random() >= spec.probability:
                return
            self._fired[site] = self._fired.get(site, 0) + 1
        from .events import emit as emit_event
        from .metrics import get_registry
        get_registry().counter("pinot_fault_injections").inc()
        emit_event("fault.fired", site=site,
                   latencyMs=spec.latency_ms, fail=bool(spec.fail))
        if spec.latency_ms > 0:
            time.sleep(spec.latency_ms / 1000.0)
        if spec.fail:
            raise FaultInjected(site)


#: the process-wide active schedule; None = plane disabled (the common case —
#: `fault_point` must stay one load + None check on every hot path).
_active: Optional[FaultSchedule] = None


def fault_point(site: str) -> None:
    """The hook every injection site crosses. Near-free when no schedule is
    active; otherwise delegates the (seeded, budgeted) decision — which may
    sleep and/or raise `FaultInjected` — to the schedule."""
    sched = _active
    if sched is None:
        return
    sched.check(site)


def activate(schedule: Optional[FaultSchedule]) -> None:
    global _active
    _active = schedule


def deactivate() -> None:
    activate(None)


def active_schedule() -> Optional[FaultSchedule]:
    return _active


@contextmanager
def active(schedule: FaultSchedule) -> Iterator[FaultSchedule]:
    """Test-fixture activation: installs the schedule for the scope and
    always restores the previous plane state (including nesting)."""
    global _active
    prev = _active
    _active = schedule
    try:
        yield schedule
    finally:
        _active = prev


def activate_from_config(catalog) -> Optional[FaultSchedule]:
    """Cluster-wide activation: read the `fault.schedule` clusterConfig knob
    (a JSON spec, see module docstring) and install it process-wide. Called
    by role services at startup; a missing/empty knob leaves the plane
    untouched, a malformed one raises (a chaos drill with a typo'd schedule
    silently not running is worse than failing the start)."""
    raw = catalog.get_property("clusterConfig/fault.schedule")
    if not raw:
        return None
    sched = FaultSchedule.from_json(raw)
    activate(sched)
    return sched
