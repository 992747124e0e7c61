"""Typed per-query execution statistics (the tentpole of the telemetry layer).

Reference pattern: the reference's BrokerResponseNative metadata block
(numDocsScanned, numSegmentsQueried/Processed/Matched, numServersResponded,
timeUsedMs) plus ServerQueryPhase/BrokerQueryPhase timers — but carried as ONE
typed record created per request and threaded through
scatter -> server -> executor/pipeline -> partial -> wire -> combine -> reduce,
then merged back into `QueryResult.stats` under well-known keys.

Accounting sites publish through a thread-local "current stats" slot (same
pattern as `utils.trace`): the server activates a fresh record on its
execution thread, kernel/launch/fetch hooks `record()` into whatever record is
active (a no-op when none is, e.g. warm-up; the pipeline's
dispatcher thread serves many queries at once, so it activates a scratch
record around each query's prepare and around each launch and folds what the
kernel cache recorded there into the items that launch answers), and the
record rides `SegmentResult.stats` back across the wire as a flat
summable dict. Per-operator rows/ms breakdowns (EXPLAIN ANALYZE) flatten into
the same dict under `op:<label>:rows` / `op:<label>:ms` keys so one merge rule
covers everything; the public export strips them.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Iterator, Optional, Tuple

# -- well-known stats keys ---------------------------------------------------
# Every key the executor/broker can emit into `QueryResult.stats`, with the
# operator-facing meaning. README's "Observability" glossary and the tier-1
# drift-guard test are checked against THIS table: add a key here (and to
# README) before emitting it.
NUM_SEGMENTS_QUERIED = "numSegmentsQueried"
NUM_SEGMENTS_PRUNED = "numSegmentsPruned"
# per-pruner-kind breakdown of NUM_SEGMENTS_PRUNED: which pruner rejected the
# segment first (broker metadata pruners) or that the filter folded to
# constant-false server-side (the pre-existing numSegmentsPruned path)
NUM_SEGMENTS_PRUNED_BY_PARTITION = "numSegmentsPrunedByPartition"
NUM_SEGMENTS_PRUNED_BY_TIME = "numSegmentsPrunedByTime"
NUM_SEGMENTS_PRUNED_BY_RANGE = "numSegmentsPrunedByRange"
NUM_SEGMENTS_PRUNED_BY_BLOOM = "numSegmentsPrunedByBloom"
# docs that were never scanned because their segment was pruned (broker
# metadata pruning + server constant-false folds) — the "work avoided" number
SCAN_ROWS_AVOIDED = "scanRowsAvoided"
NUM_SEGMENTS_MATCHED = "numSegmentsMatched"
NUM_DOCS_SCANNED = "numDocsScanned"
DEVICE_LAUNCHES = "deviceLaunches"
COMPILE_CACHE_HITS = "compileCacheHits"
COMPILE_CACHE_MISSES = "compileCacheMisses"
COMPILE_MS = "compileMs"
DEVICE_EXEC_MS = "deviceExecMs"
DEVICE_FETCH_MS = "deviceFetchMs"
BYTES_FETCHED = "bytesFetched"
QUEUE_WAIT_MS = "queueWaitMs"
# the device pipeline's phases after the queue wait, per query (PR 26): the
# query's own plan + input build; its drain's launch (shared by the batch);
# launch end -> the fetcher taking the batch; from the start of its launch's
# decode to its answer. deviceBatchSize is the live queries of its drain and
# serverTimeMs the server's whole execute wall (both max-merged)
DEVICE_PREPARE_MS = "devicePrepareMs"
DEVICE_LAUNCH_MS = "deviceLaunchMs"
DEVICE_HANDOFF_MS = "deviceHandoffMs"
DEVICE_DECODE_MS = "deviceDecodeMs"
DEVICE_BATCH_SIZE = "deviceBatchSize"
SERVER_TIME_MS = "serverTimeMs"
# the hop's pieces, each timed where its work runs (PR 38): the CPU its
# prepare and its drain's launch took on the dispatcher thread (wall far above
# CPU is a wait for the GIL); the prepare's plan and its input build; the
# fetcher resolving its answer -> the handler thread back from the future;
# the server's acquire + admission + settle, its merge, its request decode;
# the broker's request encode and its result decode
DEVICE_PREPARE_CPU_MS = "devicePrepareCpuMs"
DEVICE_LAUNCH_CPU_MS = "deviceLaunchCpuMs"
DEVICE_PLAN_MS = "devicePlanMs"
DEVICE_INPUTS_MS = "deviceInputsMs"
DEVICE_WAKE_MS = "deviceWakeMs"
SERVER_ACQUIRE_MS = "serverAcquireMs"
SERVER_MERGE_MS = "serverMergeMs"
SERVER_DECODE_MS = "serverDecodeMs"
SCATTER_SERIALIZE_MS = "scatterSerializeMs"
SCATTER_DESERIALIZE_MS = "scatterDeserializeMs"
DEDUPED_LAUNCHES = "dedupedLaunches"
STACKED_LAUNCHES = "stackedLaunches"
# fused-vs-staged execution split (PR 16): fusedLaunches counts single-launch
# kernels that decode compressed forms (dict ids / FOR deltas) in-register;
# stagedLaunches counts the sub-launches of the two-dispatch fallback
# (mask kernel + aggregate kernel over decoded HBM columns)
FUSED_LAUNCHES = "fusedLaunches"
STAGED_LAUNCHES = "stagedLaunches"
# of the launches that decode compressed forms in-kernel, those with no gather
# in them: every dict column's decode table was small enough for the select
# tree (kernels.SELECT_DECODE_CAP), so the decode fused into the scan (PR 27)
GATHER_FREE_LAUNCHES = "gatherFreeLaunches"
# launches whose program HOLDS a matmul regime (one-hot, chunk64) over more
# than one slab of rows: past kernels.SLAB_ROWS (2^24) rows a device an f32
# cell cannot hold a count, so the rows go slab by slab and the counts are
# added as int32. From the static shape the kernel was built with;
# where the rung's rows are compacted in front of it the loop is only the
# fall-back's (FULL_MATMUL_LAUNCHES counts the launches that ran it)
SLABBED_LAUNCHES = "slabbedLaunches"
# launches whose GROUP BY ran the masked VPU reduce (PR 37): at most
# `KernelCaps.masked_cap` padded keys + 1, so its counts and sums are a
# compare, a select and a reduce a key cell and value row, with no matmul and
# no slabs. From the static plan (`kernels.masked`)
MASKED_GROUPBY_LAUNCHES = "maskedGroupByLaunches"
# launches whose GROUP BY answered from its SORTED GROUPS: a key space
# past `KernelCaps.dense_keys`, so the sort regime returns the keys that occur
# with their counts, sums, MINs and MAXs, never a table of every key; and of
# them, those that also cut the ORDER BY ... LIMIT on the device, where the
# broker routed the query to this one server (the partial is the whole
# answer). From the static plan (`kernels.sparse`, `kernels.trimmed`)
SPARSE_GROUPBY_LAUNCHES = "sparseGroupByLaunches"
DEVICE_TRIMMED_LAUNCHES = "deviceTrimmedLaunches"
# launches whose GROUP BY took the sort regime of a DENSE key space (past
# chunk_cap keys, not past dense_keys) and cut its ORDER BY ... LIMIT on the
# device from its sorted groups, with no table of the key space, the partial
# being the whole answer (`kernels.dense_trimmed`); and
# launches whose GROUP BY's MINs and MAXs rode a sort regime's sort, taken
# from the sorted rows with no scatter (`kernels.sorted_minmax`). From the
# static plan
DENSE_TRIMMED_LAUNCHES = "denseTrimmedLaunches"
SORTED_MINMAX_LAUNCHES = "sortedMinMaxLaunches"
# launches whose aggregate argument was evaluated WIDENED (PR 36): a `+`, `-`
# or `*` of INT columns in it can leave int32 by the literals and the columns'
# min/max the plan holds, so the device computes it in float32 (the host in
# int64) where it used to wrap. From the static plan (`kernels.widened`)
WIDENED_AGG_LAUNCHES = "widenedAggLaunches"
# which decode a sort-regime GROUP BY launch ran (PR 29): the dense answer from
# the sorted prefix of rows that passed the filter (compact), or the per-key
# binary searches (dense). The kernel decides on the device and returns the
# scalar COMPACT_FLAG with its outputs (1 only if every sort regime of the
# scan, on every chip, took compact); a program built without the branch
# returns none and counts neither
COMPACT_DECODE_LAUNCHES = "compactDecodeLaunches"
DENSE_DECODE_LAUNCHES = "denseDecodeLaunches"
# graftcheck: ignore[drift-stats-keys] -- the kernel OUTPUT's name (read by
# decode_branch below), never a key of a stats record
COMPACT_FLAG = "decode.compact"
# which SORT such a launch ran (PR 33): the rows that passed the filter,
# compacted tile by tile in front of the sort (n / 64 rows sorted), or every
# row. The scalar PRESORT_FLAG rides with COMPACT_FLAG (1 only if every sort
# regime of the scan, on every chip, sorted the compacted rows); a launch that
# did also counts as a compact decode
PRESORT_COMPACT_LAUNCHES = "presortCompactLaunches"
FULL_SORT_LAUNCHES = "fullSortLaunches"
# graftcheck: ignore[drift-stats-keys] -- a kernel OUTPUT's name, as above
PRESORT_FLAG = "decode.presort"
# which rows a matmul GROUP BY rung (one-hot, chunk64) contracted: those that
# passed the filter, compacted tile by tile with the few overfull tiles taken
# whole (n / 64 or n / 16 rows and n / 256), or every row. The kernel returns
# the scalar MATMUL_FLAG (1 only if every matmul rung of the scan, on every
# chip, contracted the compacted rows); a program built without the stage
# (`kernels.compacted_rows` 0) returns none and counts neither
COMPACT_MATMUL_LAUNCHES = "compactMatmulLaunches"
FULL_MATMUL_LAUNCHES = "fullMatmulLaunches"
# graftcheck: ignore[drift-stats-keys] -- a kernel OUTPUT's name, as above
MATMUL_FLAG = "decode.matmul"
NUM_CONSUMING_SEGMENTS_QUERIED = "numConsumingSegmentsQueried"
MIN_CONSUMING_FRESHNESS_TIME_MS = "minConsumingFreshnessTimeMs"
MUX_FRAME_QUEUE_MS = "muxFrameQueueMs"
MUX_FLOW_CONTROL_MS = "muxFlowControlMs"
# what crosses the chips (PR 28), per launch, from the static shapes the
# shard kernel was built with: launches whose program ran on more than one
# device; those of them with at least one reduce-scattered output
# (`psum_scatter`, combine.SCATTER_MIN_KEYS); and the bytes one device hands
# to the launch's psum / pmin / pmax / psum_scatter. All 0 on a mesh of one
MESH_LAUNCHES = "meshLaunches"
SCATTER_LAUNCHES = "scatterLaunches"
COLLECTIVE_BYTES = "collectiveBytes"
# what a launch over a server's RESIDENT segment set read (PR 32), per launch,
# from what the launch was built with: the segments the query was routed to,
# the segments the stacked block holds, the slots the program read (the slot
# window's static length; the resident count where every slot is read and the
# routed ones are a mask), and whether the plan is in a merged id space
# (per-segment dictionaries differ: parallel/merged.py)
ROUTED_SLOTS = "routedSlots"
RESIDENT_SLOTS = "residentSlots"
SCANNED_SLOTS = "scannedSlots"
MERGED_LAUNCHES = "mergedLaunches"
# segment-set blocks built, and the bytes `device_put` into them (stacked
# columns, decode tables, slot masks): 0 for a query over a warm block
SET_BLOCKS_STAGED = "setBlocksStaged"
SET_BLOCK_BYTES = "setBlockBytes"
DEVICE_SKEW_PCT = "deviceSkewPct"
HEDGED_REQUESTS = "hedgedRequests"
ADMISSION_DEFER_MS = "admissionDeferMs"
# tiered-storage lifecycle: segments the admission gate kept OFF the device
# (served by the host plan instead of OOMing), segments freshly promoted
# host→HBM this query, and cold-tier segments lazily downloaded from the
# deep store on first query (+ the wall time those downloads took)
SEGMENTS_SERVED_HOST_TIER = "segmentsServedHostTier"
TIER_PROMOTIONS = "tierPromotions"
SEGMENTS_COLD_LOADED = "segmentsColdLoaded"
COLD_LOAD_MS = "coldLoadMs"
# device hash-join fast path (PR 17): wall time in the build-side sort /
# scatter launches and the probe launches (summed across join partitions),
# bytes exchanged between join stages, probe segments skipped by the
# build-key derived filter, and joins the admission gate priced off the
# device (served by the host hash_join instead of OOMing HBM)
JOIN_BUILD_MS = "joinBuildMs"
JOIN_PROBE_MS = "joinProbeMs"
JOIN_SHUFFLE_BYTES = "joinShuffleBytes"
NUM_SEGMENTS_PRUNED_BY_JOIN_KEY = "numSegmentsPrunedByJoinKey"
JOIN_SERVED_HOST_TIER = "joinServedHostTier"
# worst probe-key skew any join partition saw (hot-bucket excess percentage
# from the probe-hash histogram); max-merged like deviceSkewPct
JOIN_SKEW_PCT = "joinSkewPct"

# merged-counter keys always present in a query response (0 when the path
# never ran); `*Ms` keys round to 3 decimals on export
COUNTER_KEYS = (
    NUM_SEGMENTS_QUERIED, NUM_SEGMENTS_PRUNED,
    NUM_SEGMENTS_PRUNED_BY_PARTITION, NUM_SEGMENTS_PRUNED_BY_TIME,
    NUM_SEGMENTS_PRUNED_BY_RANGE, NUM_SEGMENTS_PRUNED_BY_BLOOM,
    SCAN_ROWS_AVOIDED, NUM_SEGMENTS_MATCHED,
    DEVICE_LAUNCHES, COMPILE_CACHE_HITS, COMPILE_CACHE_MISSES,
    COMPILE_MS, DEVICE_EXEC_MS, DEVICE_FETCH_MS, BYTES_FETCHED,
    QUEUE_WAIT_MS, DEVICE_PREPARE_MS, DEVICE_LAUNCH_MS, DEVICE_HANDOFF_MS,
    DEVICE_DECODE_MS, DEVICE_PREPARE_CPU_MS, DEVICE_LAUNCH_CPU_MS,
    DEVICE_PLAN_MS, DEVICE_INPUTS_MS, DEVICE_WAKE_MS,
    SERVER_ACQUIRE_MS, SERVER_MERGE_MS, SERVER_DECODE_MS,
    SCATTER_SERIALIZE_MS, SCATTER_DESERIALIZE_MS,
    DEDUPED_LAUNCHES, STACKED_LAUNCHES,
    FUSED_LAUNCHES, STAGED_LAUNCHES, GATHER_FREE_LAUNCHES, SLABBED_LAUNCHES,
    WIDENED_AGG_LAUNCHES, MASKED_GROUPBY_LAUNCHES,
    SPARSE_GROUPBY_LAUNCHES, DEVICE_TRIMMED_LAUNCHES,
    DENSE_TRIMMED_LAUNCHES, SORTED_MINMAX_LAUNCHES,
    COMPACT_DECODE_LAUNCHES, DENSE_DECODE_LAUNCHES,
    PRESORT_COMPACT_LAUNCHES, FULL_SORT_LAUNCHES,
    COMPACT_MATMUL_LAUNCHES, FULL_MATMUL_LAUNCHES,
    NUM_CONSUMING_SEGMENTS_QUERIED, MUX_FRAME_QUEUE_MS, MUX_FLOW_CONTROL_MS,
    MESH_LAUNCHES, SCATTER_LAUNCHES, COLLECTIVE_BYTES,
    ROUTED_SLOTS, RESIDENT_SLOTS, SCANNED_SLOTS, MERGED_LAUNCHES,
    SET_BLOCKS_STAGED, SET_BLOCK_BYTES,
    HEDGED_REQUESTS, ADMISSION_DEFER_MS,
    SEGMENTS_SERVED_HOST_TIER, TIER_PROMOTIONS,
    SEGMENTS_COLD_LOADED, COLD_LOAD_MS,
    JOIN_BUILD_MS, JOIN_PROBE_MS, JOIN_SHUFFLE_BYTES,
    NUM_SEGMENTS_PRUNED_BY_JOIN_KEY, JOIN_SERVED_HOST_TIER,
)

# keys that merge by MINIMUM instead of sum (reference: the broker reduces
# minConsumingFreshnessTimeMs across servers with Math.min — the answer is
# only as fresh as the STALEST consuming segment it touched). Absent on
# responses that touched no consuming segment; never zero-filled, because a
# zero-fill would poison every min-merge round.
MIN_KEYS = (MIN_CONSUMING_FRESHNESS_TIME_MS,)

# keys that merge by MAXIMUM: deviceSkewPct reports the WORST per-device
# exec-time imbalance any mesh launch saw (summing percentages across
# launches/servers is meaningless; the slowest chip bounds the query).
# Absent on responses that never took a multi-device mesh path.
# deviceBatchSize and serverTimeMs keep the largest any server reported (a
# query waits for its slowest server).
MAX_KEYS = (DEVICE_SKEW_PCT, JOIN_SKEW_PCT, DEVICE_BATCH_SIZE, SERVER_TIME_MS)

# the query's 16-hex plan-shape fingerprint (sql/fingerprint.py): stamped by
# the broker so any response / slow-log line / trace resolves to its shape
# profile at GET /debug/workload?fp=
WORKLOAD_FINGERPRINT = "workloadFingerprint"

# broker-level keys that live beside the merged counters in QueryResult.stats
# (listed so the glossary drift guard covers the full emitted surface)
BROKER_KEYS = (
    "timeUsedMs", NUM_DOCS_SCANNED, "numGroupsTotal", "numServersQueried",
    "numServersResponded", "partialResult", "phaseTimesMs", "traceInfo",
    "traceId", "gapfilled", "explain", "analyze", "joinStrategy",
    WORKLOAD_FINGERPRINT,
)

#: routing pruner kind (cluster.routing.PRUNER_KINDS) -> its breakdown counter
PRUNED_BY_KIND = {
    "partition": NUM_SEGMENTS_PRUNED_BY_PARTITION,
    "time": NUM_SEGMENTS_PRUNED_BY_TIME,
    "range": NUM_SEGMENTS_PRUNED_BY_RANGE,
    "bloom": NUM_SEGMENTS_PRUNED_BY_BLOOM,
}

_OP_PREFIX = "op:"


def op_key(label: str, field: str) -> str:
    return f"{_OP_PREFIX}{label}:{field}"


class ExecutionStats:
    """One query's execution accounting: a flat dict of summable counters
    (plus flattened per-operator entries consumed by EXPLAIN ANALYZE)."""

    __slots__ = ("counters", "_lock")

    def __init__(self, counters: Optional[Dict[str, float]] = None):
        self.counters: Dict[str, float] = dict(counters or {})
        self._lock = threading.Lock()

    def add(self, key: str, n: float = 1) -> None:
        with self._lock:
            # graftcheck: ignore[unbounded-keyed-accumulation] -- per-query
            # stats object; key space is the drift-guarded stat-key constants
            self.counters[key] = self.counters.get(key, 0) + n

    def set_min(self, key: str, v: float) -> None:
        """Keep the minimum seen for a min-merged key (no-op when `v` loses)."""
        with self._lock:
            cur = self.counters.get(key)
            self.counters[key] = v if cur is None else min(cur, v)

    def set_max(self, key: str, v: float) -> None:
        """Keep the maximum seen for a max-merged key (no-op when `v` loses)."""
        with self._lock:
            cur = self.counters.get(key)
            self.counters[key] = v if cur is None else max(cur, v)

    def add_operator(self, label: str, rows: float = 0, ms: float = 0.0) -> None:
        with self._lock:
            rk, mk = op_key(label, "rows"), op_key(label, "ms")
            self.counters[rk] = self.counters.get(rk, 0) + rows
            self.counters[mk] = self.counters.get(mk, 0) + ms

    def merge(self, other) -> None:
        """Fold another record (ExecutionStats or its flat dict form) into
        this one: every numeric key sums, except MIN_KEYS (MAX_KEYS) which
        keep the minimum (maximum) of the sides that carry the key."""
        if other is None:
            return
        src = other.counters if isinstance(other, ExecutionStats) else other
        if isinstance(other, ExecutionStats):
            with other._lock:
                src = dict(src)
        with self._lock:
            for k, v in src.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    if k in MIN_KEYS:
                        cur = self.counters.get(k)
                        self.counters[k] = v if cur is None else min(cur, v)
                    elif k in MAX_KEYS:
                        cur = self.counters.get(k)
                        self.counters[k] = v if cur is None else max(cur, v)
                    else:
                        self.counters[k] = self.counters.get(k, 0) + v

    def operators(self) -> Dict[str, Dict[str, float]]:
        """Reassemble the per-operator breakdown: label -> {rows, ms}."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for k, v in self.counters.items():
                if not k.startswith(_OP_PREFIX):
                    continue
                label, _, fld = k[len(_OP_PREFIX):].rpartition(":")
                out.setdefault(label, {"rows": 0, "ms": 0.0})[fld] = v
        return out

    def to_wire(self) -> Dict[str, float]:
        """Flat dict for `SegmentResult.stats` (keeps op:* entries)."""
        with self._lock:
            return dict(self.counters)

    def to_public_dict(self) -> Dict[str, object]:
        """Export for `QueryResult.stats`: every well-known counter (0 when
        untouched), ints for counts, rounded floats for `*Ms`; internal op:*
        breakdowns stay off the response (EXPLAIN ANALYZE consumes them)."""
        with self._lock:
            out: Dict[str, object] = {}
            for k in COUNTER_KEYS:
                v = float(self.counters.get(k, 0))
                out[k] = round(v, 3) if k.endswith("Ms") else int(v)
            for k, v in self.counters.items():
                if k not in out and not k.startswith(_OP_PREFIX):
                    # MIN_KEYS are epoch-ms timestamps, not durations: whole ms
                    out[k] = (round(float(v), 3)
                              if (k.endswith("Ms") and k not in MIN_KEYS)
                              or k.endswith("Pct")
                              else int(v))
            return out


# -- thread-local current record (mirrors utils.trace's _local pattern) ------

_local = threading.local()


def current_stats() -> Optional[ExecutionStats]:
    return getattr(_local, "stats", None)


def record(key: str, n: float = 1) -> None:
    """Accounting hook for hot paths: add to the active record if any.
    Deliberately tolerant — kernel/fetch sites run on threads that may serve
    many queries (pipeline dispatcher) or none (warm-up), where
    per-query attribution happens elsewhere or not at all."""
    st = getattr(_local, "stats", None)
    if st is not None:
        st.add(key, n)


#: a GROUP BY launch's flag -> the counter it adds to when set, when not
DECODE_FLAGS = {
    COMPACT_FLAG: (COMPACT_DECODE_LAUNCHES, DENSE_DECODE_LAUNCHES),
    PRESORT_FLAG: (PRESORT_COMPACT_LAUNCHES, FULL_SORT_LAUNCHES),
    MATMUL_FLAG: (COMPACT_MATMUL_LAUNCHES, FULL_MATMUL_LAUNCHES),
}


def decode_branch(outs) -> Tuple[str, ...]:
    """The counters one launch's fetched outputs add to: which decode branch
    and which sort its sort regimes ran, which rows its matmul rung
    contracted (DECODE_FLAGS), or none for a program without the branches
    (and for whatever a test's fake executor hands back)."""
    if not isinstance(outs, dict):
        return ()
    return tuple(keys[0] if int(outs[flag]) else keys[1]
                 for flag, keys in DECODE_FLAGS.items() if flag in outs)


def add_ms(result, *timed: Tuple[str, float]) -> None:
    """Add each (key, ms) to a partial's flat stats dict (`SegmentResult.stats`,
    the wire's form): what a transport or handler timed outside the record
    the server merged. A 0 adds nothing."""
    stats = result.stats if isinstance(result.stats, dict) else {}
    for key, ms in timed:
        if ms:
            stats[key] = round(stats.get(key, 0.0) + ms, 3)
    result.stats = stats


def record_min(key: str, v: float) -> None:
    """Min-merge accounting hook (freshness timestamps): keep the smallest
    value seen by the active record, if any."""
    st = getattr(_local, "stats", None)
    if st is not None:
        st.set_min(key, v)


def record_max(key: str, v: float) -> None:
    """Max-merge accounting hook (per-launch device skew): keep the largest
    value seen by the active record, if any."""
    st = getattr(_local, "stats", None)
    if st is not None:
        st.set_max(key, v)


def record_operator(label: str, rows: float = 0, ms: float = 0.0) -> None:
    st = getattr(_local, "stats", None)
    if st is not None:
        st.add_operator(label, rows=rows, ms=ms)


@contextmanager
def collect_stats(st: Optional[ExecutionStats] = None
                  ) -> Iterator[ExecutionStats]:
    """Install a (fresh) record as this thread's active stats for the scope."""
    st = st if st is not None else ExecutionStats()
    prev = getattr(_local, "stats", None)
    _local.stats = st
    try:
        yield st
    finally:
        _local.stats = prev


@contextmanager
def scoped() -> Iterator[ExecutionStats]:
    """A fresh record for the scope, folded into the record that was active
    around it (if any) on the way out: what ONE launch recorded, read apart,
    with nothing lost to an enclosing query."""
    prev = getattr(_local, "stats", None)
    try:
        with collect_stats() as st:
            yield st
    finally:
        if prev is not None:
            prev.merge(st)


@contextmanager
def activate(st: ExecutionStats) -> Iterator[ExecutionStats]:
    """Re-install an existing record on a worker thread (scheduler slots,
    scatter pool) — the stats analog of `Trace.activate`."""
    prev = getattr(_local, "stats", None)
    _local.stats = st
    try:
        yield st
    finally:
        _local.stats = prev
