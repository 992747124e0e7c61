"""Tiered-storage lifecycle tests: HBM admission gate, pressure eviction,
host-tier degradation, cold demotion + lazy reload, deep-store download
retry/quarantine, and the unload-vs-in-flight-query deferred-release fix.

The tier ladder under test (cluster/tiering.py):

* hot  — ledger-accounted device blocks, bounded by
         `capacity * (1 - server.hbm.target.headroom.pct/100)`
* warm — host-RAM readers; an evicted/rejected segment answers on the host
         plan (`segmentsServedHostTier`), never with short rows
* cold — deep store only; a COLD-assigned segment stays routable and the
         first query lazily re-downloads it within its deadline budget

Every scenario pins the process ledger's capacity explicitly
(`set_capacity`) and restores a fresh ledger afterwards — capacity is
process-global state and must not leak between tests.
"""

import threading
import time

import numpy as np
import pytest

from pinot_tpu.cluster import QuickCluster
from pinot_tpu.engine import datablock
from pinot_tpu.engine.datablock import (block_for, has_block,
                                        predicted_block_bytes, release_block)
from pinot_tpu.table import TableConfig
from pinot_tpu.utils import faults
from pinot_tpu.utils.faults import FaultSchedule
from pinot_tpu.utils.memledger import get_ledger, reset_ledger
from pinot_tpu.utils.metrics import get_registry

from conftest import make_ssb_columns

ROWS_PER_SEGMENT = 2000


def _counter_value(name, **labels):
    """One counter/gauge out of the registry snapshot by name + label pairs
    (label render order is an implementation detail)."""
    for key, v in get_registry().snapshot().items():
        if key == name:
            return v
        if key.startswith(name + "{") and all(
                f"{lk}={lv}" in key for lk, lv in labels.items()):
            return v
    return None


def _build_cluster(tmp_path, ssb_schema, num_segments, seed=11):
    cluster = QuickCluster(num_servers=1, work_dir=str(tmp_path))
    cfg = TableConfig(ssb_schema.name, replication=1,
                      time_column="lo_orderdate")
    cluster.create_table(ssb_schema, cfg)
    rng = np.random.default_rng(seed)
    names = [cluster.ingest_columns(cfg, make_ssb_columns(rng,
                                                          ROWS_PER_SEGMENT))
             for _ in range(num_segments)]
    return cluster, cfg, names


@pytest.fixture()
def fresh_ledger():
    """Isolate the process-global ledger + metrics registry: tests in this
    module pin tiny capacities that must not leak into other modules."""
    reset_ledger()
    get_registry().reset()
    faults.deactivate()
    from pinot_tpu.cluster.peers import clear_download_quarantine
    clear_download_quarantine()
    yield get_ledger()
    faults.deactivate()
    clear_download_quarantine()
    reset_ledger()
    get_registry().reset()


# -- deferred release: unload never races an in-flight query ------------------

def test_remove_segment_defers_block_drop_until_refcount_drains(
        tmp_path, ssb_schema, fresh_ledger):
    """The satellite race fix, threaded: a query thread holds acquired
    segment handles and keeps executing while the main thread unloads the
    segment. Every execution must see the full row count — the device block
    and ledger entries survive until the LAST release drains the refcount."""
    from pinot_tpu.query.context import compile_query
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 1)
    table = cfg.table_name_with_type
    server = cluster.servers[0]
    mgr = server.tables[table]
    ctx = compile_query("SELECT COUNT(*) FROM lineorder", ssb_schema)

    held = mgr.acquire([names[0]])
    assert len(held) == 1 and mgr.refcount(names[0]) == 1
    seg = held[0]
    blk = block_for(seg)    # stage device arrays the race would drop
    blk.valid
    blk.ids("lo_region")
    assert get_ledger().resident_bytes(segment=seg.name) > 0

    removed = threading.Event()
    counts = []

    def query_loop():
        for i in range(40):
            if i == 10:
                removed.wait(timeout=10.0)   # unload happens mid-stream
            res = server.executor.execute_segment(ctx, seg, None)
            counts.append(res.scalar[0] if res.scalar else None)

    t = threading.Thread(target=query_loop)
    t.start()
    mgr.remove_segment(names[0])    # in-flight refs: must defer, not drop
    removed.set()
    t.join(timeout=30.0)
    assert not t.is_alive()

    # the unload took effect for NEW queries...
    assert names[0] not in mgr.segment_names
    assert mgr.acquire([names[0]]) == []
    # ...but the in-flight holder kept its device block the whole time
    assert has_block(seg)
    assert get_ledger().resident_bytes(segment=seg.name) > 0
    assert counts == [ROWS_PER_SEGMENT] * 40, "a query saw short rows"

    mgr.release(held)               # refcount drains -> deferred drop fires
    assert not has_block(seg)
    assert get_ledger().resident_bytes(segment=seg.name) == 0


# -- admission gate + host-tier degradation -----------------------------------

def test_admission_gate_rejects_past_target_and_host_tier_answers(
        tmp_path, ssb_schema, fresh_ledger):
    """Capacity sized for ~one block out of three: the query still returns
    the full (non-partial) answer, rejected segments ride the host plan
    (`segmentsServedHostTier`), and residency never exceeds capacity."""
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 3)
    table = cfg.table_name_with_type
    server = cluster.servers[0]
    mgr = server.tables[table]
    predicted = predicted_block_bytes(mgr.get(names[0]))
    assert predicted > 0
    capacity = int(predicted * 1.5)      # target = 0.9*cap ~= 1.35 blocks
    get_ledger().set_capacity(capacity)

    res = cluster.query("SELECT COUNT(*) FROM lineorder")
    assert res.rows[0][0] == 3 * ROWS_PER_SEGMENT
    assert not res.stats["partialResult"]
    assert res.stats["segmentsServedHostTier"] >= 1
    assert get_ledger().snapshot()["totalBytes"] <= capacity

    tiering = server.tiering.snapshot()
    assert tiering["rejections"] >= 1
    assert tiering["targetBytes"] == int(capacity * 0.9)
    assert _counter_value("pinot_server_hbm_admission_rejects",
                          table=table) >= 1


def test_admission_prices_the_fused_working_set(tmp_path, ssb_schema,
                                                fresh_ledger):
    """The gate charges the compressed-resident layout fused plans read (ids +
    decode table, no decoded-values cache): a segment whose fused price fits
    the target and whose decoded price does not is admitted."""
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 1)
    server = cluster.servers[0]
    seg = server.tables[cfg.table_name_with_type].get(names[0])
    fused = predicted_block_bytes(seg, fused=True)
    decoded = predicted_block_bytes(seg)
    assert 0 < fused < decoded
    get_ledger().set_capacity(int((fused + decoded) / 2 / 0.9))  # the target

    res = cluster.query("SELECT SUM(lo_revenue) FROM lineorder")
    assert res.rows[0][0] is not None and not res.stats["partialResult"]
    assert not res.stats.get("segmentsServedHostTier")
    tiering = server.tiering.snapshot()
    assert fused <= tiering["targetBytes"] < decoded
    assert tiering["rejections"] == 0 and tiering["admissions"] >= 1
    assert has_block(seg)


def test_admission_reservations_prevent_same_query_overcommit(
        tmp_path, ssb_schema, fresh_ledger):
    """All of a query's segments admit BEFORE any block stages; without
    in-flight reservations the gate would admit every segment against an
    empty ledger and overshoot. With them, one query over 3 segments stays
    under capacity."""
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 3)
    server = cluster.servers[0]
    mgr = server.tables[cfg.table_name_with_type]
    predicted = predicted_block_bytes(mgr.get(names[0]))
    capacity = int(predicted * 1.5)
    get_ledger().set_capacity(capacity)

    # first-ever query: ledger empty, all three admissions race the stage
    res = cluster.query("SELECT SUM(lo_revenue) FROM lineorder")
    assert res.rows[0][0] is not None
    assert get_ledger().snapshot()["totalBytes"] <= capacity
    staged = sum(1 for n in names if has_block(mgr.get(n)))
    assert staged <= 1, "reservations failed: multiple blocks staged"


def test_pressure_sweep_evicts_cold_blocks_but_never_inflight(
        tmp_path, ssb_schema, fresh_ledger):
    """The periodic pressure loop walks residency back under target by
    bytes*coldness score — but a segment acquired by an in-flight query is
    never a victim; its eviction waits for the refcount to drain."""
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 2)
    table = cfg.table_name_with_type
    server = cluster.servers[0]
    mgr = server.tables[table]

    # big capacity: both segments admit + stage
    cluster.query("SELECT SUM(lo_revenue) FROM lineorder")
    assert all(has_block(mgr.get(n)) for n in names)
    resident = get_ledger().resident_bytes()
    assert resident > 0

    held = mgr.acquire([names[0]])   # an in-flight query holds segment 0
    get_ledger().set_capacity(max(1, resident // 4))   # force pressure
    evicted = server.tiering.run_pressure_sweep()
    assert evicted >= 1
    assert has_block(mgr.get(names[0])), "evicted a block under a live query"
    assert not has_block(mgr.get(names[1]))
    assert _counter_value("pinot_server_hbm_evictions") >= 1

    mgr.release(held)                # refcount drained: now evictable
    assert server.tiering.run_pressure_sweep() >= 1
    assert not has_block(mgr.get(names[0]))
    assert get_ledger().resident_bytes() <= server.tiering.target_bytes()


def test_hot_and_host_tier_answers_are_identical(tmp_path, ssb_schema,
                                                 fresh_ledger):
    """Differential suite: the same queries over the same data must return
    identical rows whether every segment rides the device plan (unconstrained
    capacity) or admission forces most onto the host plan (pinned capacity
    with eviction cycling between queries)."""
    suite = [
        "SELECT COUNT(*) FROM lineorder",
        "SELECT SUM(lo_revenue), MIN(lo_quantity), MAX(lo_discount) "
        "FROM lineorder",
        "SELECT lo_region, SUM(lo_revenue) FROM lineorder "
        "GROUP BY lo_region ORDER BY lo_region LIMIT 20",
        "SELECT COUNT(*) FROM lineorder WHERE lo_quantity > 25",
        "SELECT lo_category, COUNT(*) FROM lineorder "
        "WHERE lo_region = 'ASIA' GROUP BY lo_category "
        "ORDER BY lo_category LIMIT 20",
    ]

    def run(workdir, capacity_blocks):
        reset_ledger()
        cluster, cfg, names = _build_cluster(workdir, ssb_schema, 3, seed=23)
        mgr = cluster.servers[0].tables[cfg.table_name_with_type]
        predicted = predicted_block_bytes(mgr.get(names[0]))
        get_ledger().set_capacity(int(predicted * capacity_blocks))
        rows, host_served = [], 0
        for _ in range(2):           # two passes: evict/promote churn
            for sql in suite:
                res = cluster.query(sql)
                assert not res.stats["partialResult"]
                rows.append(res.rows)
                host_served += res.stats.get("segmentsServedHostTier", 0)
        return rows, host_served

    hot_rows, hot_host = run(tmp_path / "hot", capacity_blocks=100.0)
    tiered_rows, tiered_host = run(tmp_path / "tiered", capacity_blocks=1.5)
    assert hot_host == 0
    assert tiered_host > 0, "constrained run never exercised the host tier"
    # float aggregates accumulate in different precisions on the two plans
    # (device f32 reductions vs host f64) — identical up to rounding
    assert len(hot_rows) == len(tiered_rows)
    for hot_res, tiered_res in zip(hot_rows, tiered_rows):
        assert len(hot_res) == len(tiered_res)
        for hot_row, tiered_row in zip(hot_res, tiered_res):
            assert len(hot_row) == len(tiered_row)
            for hot_cell, tiered_cell in zip(hot_row, tiered_row):
                if isinstance(hot_cell, float):
                    assert tiered_cell == pytest.approx(hot_cell, rel=1e-6)
                else:
                    assert tiered_cell == hot_cell


def test_4x_capacity_table_serves_full_suite_without_oom(
        tmp_path, ssb_schema, fresh_ledger):
    """The tentpole acceptance: a table ~4x the pinned HBM capacity serves
    the full query suite with residency <= capacity after every query and in
    the ledger's watermark history (modulo transient scratch, which the
    watermark includes by design)."""
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 5)
    mgr = cluster.servers[0].tables[cfg.table_name_with_type]
    predicted = predicted_block_bytes(mgr.get(names[0]))
    capacity = int(predicted * 1.25)     # 5 blocks / 1.25 = 4x capacity
    get_ledger().set_capacity(capacity)

    suite = [
        "SELECT COUNT(*) FROM lineorder",
        "SELECT SUM(lo_revenue) FROM lineorder",
        "SELECT lo_region, COUNT(*) FROM lineorder GROUP BY lo_region "
        "ORDER BY lo_region LIMIT 10",
        "SELECT COUNT(*) FROM lineorder WHERE lo_discount >= 5",
    ]
    for round_ in range(2):
        for sql in suite:
            res = cluster.query(sql)
            assert not res.stats["partialResult"], sql
            snap = get_ledger().snapshot()
            assert snap["totalBytes"] <= capacity, \
                f"resident {snap['totalBytes']} > capacity {capacity}: {sql}"
    assert cluster.query(
        "SELECT COUNT(*) FROM lineorder").rows[0][0] == 5 * ROWS_PER_SEGMENT

    snap = get_ledger().snapshot()
    transient = snap["transientPeakBytes"]
    # the watermark is the peak of resident + transient scratch: residency
    # itself never passed capacity (the history ring samples on an interval
    # and may be empty in a fast test — the scalar peak always updates)
    assert snap["watermarkBytes"] <= capacity + transient
    for _, footprint in snap["watermarkHistory"]:
        assert footprint <= capacity + transient
    # the gate was actually exercised, not vacuously satisfied
    tiering = cluster.servers[0].tiering.snapshot()
    assert tiering["rejections"] + tiering["evictions"] > 0


# -- capacity knob ------------------------------------------------------------

def test_capacity_knob_overrides_probe_on_server_start(tmp_path,
                                                       fresh_ledger):
    """`server.hbm.capacity.bytes` replaces the probed/estimated capacity at
    server construction and marks it exact."""
    from pinot_tpu.cluster.catalog import Catalog
    from pinot_tpu.cluster.deepstore import LocalDeepStore
    from pinot_tpu.cluster.server import ServerNode
    catalog = Catalog()
    catalog.put_property("clusterConfig/server.hbm.capacity.bytes", "123456")
    server = ServerNode("server_knob", catalog,
                        LocalDeepStore(str(tmp_path / "ds")),
                        str(tmp_path / "data"))
    try:
        assert get_ledger().capacity_bytes() == (123456, False)
        assert get_ledger().snapshot()["capacityBytes"] == 123456
    finally:
        server.shutdown()


def test_malformed_capacity_knob_keeps_probed_value(tmp_path, fresh_ledger):
    from pinot_tpu.cluster.catalog import Catalog
    from pinot_tpu.cluster.deepstore import LocalDeepStore
    from pinot_tpu.cluster.server import ServerNode
    before = get_ledger().capacity_bytes()
    catalog = Catalog()
    catalog.put_property("clusterConfig/server.hbm.capacity.bytes",
                         "not-a-number")
    server = ServerNode("server_knob2", catalog,
                        LocalDeepStore(str(tmp_path / "ds")),
                        str(tmp_path / "data"))
    try:
        assert get_ledger().capacity_bytes() == before
    finally:
        server.shutdown()


# -- cold tier: demotion, lazy reload, deadline bound -------------------------

def test_cold_demotion_unloads_and_first_query_lazily_reloads(
        tmp_path, ssb_schema, fresh_ledger):
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 2)
    table = cfg.table_name_with_type
    server = cluster.servers[0]
    mgr = server.tables[table]
    assert cluster.query(
        "SELECT COUNT(*) FROM lineorder").rows[0][0] == 2 * ROWS_PER_SEGMENT

    assert cluster.controller.demote_segment_to_cold(table, names[0])
    # catalog notify is synchronous: the server reconciled inline
    from pinot_tpu.cluster.catalog import COLD
    assert cluster.catalog.external_view[table][names[0]] \
        == {"server_0": COLD}
    assert names[0] not in mgr.segment_names
    assert server.local_segment_dir(table, names[0]) is None
    assert get_ledger().resident_bytes(segment=names[0]) == 0
    assert _counter_value("pinot_controller_cold_demotions", table=table) == 1
    # re-demoting an already-cold segment is a no-op, not a double count
    assert not cluster.controller.demote_segment_to_cold(table, names[0])

    # COLD stays routable: the next query lazily downloads + answers in full
    res = cluster.query("SELECT COUNT(*) FROM lineorder")
    assert res.rows[0][0] == 2 * ROWS_PER_SEGMENT
    assert not res.stats["partialResult"]
    assert res.stats["segmentsColdLoaded"] == 1
    assert res.stats["coldLoadMs"] > 0
    assert server.tiering.snapshot()["coldLoads"] == 1
    assert _counter_value("pinot_server_hbm_cold_loads") == 1

    # the lazily loaded copy STAYS loaded (reconcile must not tear it down:
    # eviction is the tiering manager's call, not the reconciler's)
    server.reconcile(table)
    assert names[0] in mgr.segment_names
    res = cluster.query("SELECT COUNT(*) FROM lineorder")
    assert res.stats.get("segmentsColdLoaded", 0) == 0
    assert server.tiering.snapshot()["coldLoads"] == 1


def test_cold_load_past_deadline_fails_typed(tmp_path, ssb_schema,
                                             fresh_ledger):
    """A query whose budget is already spent must fail with a typed
    QueryTimeoutError BEFORE burning a deep-store download, naming the
    cold-tier load it refused."""
    from pinot_tpu.query.context import compile_query
    from pinot_tpu.query.scheduler import QueryTimeoutError
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 1)
    table = cfg.table_name_with_type
    server = cluster.servers[0]
    assert cluster.controller.demote_segment_to_cold(table, names[0])
    assert names[0] not in server.tables[table].segment_names

    ctx = compile_query("SELECT COUNT(*) FROM lineorder", ssb_schema)
    ctx.options["deadlineEpochMs"] = time.time() * 1000 - 1000
    with pytest.raises(QueryTimeoutError) as exc:
        server._execute_partial(table, ctx, [names[0]])
    assert "cold-tier load" in str(exc.value)
    # the refusal left nothing half-loaded
    assert names[0] not in server.tables[table].segment_names


# -- deep-store download faults: retry, quarantine ----------------------------

def test_download_retry_absorbs_transient_faults(tmp_path, ssb_schema,
                                                 fresh_ledger):
    """Two injected download failures < the default 3-attempt budget: the
    cold reload succeeds on the final attempt and the retries are counted."""
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 2)
    table = cfg.table_name_with_type
    assert cluster.controller.demote_segment_to_cold(table, names[0])

    sched = FaultSchedule({"deepstore.download.fail": {"p": 1.0, "count": 2}},
                          seed=3)
    with faults.active(sched):
        res = cluster.query("SELECT COUNT(*) FROM lineorder")
    assert sched.fired("deepstore.download.fail") == 2, \
        "the schedule never fired: the retry path was not exercised"
    assert res.rows[0][0] == 2 * ROWS_PER_SEGMENT
    assert not res.stats["partialResult"]
    assert _counter_value("pinot_deepstore_download_retries") >= 2


def test_download_exhaustion_quarantines_then_recovers(tmp_path, ssb_schema,
                                                       fresh_ledger):
    """Faults beyond the retry budget: the blob is quarantined (later
    fetches skip the backoff), the query outcome is typed or flagged —
    never silent short rows — and clearing the quarantine after the store
    recovers restores full answers."""
    from pinot_tpu.cluster.peers import clear_download_quarantine
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 2)
    table = cfg.table_name_with_type
    assert cluster.controller.demote_segment_to_cold(table, names[0])

    sched = FaultSchedule({"deepstore.download.fail": {"p": 1.0, "count": 50}},
                          seed=5)
    with faults.active(sched):
        try:
            res = cluster.query("SELECT COUNT(*) FROM lineorder")
        except Exception as e:
            outcome = f"error:{type(e).__name__}"
        else:
            assert res.stats["partialResult"], \
                f"silent short rows: {res.rows} without partialResult"
            outcome = "partial"
    assert sched.fired("deepstore.download.fail") >= 3
    assert outcome in ("partial", "error:ConnectionError",
                       "error:QueryScatterError", "error:RuntimeError")
    assert _counter_value("pinot_deepstore_download_quarantined") >= 1

    # store healthy again, but the blob is quarantined: deep store is still
    # skipped (and the only replica is COLD, so no peer can serve it). The
    # broker marked the erroring server unhealthy — re-admit it first, the
    # way the chaos scenarios model the operator/detector recovery.
    cluster.revive_server("server_0")
    cluster.broker.failure_detector.notify_healthy("server_0")
    try:
        res = cluster.query("SELECT COUNT(*) FROM lineorder")
        still_degraded = res.stats["partialResult"]
    except Exception:
        still_degraded = True
    assert still_degraded, "quarantine did not stick"

    clear_download_quarantine()      # operator re-admits the blob
    cluster.revive_server("server_0")
    cluster.broker.failure_detector.notify_healthy("server_0")
    res = cluster.query("SELECT COUNT(*) FROM lineorder")
    assert res.rows[0][0] == 2 * ROWS_PER_SEGMENT
    assert not res.stats["partialResult"]


# -- controller planes: retention demotion, memoryStatus rollup ---------------

def test_retention_demotes_to_cold_instead_of_deleting(tmp_path, ssb_schema,
                                                       fresh_ledger):
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 2)
    table = cfg.table_name_with_type
    cfg.retention_days = 1.0
    cluster.catalog.put_table_config(cfg)
    metas = cluster.catalog.segments[table]
    assert all(metas[n].end_time_ms is not None for n in names)
    future = max(m.end_time_ms for m in metas.values()) \
        + 2 * 24 * 3600 * 1000

    cluster.catalog.put_property(
        "clusterConfig/controller.retention.cold.demote", "true")
    acted = cluster.controller.run_retention(now_ms=future)
    assert sorted(acted) == sorted(f"cold:{table}/{n}" for n in names)
    # demoted, NOT deleted: metadata + deep-store copy survive, and the
    # table still answers in full via lazy cold reloads
    assert set(cluster.catalog.segments[table]) == set(names)
    res = cluster.query("SELECT COUNT(*) FROM lineorder")
    assert res.rows[0][0] == 2 * ROWS_PER_SEGMENT
    assert res.stats["segmentsColdLoaded"] == 2
    # a second pass finds everything already cold: nothing more to do
    assert cluster.controller.run_retention(now_ms=future) == []


def test_memory_status_carries_tiering_rollup(tmp_path, ssb_schema,
                                              fresh_ledger):
    cluster, cfg, names = _build_cluster(tmp_path, ssb_schema, 3)
    table = cfg.table_name_with_type
    mgr = cluster.servers[0].tables[table]
    predicted = predicted_block_bytes(mgr.get(names[0]))
    get_ledger().set_capacity(int(predicted * 1.5))
    cluster.query("SELECT COUNT(*) FROM lineorder")

    verdicts = cluster.controller.run_memory_check()
    assert verdicts[table] in ("HEALTHY", "DEGRADED", "UNHEALTHY")
    st = cluster.controller.memory_status(table)
    tiering = st["tiering"]
    assert tiering["admissions"] >= 1
    assert tiering["rejections"] >= 1
    # the cluster_top memory panel renders the same rollup
    from pinot_tpu.tools import cluster_top
    text = cluster_top.render({
        "tables": {}, "memory": {table: st}, "slo": {}})
    assert "tiering:" in text and "rejections=" in text
