#!/usr/bin/env python3
"""Chip smoke: serve SQL from the chip through the entry points users start.

    python chip_smoke.py [--seed N] [--rows N] [--chips 4] [--rehearse]

One process. Controller + server + broker start through
`run_service_manager(block=False)` with `server.device.enabled=true` (the
service-manager role: every role in the one chip-owning process), an SSB
lineorder table is built from `--seed`, uploaded through the controller and
loaded by the server onto the device, and each query below is answered twice
(cold, warm) over broker HTTP and compared with a plain numpy evaluation over
the same generated columns. Exit code 0 only if every phase passed: nothing
here catches an exception to let a failed phase continue.

Without `--chips` it needs one TPU chip; finding none is a failure, not a CPU
run. `--chips 4` runs ONLY the four-chip phase: the same store with
`server.mesh.devices=4`, the same queries and comparison, plus proof that the
work is spread over the four devices. `--rehearse` (CPU sandbox) relaxes the
platform check and the default size, nothing else.

The cold/warm times it prints are a smoke's, not a benchmark's.

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEGMENT_ROWS = 4 << 20          # 4Mi-row segments
DEFAULT_ROWS = 64 << 20         # ~ SSB SF10's lineorder (59,986,052 rows)
REHEARSE_ROWS = 1 << 20
SUPPKEYS = 20_000               # the chunked matmul
CUSTKEYS = 500_000              # past chunk_cap: the sort regime
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
LOAD_TIMEOUT_S = 600.0
BUILD_THREADS = 4
SUM_RTOL = 1e-4                 # f32-accumulated sums (the differential tests')
HLL_RTOL = 3 * 1.04 / 64        # 3 sigma at p=12 (4096 registers, ~1.6%)

QUERIES = [
    ("q1.1 filter+sum",
     "SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder "
     "WHERE lo_orderdate BETWEEN 19930101 AND 19931231 "
     "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25"),
    ("group-by region",
     "SELECT lo_region, SUM(lo_revenue), COUNT(*), MAX(lo_quantity) "
     "FROM lineorder WHERE lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25 "
     "GROUP BY lo_region ORDER BY lo_region LIMIT 10"),
    # 5 regions x 50 quantities: 250 keys, 256 padded (SSB Q4.1's shape): past
    # `masked_cap`, the one-hot matmul regime, which "group-by region" (5
    # keys: the masked reduce) no longer runs
    ("group-by region x quantity",
     "SELECT lo_region, lo_quantity, SUM(lo_revenue), COUNT(*) "
     "FROM lineorder WHERE lo_discount BETWEEN 1 AND 3 "
     "GROUP BY lo_region, lo_quantity "
     "ORDER BY lo_region, lo_quantity LIMIT 300"),
    ("group-by 20k keys",
     "SELECT lo_suppkey, SUM(lo_revenue), COUNT(*) FROM lineorder "
     "GROUP BY lo_suppkey LIMIT 100000"),
    ("group-by 500k keys",
     "SELECT lo_custkey, SUM(lo_revenue), COUNT(*) FROM lineorder "
     "GROUP BY lo_custkey LIMIT 600000"),
    ("bitmap-filter count",
     "SELECT COUNT(*) FROM lineorder WHERE lo_region = 'ASIA'"),
    ("distinctcounthll",
     "SELECT DISTINCTCOUNTHLL(lo_orderdate) FROM lineorder "
     "WHERE lo_quantity < 25"),
    ("top-k",
     "SELECT lo_revenue FROM lineorder WHERE lo_quantity >= 10 "
     "ORDER BY lo_revenue DESC LIMIT 10"),
]


def lineorder_schema():
    """The lineorder (SSB) schema."""
    from pinot_tpu.schema import DataType, Schema, date_time, dimension, metric
    return Schema("lineorder", [
        dimension("lo_region", DataType.STRING),
        dimension("lo_suppkey", DataType.INT),
        dimension("lo_custkey", DataType.INT),
        date_time("lo_orderdate", DataType.INT),
        metric("lo_quantity", DataType.INT),
        metric("lo_extendedprice", DataType.DOUBLE),
        metric("lo_discount", DataType.INT),
        metric("lo_revenue", DataType.DOUBLE),
    ])


def segment_columns(seed: int, i: int, n: int, suppkeys: int, custkeys: int):
    """Segment i's columns, from the seed alone (uniform draws).
    Every key value occurs in every segment (the first `keys` rows are a
    permutation of the key space), so the per-segment dictionaries agree and
    the set rides the aligned stacked block like `build_aligned_segments`
    data does."""
    rng = np.random.default_rng([seed, i])

    def keys(card):
        k = rng.integers(0, card, n).astype(np.int32)
        k[:card] = rng.permutation(card).astype(np.int32)
        return k

    region = rng.integers(0, len(REGIONS), n).astype(np.int8)
    region[:len(REGIONS)] = np.arange(len(REGIONS))
    return {
        "lo_region": region,
        "lo_suppkey": keys(suppkeys),
        "lo_custkey": keys(custkeys),
        "lo_orderdate": (19920101 + rng.integers(0, 7, n) * 10000
                         + rng.integers(1, 13, n) * 100
                         + rng.integers(1, 29, n)).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        "lo_extendedprice": np.round(rng.uniform(1.0, 10_000.0, n), 2),
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_revenue": np.round(rng.uniform(1.0, 60_000.0, n), 2),
    }


# -- the plain reference: numpy over the generated columns ---------------------

def reference(name: str, c: dict):
    """The expected answer of QUERIES[name], independent of pinot_tpu.query."""
    if name == "q1.1 filter+sum":
        m = ((c["lo_orderdate"] >= 19930101) & (c["lo_orderdate"] <= 19931231)
             & (c["lo_discount"] >= 1) & (c["lo_discount"] <= 3)
             & (c["lo_quantity"] < 25))
        return float((c["lo_extendedprice"][m] * c["lo_discount"][m]).sum())
    if name == "group-by region":
        m = ((c["lo_discount"] >= 1) & (c["lo_discount"] <= 3)
             & (c["lo_quantity"] < 25))
        r = c["lo_region"][m]
        sums = np.bincount(r, weights=c["lo_revenue"][m], minlength=5)
        cnts = np.bincount(r, minlength=5)
        maxq = np.zeros(5, np.int64)
        np.maximum.at(maxq, r, c["lo_quantity"][m])
        return [(REGIONS[i], float(sums[i]), int(cnts[i]), int(maxq[i]))
                for i in range(5)]
    if name == "group-by region x quantity":
        m = (c["lo_discount"] >= 1) & (c["lo_discount"] <= 3)
        k = c["lo_region"][m].astype(np.int64) * 51 + c["lo_quantity"][m]
        sums = np.bincount(k, weights=c["lo_revenue"][m], minlength=5 * 51)
        cnts = np.bincount(k, minlength=5 * 51)
        return [(REGIONS[i // 51], i % 51, float(sums[i]), int(cnts[i]))
                for i in np.flatnonzero(cnts)]
    if name in ("group-by 20k keys", "group-by 500k keys"):
        k = c["lo_suppkey" if "20k" in name else "lo_custkey"]
        return (np.bincount(k, weights=c["lo_revenue"]), np.bincount(k))
    if name == "bitmap-filter count":
        return int((c["lo_region"] == REGIONS.index("ASIA")).sum())
    if name == "distinctcounthll":
        return int(np.unique(c["lo_orderdate"][c["lo_quantity"] < 25]).size)
    if name == "top-k":
        v = c["lo_revenue"][c["lo_quantity"] >= 10]
        return np.sort(np.partition(v, v.size - 10)[-10:])[::-1].tolist()
    raise KeyError(name)


def check(name: str, rows: list, want) -> None:
    """Raise unless the broker's rows equal the reference (counts and keys
    exact, sums to SUM_RTOL, HLL to its documented error)."""
    def close(a, b):
        return abs(a - b) <= SUM_RTOL * max(abs(b), 1.0)

    if name == "q1.1 filter+sum":
        ok = len(rows) == 1 and close(rows[0][0], want)
    elif name in ("group-by region", "group-by region x quantity"):
        # row by row in the ORDER BY's order: sums close, all else equal
        ok = len(rows) == len(want) and all(
            len(r) == len(w) and all(
                close(a, b) if isinstance(b, float) else a == b
                for a, b in zip(r, w)) for r, w in zip(rows, want))
    elif name.startswith("group-by"):
        sums, cnts = want
        got = np.asarray(rows, dtype=np.float64)
        keys = got[:, 0].astype(np.int64)
        ok = (len(rows) == int((cnts > 0).sum())
              and np.unique(keys).size == keys.size
              and np.array_equal(got[:, 2].astype(np.int64), cnts[keys])
              and bool(np.all(np.abs(got[:, 1] - sums[keys])
                              <= SUM_RTOL * np.maximum(np.abs(sums[keys]),
                                                       1.0))))
    elif name == "bitmap-filter count":
        ok = len(rows) == 1 and rows[0][0] == want
    elif name == "distinctcounthll":
        ok = len(rows) == 1 and abs(rows[0][0] - want) <= HLL_RTOL * want
    elif name == "top-k":
        ok = [r[0] for r in rows] == want
    else:
        raise KeyError(name)
    if not ok:
        raise AssertionError(
            f"{name}: broker answer differs from the numpy reference "
            f"(got {str(rows[:5])[:300]}..., want {str(want)[:300]}...)")


# -- phases ------------------------------------------------------------------

def describe_platform(args):
    """Phase 1: cache placement, versions, device. Fails without the chip(s)."""
    from pinot_tpu.utils.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    import jaxlib

    from pinot_tpu import native
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    devs = jax.devices()
    want = args.chips or 1
    print(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu} "
          f"numpy {np.__version__}")
    print(f"platform {devs[0].platform} device_kind {devs[0].device_kind!r} "
          f"devices {len(devs)}")
    print(f"compile cache: {cache_dir} "
          f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
          f"entries at start; JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    print(f"native: {'loaded' if native.get_lib() is not None else 'absent'}")
    if not args.rehearse and devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; jax found platform "
                         f"{devs[0].platform!r} (use --rehearse on a CPU)")
    if len(devs) < want:
        raise SystemExit(f"chip_smoke --chips {want} needs {want} devices; "
                         f"jax found {len(devs)}")
    return jax, cache_dir


def start_services(work: str, chips: int):
    """Phase 2a: all roles in this (chip-owning) process."""
    from pinot_tpu.cluster.process import run_service_manager
    cfg = {"server.device.enabled": "true",
           "server.mesh.devices": str(chips)}
    cfg_path = os.path.join(work, "smoke.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    return run_service_manager(os.path.join(work, "work"),
                               os.path.join(work, "run"),
                               config_path=cfg_path, block=False)


def stop_services(handles) -> None:
    handles["minion"].stop()  # claim loop first: it polls the controller
    handles["server_obj"].shutdown()
    handles["controller_obj"].stop_periodic_tasks()
    for c in handles["catalogs"]:
        c.close()
    for role in ("controller", "server", "broker"):
        handles[role].stop()


def load_table(handles, work: str, args, seg_rows: int, suppkeys: int,
               custkeys: int) -> dict:
    """Phase 2b: schema + OFFLINE table, segments from --seed through the
    controller, wait for the broker's COUNT(*). Returns the full columns."""
    from pinot_tpu.cluster.process import BrokerClient, ControllerClient
    from pinot_tpu.segment.writer import SegmentBuilder
    from pinot_tpu.table import TableConfig

    ctrl = ControllerClient(handles["controller"].url)
    broker = BrokerClient(handles["broker"].url)
    schema = lineorder_schema()
    ctrl.add_schema(schema)
    table = TableConfig("lineorder")
    ctrl.add_table(table)
    n_segs = args.rows // seg_rows
    region_names = np.array(REGIONS, dtype=object)

    def build_and_upload(i: int) -> dict:
        cols = segment_columns(args.seed, i, seg_rows, suppkeys, custkeys)
        seg_dir = SegmentBuilder(schema).build(
            dict(cols, lo_region=region_names[cols["lo_region"]]),
            os.path.join(work, "build"), f"lineorder_{i}")
        ctrl.upload_segment(table.table_name_with_type, seg_dir)
        shutil.rmtree(seg_dir)
        return cols

    # generate + build + gzip + upload is host work that mostly releases the
    # GIL (numpy, zlib): a few segments at a time keeps set-up inside the
    # smoke's time limit without changing what is loaded
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=BUILD_THREADS) as pool:
        parts = list(pool.map(build_and_upload, range(n_segs)))
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    deadline = t0 + LOAD_TIMEOUT_S
    loaded = -1
    while loaded != args.rows:
        if time.perf_counter() > deadline:
            raise TimeoutError(f"only {loaded}/{args.rows} rows loaded after "
                               f"{LOAD_TIMEOUT_S:.0f}s")
        r = broker.query("SELECT COUNT(*) FROM lineorder")["resultTable"]["rows"]
        loaded = r[0][0] if r else 0
        if loaded != args.rows:
            time.sleep(0.25)
    t_wait = time.perf_counter() - t0
    print(f"loaded {loaded} rows in {n_segs} segments of {seg_rows} through "
          f"controller -> server (generate+build+upload {t_build:.1f}s on "
          f"{BUILD_THREADS} threads, then wait for load {t_wait:.1f}s)")
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def server_state(handles) -> dict:
    """The server's pipeline stats and memory ledger, over its HTTP surface."""
    from pinot_tpu.cluster.http_service import get_json
    url = handles["server"].url
    return {"device": get_json(f"{url}/health")["device"],
            "memory": get_json(f"{url}/debug/memory")}


def run_queries(handles, cols: dict) -> int:
    """Phase 3: every query cold then warm over broker HTTP, each compared
    with its numpy reference. Returns the number of queries sent.

    The pipeline's dispatcher thread serves many queries per drain, so the
    kernel-compile accounting does not reach a single response: compile ms
    and executable-cache misses are read as deltas of this process's metric
    registry (`pinot_kernel_compile_ms`, `pinot_kernel_cache_misses`);
    `deviceFetchMs` (the batched host sync: device execution + transfer) and
    `deviceLaunches` come from the response."""
    from pinot_tpu.cluster.process import BrokerClient
    from pinot_tpu.utils.metrics import get_registry
    broker = BrokerClient(handles["broker"].url)
    misses = get_registry().counter("pinot_kernel_cache_misses")
    compile_ms = get_registry().histogram("pinot_kernel_compile_ms")
    sent = 0
    for name, sql in QUERIES:
        want = reference(name, cols)
        line = [f"{name}:"]
        for run in ("cold", "warm"):
            m0, c0 = misses.value, compile_ms.total
            t0 = time.perf_counter()
            resp = broker.query(sql, timeout=900.0)
            ms = (time.perf_counter() - t0) * 1000
            sent += 1
            if resp.get("exceptions") or resp.get("partialResult") or \
                    resp["numServersResponded"] != resp["numServersQueried"]:
                raise AssertionError(
                    f"{name} ({run}): incomplete answer: "
                    f"{ {k: v for k, v in resp.items() if k != 'resultTable'} }")
            check(name, resp["resultTable"]["rows"], want)
            if int(resp.get("deviceLaunches", 0)) < 1:
                raise AssertionError(
                    f"{name} ({run}): deviceLaunches="
                    f"{resp.get('deviceLaunches')} - the host answered")
            missed = int(misses.value - m0) + int(
                resp.get("compileCacheMisses", 0))
            if run == "warm" and missed != 0:
                raise AssertionError(
                    f"{name}: warm run built {missed} executable(s) again")
            line.append(
                f"{run} {ms:.1f} ms (compileMs {compile_ms.total - c0:.1f}, "
                f"compileCacheMisses {missed}, deviceExecMs "
                f"{resp.get('deviceExecMs', 'not attributed')}, deviceFetchMs "
                f"{float(resp.get('deviceFetchMs', 0.0)):.2f})")
        print(" ".join(line) + " = numpy reference")
    return sent


def round_trip_ms(jax) -> None:
    """Dispatch + fetch round trip of a trivial kernel (what the planner's
    SMALL_SCAN_DOCS gate stands in for): median of a few."""
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1)
    x = jax.block_until_ready(jnp.zeros((), jnp.int32))
    np.asarray(f(x))
    sync, fetch = [], []
    for _ in range(21):
        t0 = time.perf_counter()
        y = jax.block_until_ready(f(x))
        t1 = time.perf_counter()
        np.asarray(y)
        t2 = time.perf_counter()
        sync.append((t1 - t0) * 1000)
        fetch.append((t2 - t0) * 1000)
    print(f"trivial kernel round trip: dispatch+block_until_ready median "
          f"{np.median(sync)} ms, dispatch+fetch median {np.median(fetch)} ms "
          f"(21 readings)")


def check_spread(jax, handles, before: list, chips: int) -> None:
    """Four-chip phase only: the stacked block is sharded over `chips`
    distinct devices, every device's memory grew, and the >= 4096-key
    group-bys took the psum_scatter path."""
    from pinot_tpu.utils.metrics import get_registry
    pipeline = handles["server_obj"].device_pipeline
    mesh_exec = pipeline.mesh_exec
    if mesh_exec.n_devices != chips:
        raise AssertionError(f"mesh has {mesh_exec.n_devices} devices")
    owners = set()
    n_arrays = 0
    for _, block in mesh_exec._set_blocks.values():
        for arr in block._cache.values():
            devs = {s.device for s in arr.addressable_shards}
            if len(devs) != chips:
                raise AssertionError(
                    f"a stacked block array lives on {len(devs)} devices")
            owners |= devs
            n_arrays += 1
    if n_arrays == 0 or len(owners) != chips:
        raise AssertionError(f"{n_arrays} stacked arrays on {len(owners)} "
                             "devices")
    grew = []
    for d, b in zip(jax.devices()[:chips], before):
        now = (d.memory_stats() or {}).get("bytes_in_use")
        if b is None or now is None:
            grew.append(None)   # CPU rehearsal: the backend reports nothing
        elif now <= b:
            raise AssertionError(f"{d}: bytes_in_use did not grow "
                                 f"({b} -> {now})")
        else:
            grew.append(now - b)
    scattered = int(get_registry().counter(
        "pinot_kernel_scatter_builds").value)
    if scattered < 1:
        raise AssertionError("no kernel took the psum_scatter path")
    print(f"spread: {n_arrays} stacked arrays each sharded over {chips} "
          f"devices; bytes_in_use growth per device {grew}; "
          f"psum_scatter kernels built {scattered}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help=f"table rows (default {DEFAULT_ROWS}; a multiple of "
                         f"the {SEGMENT_ROWS}-row segment)")
    ap.add_argument("--chips", type=int, default=0, choices=[0, 4],
                    help="4: run ONLY the four-chip mesh phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU sandbox: relax the platform check and the size")
    args = ap.parse_args()
    args.rows = args.rows or (REHEARSE_ROWS if args.rehearse else DEFAULT_ROWS)
    seg_rows = min(SEGMENT_ROWS, args.rows // 4)
    if args.rows % seg_rows or seg_rows < 1024:
        raise SystemExit(f"--rows {args.rows}: not a multiple of the "
                         f"{seg_rows}-row segment")
    # key spaces are the source's at real size; a rehearsal's tiny segments
    # cannot hold every key, so they shrink with it (never on the chip)
    suppkeys = min(SUPPKEYS, seg_rows // 8)
    custkeys = min(CUSTKEYS, seg_rows // 2)
    if not args.rehearse and (suppkeys, custkeys) != (SUPPKEYS, CUSTKEYS):
        raise SystemExit(f"--rows {args.rows} is too small for the real key "
                         "spaces; sizes are cut only with --rehearse")

    t_start = time.perf_counter()
    jax, cache_dir = describe_platform(args)
    chips = args.chips or 1
    mem_before = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()[:chips]]
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    handles = start_services(work, chips)
    try:
        cols = load_table(handles, work, args, seg_rows, suppkeys, custkeys)
        base = server_state(handles)["device"]
        sent = run_queries(handles, cols)
        state = server_state(handles)
        dev, mem = state["device"], state["memory"]
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()[:chips]]
        print(f"HBM: ledger-tracked resident {mem['totalBytes']} bytes of "
              f"capacity {mem['capacityBytes']} (estimated: "
              f"{mem['capacityEstimated']}); device bytes_in_use {in_use} "
              f"(the mesh executor's stacked blocks are not ledger-registered)")
        if mem["capacityEstimated"] and not args.rehearse:
            raise AssertionError("HBM capacity is an estimate on the chip")
        dispatched = dev["dispatched"] - base["dispatched"]
        fallbacks = dev["fallbacks"] - base["fallbacks"]
        print(f"pipeline: sent {sent} dispatched {dispatched} fallbacks "
              f"{fallbacks} deviceErrors {dev['deviceErrors']} timeouts "
              f"{dev['timeouts']} launches {dev['launches'] - base['launches']}"
              f" (before the queries: {base['fallbacks']} fallbacks from the "
              f"metadata-answered COUNT(*) load probe)")
        if (dispatched != sent or fallbacks != 0 or dev["deviceErrors"] != 0
                or dev["timeouts"] != 0):
            raise AssertionError("a query did not ride the device path")
        if args.chips:
            check_spread(jax, handles, mem_before, chips)
        else:
            round_trip_ms(jax)
    finally:
        stop_services(handles)
        shutil.rmtree(work, ignore_errors=True)
    print(f"compile cache: {len(os.listdir(cache_dir))} entries at end; "
          f"smoke took {time.perf_counter() - t_start:.0f} s")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
