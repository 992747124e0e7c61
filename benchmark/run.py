#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's table from `--seed` (a process pool, one segment a worker),
serves it from the chip through `run_service_manager` (every role in this one
chip-owning process), drives it over broker HTTP from a child process, and
compares every answer with the numpy reference. The last line of stdout is the
result object; see benchmark/README.md.

`--rehearse` (CPU sandbox) relaxes the platform check and cuts the rows; it
prints `cpu` as its device and is never a measurement. `--control 1` also
puts each control in the program's place (the reference at bfloat16, rounded
to the nearest and truncated: `reference.ROUNDINGS`; the reference with one
segment left out) and reports its numbers and its own `correct`, by the same
limits; each has to come out false.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ProcessPoolExecutor  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import (build, cells, readers, reference,  # noqa: E402
                               serve, traffic)

REHEARSE_SEGMENT_ROWS = 65_536
MAX_WORKERS = 4          # more at once ran a 40 GiB host out of memory (PERF.md)
TRACE_LEAD_S = 2.0
TRACE_SLICE_S = 8.0
DEVICE = "device not opened yet"


def log(msg: str) -> None:
    print(f"[{DEVICE}] {msg}", flush=True)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def answered_by_slice(records, seconds: float, width: float = 5.0) -> list:
    """Answers that arrived in each `width` seconds of the window: a stall or a
    change of regime inside a run shows here."""
    counts = [0] * max(1, math.ceil(seconds / width))
    for r in records:
        if r["done"] <= seconds:
            counts[min(len(counts) - 1, int(r["done"] // width))] += 1
    return counts


class LoadGen:
    """The child that sends the queries (harness/loadgen.py)."""

    def __init__(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "harness",
                                          "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True)

    def send(self, cmd: dict) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def reply(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError("the load generator died")
        return json.loads(line)

    def ask(self, cmd: dict) -> dict:
        self.send(cmd)
        return self.reply()

    def stop(self) -> None:
        try:
            self.send({"cmd": "quit"})
        except Exception:
            pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def incomplete(resp: dict) -> bool:
    return bool(resp.get("exceptions") or resp.get("partialResult")
                or resp.get("numServersResponded")
                != resp.get("numServersQueried")
                or "resultTable" not in resp)


def judge(records, pool, want, sum_limit: float) -> dict:
    """Every answer against the reference; the numbers `correct` rests on."""
    n = {"unanswered": 0, "incomplete": 0, "wrong_rows": 0,
         "count_mismatch": 0, "sum_rel_gap_max": 0.0}
    failed, notes = 0, []
    for r in records:
        bad = ""
        if not r["ok"]:
            n["unanswered"] += 1
            bad = r["error"]
        elif incomplete(r["response"]):
            n["incomplete"] += 1
            bad = "incomplete: " + str({k: v for k, v in r["response"].items()
                                        if k != "resultTable"})[:300]
        else:
            c = reference.compare(pool[r["pool"]]["spec"],
                                  r["response"]["resultTable"]["rows"],
                                  want[r["pool"]], sum_limit)
            n["wrong_rows"] += c["wrong"]
            n["count_mismatch"] += c["count_wrong"]
            n["sum_rel_gap_max"] = max(n["sum_rel_gap_max"], c["sum_gap"])
            if c["wrong"] or c["count_wrong"] or not c["sum_gap"] <= sum_limit:
                bad = c["why"] or f"sum gap {c['sum_gap']}"
        if bad:
            failed += 1
            if len(notes) < 5:
                notes.append(f"{pool[r['pool']]['sql'][:120]} -> {bad}")
    return {"numbers": n, "failed": failed, "notes": notes}


def reference_answers(pool_exec, cell, seed, rows_per_segment, pool, tables,
                      control: bool):
    """The reference's answer to every query of the pool: the segments are
    generated again from the seed in the workers, evaluated in parts, and the
    parts added by group key."""
    jobs = [{"config": cell["config"], "seed": seed, "index": i,
             "rows": rows_per_segment, "pool": [p["spec"] for p in pool],
             "control": control}
            for i in range(cell["config"]["segments"])]
    parts = sorted(pool_exec.map(build.reference_segment, jobs),
                   key=lambda p: p["index"])

    def answers(key, keep=lambda i: True):
        return [reference.finish(
            pool[q]["spec"],
            reference.merge([p[key][q] for p in parts if keep(p["index"])]),
            tables) for q in range(len(pool))]
    want = answers("parts")
    controls = None
    if control:
        last = len(parts) - 1
        controls = {name: answers(name) for name in reference.ROUNDINGS}
        controls["segment_left_out"] = answers("parts", lambda i: i != last)
    return want, controls


class LeastMemory:
    """Samples /proc/meminfo's MemAvailable once a second during set-up; the
    least it saw goes into the set-up line (the chip's host ends a command
    that passes its limit)."""

    def __init__(self):
        self.least_mb, self._stop = None, threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.wait(1.0):
            try:
                with open("/proc/meminfo") as f:
                    for line in f:
                        if line.startswith("MemAvailable:"):
                            mb = int(line.split()[1]) // 1024
                            if self.least_mb is None or mb < self.least_mb:
                                self.least_mb = mb
            except OSError:
                return

    def stop(self):
        self._stop.set()
        self._thread.join()
        return self.least_mb


def open_chip(args, cell):
    """JAX's devices, or an exit with no result where the chip is not there."""
    global DEVICE
    from pinot_tpu.utils.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    devs = jax.devices()
    DEVICE = (f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"
              + (" REHEARSAL" if args.rehearse else ""))
    if not args.rehearse and devs[0].platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; jax found "
                         f"{devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"{args.workload} needs {cell['chips']} chips; "
                         f"jax found {len(devs)}")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache {cache_dir} ({entries} entries); host cores "
        f"{os.cpu_count()}")
    return jax, devs


def warm_up(loadgen, cell, pool, walks, url) -> int:
    """Every query of the pool once alone (a literal can build a program of
    its own: PERF.md, section 5), then the mix together for a few seconds.
    Returns the kernel-cache miss count the window starts from; a program
    first met inside the window fails the run (`compiles_in_window`)."""
    mix = cell["traffic"]
    patient = max(float(mix["timeout_s"]), 900.0)    # a cold cache compiles
    t0 = time.perf_counter()
    m0 = serve.kernel_cache_misses()
    built_by = []
    for p in pool:
        before = serve.kernel_cache_misses()
        rec = loadgen.ask({"cmd": "one", "url": url, "sql": p["sql"],
                           "timeout_s": patient})["record"]
        if not rec["ok"] or incomplete(rec["response"]):
            raise SystemExit(f"warm-up: {p['sql']} -> "
                             f"{rec['error'] or rec['response']}")
        built = serve.kernel_cache_misses() - before
        if built:
            built_by.append(f"{p['template']}/{p['variant']}:{built}")
    m1 = serve.kernel_cache_misses()
    loadgen.ask({"cmd": "window", "url": url,
                 "pool": [p["sql"] for p in pool],
                 "walks": [w[len(w) // 2:] for w in walks],
                 "seconds": float(mix.get("warm_seconds", 3.0)),
                 "clients": int(mix["clients"]), "timeout_s": patient})
    m2 = serve.kernel_cache_misses()
    log(f"warm-up {time.perf_counter() - t0:.1f} s: {len(pool)} queries "
        f"alone built {m1 - m0} executables ({' '.join(built_by)}), the mix "
        f"together {m2 - m1} more")
    return m2


def trace_slice(jax, args, prof_dir) -> None:
    """Profile a slice of the running window under a `bench:window` span."""
    time.sleep(min(TRACE_LEAD_S, args.seconds / 4))
    jax.profiler.start_trace(prof_dir, profiler_options=trace_options(jax))
    with jax.profiler.TraceAnnotation("bench:window"):
        time.sleep(min(TRACE_SLICE_S, args.seconds / 2))
    jax.profiler.stop_trace()


def trace_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def solo_replay(jax, args, loadgen, cell, pool, url, rows, solo_dir):
    """Traced: each template's first variant once, alone, under a
    `bench:solo:<template>` span. Returns (records, [{"template",
    "latency_ms", "least_bytes", "busy_s"}])."""
    from benchmark.harness import trace_reduce
    records, solo = [], []
    jax.profiler.start_trace(solo_dir, profiler_options=trace_options(jax))
    for i, p in enumerate(pool):
        if p["variant"] != 0:
            continue
        with jax.profiler.TraceAnnotation(f"bench:solo:{p['template']}"):
            rec = loadgen.ask({"cmd": "one", "url": url, "pool": i,
                               "sql": p["sql"], "timeout_s":
                               float(cell["traffic"]["timeout_s"])})["record"]
        records.append(rec)
        solo.append({"template": p["template"],
                     "latency_ms": (rec["done"] - rec["sent"]) * 1000.0,
                     "least_bytes": readers.least_bytes(
                         p["spec"], dict(cell["config"], rows=rows))})
    jax.profiler.stop_trace()
    spans = trace_reduce.reduce(trace_reduce.newest_xplane(solo_dir),
                                cpu_stand_in=args.rehearse)["spans"]
    for s in solo:
        s["busy_s"] = sum(sp["busy_s"] for sp in
                          spans.get(f"bench:solo:{s['template']}", []))
        log(f"solo {s['template']}: latency {s['latency_ms']:.1f} ms, device "
            f"busy {s['busy_s'] * 1000:.2f} ms, least bytes {s['least_bytes']}")
    return records, solo


def widest_by_aggregate(answers, pool, want) -> dict:
    """{"template/aggregate": the widest gap of that SUM or AVG} over
    (pool index, rows) answers."""
    out = {}
    for q, rows in answers:
        for name, gap in reference.gaps_by_name(pool[q]["spec"], rows,
                                                want[q]).items():
            key = f"{pool[q]['template']}/{name}"
            out[key] = max(out.get(key, 0.0), gap)
    return out


def control_numbers(controls, records, pool, want, limits) -> dict:
    """Each control put in the program's place and judged by the same
    comparison and the same limits: its numbers and its own `correct`, which
    has to come out false; beside them the program's widest sum gaps, and
    every side's widest gap aggregate by aggregate."""
    sum_limit = limits["sum_rel_gap_max"]
    out = {}
    for name, answers in controls.items():
        fake = [{"ok": True, "pool": q, "response": {
            "numServersQueried": 1, "numServersResponded": 1,
            "resultTable": {"rows": answers[q]}}} for q in range(len(pool))]
        numbers = judge(fake, pool, want, sum_limit)["numbers"]
        out[name] = {"correct": all(numbers[k] <= limits[k] for k in numbers),
                     "numbers": numbers,
                     "gap_by_aggregate": widest_by_aggregate(
                         enumerate(answers), pool, want)}
    served = [(r["pool"], r["response"]["resultTable"]["rows"])
              for r in records if r["ok"] and not incomplete(r["response"])]
    gaps = [reference.compare(pool[q]["spec"], rows, want[q],
                              sum_limit)["sum_gap"] for q, rows in served]
    out["program_sum_gaps_sorted_top"] = sorted(gaps)[-5:]
    out["program_gap_by_aggregate"] = widest_by_aggregate(served, pool, want)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced slice's .xplane.pb to this file")
    args = ap.parse_args(argv)

    import pinot_tpu  # noqa: F401  (a checkout without the program fails here)
    cell = cells.load_cell(args.workload)
    config, mix = cell["config"], cell["traffic"]
    n_seg = int(config["segments"])
    seg_rows = (REHEARSE_SEGMENT_ROWS if args.rehearse
                else int(config["rows"]) // n_seg)
    rows = seg_rows * n_seg
    sum_limit = float(config["guarantees"]["sum_rel_gap"])
    work = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)

    tables = cells.load_generator(config).tables(config)
    pool = traffic.build_pool(mix, cell["templates"], tables, args.seed)
    walks = traffic.client_walks(mix, pool, args.seed)
    sqls = [p["sql"] for p in pool]
    table_with_type = config["table"] + "_OFFLINE"
    seg_out = serve.server_segment_dir(work, table_with_type)
    os.makedirs(seg_out)

    loadgen = LoadGen()     # before this process touches JAX
    workers = min(os.cpu_count() or 1, n_seg, MAX_WORKERS)
    pool_exec = ProcessPoolExecutor(
        max_workers=workers, mp_context=multiprocessing.get_context("spawn"))
    handles = None
    try:
        # -- set-up: the workers build while this process opens the chip -------
        t0 = time.perf_counter()
        memory = LeastMemory()
        builds = [pool_exec.submit(build.build_segment, {
            "config": config, "seed": args.seed, "index": i, "rows": seg_rows,
            "out_dir": seg_out}) for i in range(n_seg)]
        jax, devs = open_chip(args, cell)
        peaks = None if args.rehearse else cells.peaks(devs[0].device_kind)
        log(f"{args.workload} seed {args.seed}: {rows} rows in {n_seg} "
            f"segments on {workers} build workers, {mix['clients']} clients, "
            f"pool of {len(pool)} queries")
        handles = serve.start_services(work, config["cluster"])
        serve.create_table(handles, config, table_with_type)
        built, upload_tail_s = serve.upload_as_built(handles, table_with_type,
                                                     builds)
        t_build = time.perf_counter() - t0 - upload_tail_s
        load_s = serve.wait_loaded(handles, config, rows)
        log(f"set-up: generate+build {t_build:.1f} s wall (a segment: generate "
            f"{statistics.fmean(b['generate_s'] for b in built):.1f} s, build "
            f"{statistics.fmean(b['build_s'] for b in built):.1f} s, "
            f"{sum(b['bytes'] for b in built)} bytes in all), upload (gzip, "
            f"metadata, assignment) {upload_tail_s:.1f} s more after the last "
            f"build, load {load_s:.1f} s; least host memory available "
            f"{memory.stop()} MB")
        url = handles["broker"].url
        misses0 = warm_up(loadgen, cell, pool, walks, url)

        # -- the window --------------------------------------------------------
        c0 = serve.pipeline_counters(handles)
        setup_s = time.perf_counter() - T_START
        loadgen.send({"cmd": "window", "url": url, "pool": sqls,
                      "walks": walks, "clients": int(mix["clients"]),
                      "seconds": args.seconds,
                      "timeout_s": float(mix["timeout_s"])})
        prof_dir = os.path.join(work, "profile")
        if args.trace:
            trace_slice(jax, args, prof_dir)
        window_records = loadgen.reply()["records"]
        c1 = serve.pipeline_counters(handles)
        compiles = serve.kernel_cache_misses() - misses0
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devs[:cell["chips"]])
        counters = {k: c1[k] - c0[k] for k in c0
                    if isinstance(c0[k], (int, float))
                    and isinstance(c1.get(k), (int, float))}
        trace, solo_records, solo = None, [], []
        if args.trace:      # reduced only now: this process served the window
            from benchmark.harness import trace_reduce
            xplane = trace_reduce.newest_xplane(prof_dir)
            trace = trace_reduce.reduce(xplane, cpu_stand_in=args.rehearse)
            if args.keep_trace:
                os.makedirs(os.path.dirname(args.keep_trace) or ".",
                            exist_ok=True)
                shutil.copy(xplane, args.keep_trace)
            log(f"trace: planes and lines "
                f"{[s for s in trace['seen'] if s[1]]}")
            solo_records, solo = solo_replay(
                jax, args, loadgen, cell, pool, url, rows,
                os.path.join(work, "profile_solo"))
        serve.stop_services(handles)
        handles = None

        # -- the reference, once the window has closed -------------------------
        t0 = time.perf_counter()
        records = window_records + solo_records
        want, controls = reference_answers(pool_exec, cell, args.seed, seg_rows,
                                           pool, tables, bool(args.control))
        verdict = judge(records, pool, want, sum_limit)
        log(f"reference: {len(pool)} answers over {rows} rows and the "
            f"comparison of {len(records)} served answers in "
            f"{time.perf_counter() - t0:.1f} s")
        numbers = dict(verdict["numbers"],
                       device_errors=counters.get("deviceErrors", 0),
                       timeouts=counters.get("timeouts", 0),
                       compiles_in_window=compiles)
        limits = dict({k: 0 for k in numbers}, sum_rel_gap_max=sum_limit)
        lat = [(r["done"] - r["sent"]) * 1000.0 for r in window_records]
        for r, ms in zip(window_records, lat):
            r["latency_ms"] = ms
        mean_ms = statistics.fmean(lat) if lat else None
        p50_ms = statistics.median(lat) if lat else None
        p95_ms = percentile(lat, 0.95) if lat else None
        in_window = [r for r in window_records if r["done"] <= args.seconds]
        correct = bool(in_window) and all(numbers[k] <= limits[k]
                                          for k in numbers)

        # -- the metrics -------------------------------------------------------
        if args.trace:
            ctx = {"records": [r for r in window_records if r["ok"]],
                   "templates": [p["template"] for p in pool],
                   "counters": counters, "trace": trace, "solo": solo,
                   "peaks": peaks}
            owed = cell["per_layer"]
            values = {m["name"]: cells.load_reader(m["name"])(ctx)
                      for m in owed}
        else:
            owed = cell["end_to_end"]
            values = {"qps": len(in_window) / args.seconds,
                      "mean_ms": mean_ms, "p50_ms": p50_ms,
                      "p95_ms": p95_ms, "setup_s": setup_s}
        log(f"window {args.seconds} s: sent {len(window_records)}, answered "
            f"inside {len(in_window)}, latency mean {mean_ms} ms p50 "
            f"{p50_ms} ms p95 {p95_ms} ms max "
            f"{max(lat) if lat else None} ms; answered in each 5 s "
            f"{answered_by_slice(window_records, args.seconds)}; pipeline "
            f"{counters}")
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": memory_peak}
        result = {"correct": correct, "attempted": len(records),
                  "failed": verdict["failed"],
                  "metrics": {m["name"]: {"value": values[m["name"]],
                                          "unit": m["unit"]}
                              for m in owed
                              if values.get(m["name"]) is not None},
                  "device": device}
        if trace is not None:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
        if controls:
            result["control"] = control_numbers(controls, records, pool, want,
                                                limits)
            for name, c in result["control"].items():
                log(f"control {name}: {c}")
        result["checked"] = {k: {"value": numbers[k], "limit": limits[k]}
                             for k in numbers}
        for note in verdict["notes"]:
            print(f"[{DEVICE}] failed: {note}", file=sys.stderr)
        for k in numbers:
            print(f"[{DEVICE}] checked {k} = {numbers[k]} (limit {limits[k]})",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        return 0
    finally:
        loadgen.stop()
        pool_exec.shutdown(wait=True, cancel_futures=True)
        if handles is not None:
            serve.stop_services(handles)
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
