"""benchmark/harness/program_trace.py and the ten per-layer metrics PR 26
added, on a fixture cut from that PR's own chip trace
(benchmark/fixtures/pr26-quarter-slice.*): the readers give the numbers
recorded with it, and None where the program wrote nothing for them to read
(a CPU trace with no TPU plane; PR 25's trace, taken before the program had
spans, scopes or the response fields). The four metrics of PR 28's four-chip
cell likewise, on a fixture cut from that PR's traced four-chip run
(benchmark/fixtures/pr28-mesh4-slice.*)."""

import json
import os

import pytest

from benchmark.harness import cells, program_trace, trace_reduce

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "fixtures")
SLICE = os.path.join(FIXTURES, "pr26-quarter-slice.xplane.pb")
PARENT = os.path.join(FIXTURES, "quarter-flights.xplane.pb")   # PR 25's
NEW_METRICS = (
    "pipeline.prepare_launch_ms", "pipeline.handoff_ms", "pipeline.decode_ms",
    "server.host_ms", "broker.scatter_overhead_ms",
    "pipeline.singleton_batch_share", "device.idle_host_busy_share",
    "device.idle_starved_share", "kernels.decode_share",
    "kernels.groupby_share")
MESH_SLICE = os.path.join(FIXTURES, "pr28-mesh4-slice.xplane.pb")
MESH_CELL = "ssb10-flat-mesh4.flights-c4"
MESH_METRICS = (
    "mesh.devices_busy", "kernels.collective_share",
    "mesh.scatter_launch_share", "mesh.collective_bytes_per_answer")
DECODE_METRIC = "kernels.compact_decode_share"      # PR 29, every cell
PRESORT_METRIC = "kernels.presort_compact_share"    # PR 33, every cell


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURES, "pr26-quarter-slice.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sliced():
    return program_trace.reduce(SLICE)


def _ctx(responses, counters, program):
    return {"records": [{"response": r, "latency_ms": 1.0, "pool": 0}
                        for r in responses],
            "templates": ["q"], "counters": counters, "trace": {}, "solo": [],
            "peaks": None, "program_trace": program}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_gives_the_number_recorded_with_the_fixture(
        name, recorded, sliced):
    ctx = _ctx(recorded["responses"], recorded["counters"], sliced)
    got = cells.load_reader(name)(ctx)
    assert got == pytest.approx(recorded["metrics"][name], rel=1e-9, abs=1e-9)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_none_on_the_parents_run(name):
    """PR 25's program: no `pinot.*` scope, no `pinot:*` span, none of the
    response fields, no `batchesOfOne` counter. Nothing raises; the harness
    leaves the metric out."""
    parent = program_trace.reduce(PARENT)
    assert parent is not None and parent["busy"] and not parent["spans"]
    responses = [{"timeUsedMs": 600.0, "queueWaitMs": 250.0,
                  "deviceFetchMs": 320.0,
                  "phaseTimesMs": {"compile": 2.0, "scatter": 595.0,
                                   "reduce": 1.0}}]
    ctx = _ctx(responses, {"batches": 10, "dispatched": 20}, parent)
    assert cells.load_reader(name)(ctx) is None


@pytest.mark.parametrize("name",
                         NEW_METRICS + MESH_METRICS
                         + (DECODE_METRIC, PRESORT_METRIC))
def test_every_new_metric_is_declared_like_the_old(name):
    """A `.json` with the keys of PR 25's and a `per_layer` entry that says
    the same; PR 26's have no `workloads` key (every cell owes them), PR 28's
    list the one four-chip cell (a mesh of one has nothing for them to read),
    PR 29's and PR 33's the four SSB cells since PR 36 (only there does a
    launch reach the sort regime: TPC-H Q1 has 9 key cells and Q6 none)."""
    meta = cells.read_json(cells.BENCH, "metrics", name + ".json")
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    if name in MESH_METRICS:
        assert entry[0]["workloads"] == [MESH_CELL]
        assert entry[0]["layer"] in {m["layer"] for m in bench["per_layer"]
                                     if "workloads" not in m}
    elif name in (DECODE_METRIC, PRESORT_METRIC):
        assert entry[0]["workloads"] == [
            w["name"] for w in bench["workloads"]
            if w["traffic"] == "flights-c4"]
    else:
        assert "workloads" not in entry[0]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[0][key] == meta[key], key
    assert meta["name"] == name and meta["what"]
    assert meta["moves"] in ("qps", "mean_ms")


@pytest.mark.parametrize("counters,want", [
    # a window's deltas of /health's device block, made by hand
    ({"compactDecodeLaunches": 152, "denseDecodeLaunches": 0}, 100.0),
    ({"compactDecodeLaunches": 3, "denseDecodeLaunches": 1}, 75.0),
    ({"compactDecodeLaunches": 0, "denseDecodeLaunches": 7}, 0.0),
    # no launch held the two decode branches: nothing to read, never 0
    ({"compactDecodeLaunches": 0, "denseDecodeLaunches": 0}, None),
    # the parent's counters (PR 28's recorded run has neither key)
    ({"batches": 10, "dispatched": 20, "meshLaunches": 0}, None),
])
def test_compact_decode_share_reads_the_counter_delta(counters, want,
                                                      mesh_recorded, recorded):
    read = cells.load_reader(DECODE_METRIC)
    got = read(_ctx([], counters, None))
    assert got == want if want is None else got == pytest.approx(want)
    for parent in (mesh_recorded["counters"], recorded["counters"]):
        assert "compactDecodeLaunches" not in parent
        assert read(_ctx([], parent, None)) is None


@pytest.mark.parametrize("counters,want", [
    ({"presortCompactLaunches": 152, "fullSortLaunches": 0}, 100.0),
    ({"presortCompactLaunches": 1, "fullSortLaunches": 3}, 25.0),
    ({"presortCompactLaunches": 0, "fullSortLaunches": 7}, 0.0),
    # no launch held the two sorts: nothing to read, never 0
    ({"presortCompactLaunches": 0, "fullSortLaunches": 0}, None),
    # PR 33's parent counts its decodes and no sort
    ({"compactDecodeLaunches": 152, "denseDecodeLaunches": 0}, None),
])
def test_presort_compact_share_reads_the_counter_delta(counters, want,
                                                       mesh_recorded, recorded):
    read = cells.load_reader(PRESORT_METRIC)
    got = read(_ctx([], counters, None))
    assert got == want if want is None else got == pytest.approx(want)
    for parent in (mesh_recorded["counters"], recorded["counters"]):
        assert "presortCompactLaunches" not in parent
        assert read(_ctx([], parent, None)) is None


MASKED_METRIC = "kernels.masked_groupby_share"      # PR 37, the lineitem cell


@pytest.mark.parametrize("counters,want", [
    # a window of Q1 (9 key cells: the masked reduce) and Q6 (no GROUP BY)
    ({"launches": 1144, "maskedGroupByLaunches": 572,
      "widenedAggLaunches": 572}, 50.0),
    ({"launches": 8, "maskedGroupByLaunches": 8}, 100.0),
    # Q1 back on the one-hot matmul: 0, not None
    ({"launches": 8, "maskedGroupByLaunches": 0}, 0.0),
    # nothing launched: nothing to read
    ({"launches": 0, "maskedGroupByLaunches": 0}, None),
    # PR 37's parent counts its widened launches and no masked one
    ({"launches": 1144, "widenedAggLaunches": 572}, None),
])
def test_masked_groupby_share_reads_the_counter_delta(counters, want,
                                                      mesh_recorded, recorded):
    read = cells.load_reader(MASKED_METRIC)
    got = read(_ctx([], counters, None))
    assert got == want if want is None else got == pytest.approx(want)
    for parent in (mesh_recorded["counters"], recorded["counters"]):
        assert "maskedGroupByLaunches" not in parent
        assert read(_ctx([], parent, None)) is None


@pytest.fixture(scope="module")
def mesh_recorded():
    with open(os.path.join(FIXTURES, "pr28-mesh4-slice.json")) as f:
        return json.load(f)


def _put_profile(root, src, cell="a-cell", kind="profile"):
    """`src` where run.py leaves a profile: `trace_slice`'s under `profile`,
    the solo replay's under `profile_solo`."""
    import shutil
    d = root / ".bench_work" / cell / kind / "plugins" / "profile" \
        / "2026_09_30"
    d.mkdir(parents=True)
    shutil.copy(src, d / "host.xplane.pb")
    return d / "host.xplane.pb"


@pytest.mark.parametrize("name", MESH_METRICS)
def test_mesh_reader_gives_the_number_recorded_with_the_fixture(
        name, mesh_recorded, tmp_path, monkeypatch):
    """Through the file, as in a run: `ctx` has the harness's reduction and
    the counters, and the readers find the slice under `.bench_work`."""
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    _put_profile(tmp_path, MESH_SLICE)
    ctx = _ctx([{"timeUsedMs": 1.0}] * mesh_recorded["answers"],
               mesh_recorded["counters"], None)
    del ctx["program_trace"]
    ctx["trace"] = trace_reduce.reduce(MESH_SLICE)
    got = cells.load_reader(name)(ctx)
    assert got == pytest.approx(mesh_recorded["metrics"][name], rel=1e-9)


@pytest.mark.parametrize("name", MESH_METRICS)
def test_mesh_reader_returns_none_on_the_parents_run(name, tmp_path,
                                                     monkeypatch):
    """PR 25's program on one chip: no scope, no mesh counter, and a `ctx`
    whose trace says nothing of devices. Nothing raises."""
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    _put_profile(tmp_path, PARENT)
    ctx = _ctx([{"timeUsedMs": 600.0}], {"batches": 10, "dispatched": 20},
               None)
    del ctx["program_trace"]
    ctx["trace"] = {"busy_s": 1.0}
    assert cells.load_reader(name)(ctx) is None
    assert cells.load_reader(name)(dict(ctx, trace=None)) is None


def test_mesh_slice_holds_four_devices_and_the_collectives(mesh_recorded):
    """What the four-chip run wrote: four device planes with operations, the
    launch and fetch spans saying `devices` 4, the `psum`s under
    `pinot.collective.sum`, and the `psum_scatter`s as the v5e compiled them:
    an `all-reduce` with no `tf_op`, known by its opcode alone."""
    by_jax = trace_reduce.reduce(MESH_SLICE)
    assert by_jax["devices"] == mesh_recorded["devices"] == 4
    assert by_jax["busy_s"] == pytest.approx(mesh_recorded["busy_s"])
    t = program_trace.reduce(MESH_SLICE)
    assert program_trace.length(t["busy"]) / 1e9 == pytest.approx(
        mesh_recorded["first_device_busy_s"])
    assert t["modules"] == mesh_recorded["modules"]
    scopes = {}
    for _, _, scope in t["ops"]:
        if scope.startswith("pinot.collective"):
            scopes[scope] = scopes.get(scope, 0) + 1
    assert scopes == mesh_recorded["collective_scopes"]
    assert "pinot.collective.sum" in scopes
    for name in ("pinot:pipeline.launch", "pinot:pipeline.fetch"):
        assert {stats["devices"] for _, _, stats, _ in t["spans"][name]} \
            == set(mesh_recorded["span_devices"]) == {4}
    share = cells.load_py(os.path.join(
        cells.BENCH, "metrics", "kernels.collective_share.py"))
    ops = share.opcode_intervals(MESH_SLICE, t["lo"], t["hi"])
    assert len(ops) == mesh_recorded["collective_opcode_ops"]
    device0 = [p for p in program_trace.load(MESH_SLICE)
               if p["name"] == "/device:TPU:0"][0]
    unnamed = [name for ln in device0["lines"] if ln["name"] == "XLA Ops"
               for name, _, _, stats in ln["events"]
               if share.COLLECTIVE_OPCODE.search(name)
               and not stats.get("tf_op")]
    assert unnamed and all(" all-reduce(" in name for name in unnamed)
    # by scope alone the share would miss them
    by_scope = program_trace.scope_share(
        {"program_trace": t}, "pinot.collective")
    assert 0 < by_scope < mesh_recorded["metrics"]["kernels.collective_share"]


def test_walker_agrees_with_profile_data_on_the_device(recorded, sliced):
    """Two readers of one file: the wire-format walker's slice and busy time
    are trace_reduce's (jax.profiler.ProfileData), to the nanosecond's
    rounding."""
    by_jax = trace_reduce.reduce(SLICE)
    assert (sliced["hi"] - sliced["lo"]) / 1e9 == pytest.approx(
        by_jax["window_s"], abs=1e-6)
    busy_s = program_trace.length(sliced["busy"]) / 1e9
    assert busy_s == pytest.approx(by_jax["busy_s"], abs=1e-5)
    assert busy_s == pytest.approx(recorded["busy_s"], abs=1e-9)
    assert by_jax["idle_share"] == pytest.approx(recorded["idle_share"])


def test_slice_holds_what_the_program_named(recorded, sliced):
    assert sliced["modules"] == recorded["modules"]
    assert all(name.startswith("jit_pinot_") for name in sliced["modules"])
    assert {k: len(v) for k, v in sliced["spans"].items()} == \
        recorded["span_counts"]
    scoped = trace_reduce.union([(s, e) for s, e, scope in sliced["ops"]
                                 if scope])
    share = 100 * program_trace.length(scoped) \
        / program_trace.length(sliced["busy"])
    assert share == pytest.approx(recorded["scoped_share"])
    assert share >= 95.0
    # a request's spans carry its trace id; the pipeline's own carry sizes
    for name in ("pinot:broker.scatter", "pinot:server.execute",
                 "pinot:pipeline.prepare"):
        assert all(len(stats.get("trace_id", "")) == 16
                   for _, _, stats, _ in sliced["spans"][name]), name
    for _, _, stats, _ in sliced["spans"]["pinot:pipeline.fetch"]:
        assert stats["batch"] >= 1 and stats["launches"] >= 1
    # dispatcher and fetcher are two threads: two lines of the host plane
    lines = {name: {line for *_, line in sliced["spans"][name]}
             for name in ("pinot:pipeline.launch", "pinot:pipeline.fetch")}
    assert all(len(v) == 1 for v in lines.values())


def test_family_time_is_a_union_not_a_sum(sliced):
    """A `while` and the fusions inside it are both events of the "XLA Ops"
    line: summed, the operations pass the busy time; united they are it, and
    no family can pass it."""
    ops = [(s, e) for s, e, _ in sliced["ops"]]
    busy = program_trace.length(sliced["busy"])
    assert sum(e - s for s, e in ops) > 1.2 * busy
    assert program_trace.length(trace_reduce.union(ops)) == \
        pytest.approx(busy)
    groupby = [(s, e) for s, e, scope in sliced["ops"]
               if scope.startswith("pinot.groupby.")]
    assert 0 < program_trace.length(trace_reduce.union(groupby)) <= busy


def test_scope_of_takes_the_outermost_pinot_scope():
    scope_of = program_trace.scope_of
    assert scope_of("jit(pinot_groupby_fused)/jit(shmap_body)/pinot.decode/"
                    "jit(take_along_axis)/gather:") == "pinot.decode"
    assert scope_of("jit(pinot_groupby)/pinot.groupby.partitioned/"
                    "pinot.groupby.partitioned.sort/sort:") == \
        "pinot.groupby.partitioned"
    assert scope_of("jit(pinot_distinct)/pinot.distinct/"
                    "pinot.groupby.sorted.sort/sort") == "pinot.distinct"
    assert scope_of("jit(shard_body)/jit(take_along_axis)/gather:") == ""
    assert scope_of(None) == "" and scope_of("reduce_window_sum:") == ""


def _program(busy, spans, lo=0.0, hi=100.0, ops=None):
    return {"lo": lo, "hi": hi, "busy": busy,
            "ops": ops if ops is not None
            else [(s, e, "pinot.agg") for s, e in busy],
            "modules": {}, "spans": {k: [(s, e, {}, "t") for s, e in v]
                                     for k, v in spans.items()}}


def test_idle_shares_on_a_slice_made_by_hand():
    """100 ns: the device idle in [10, 30) and [60, 100). prepare open in
    [5, 15) and decode in [25, 28): 8 idle ns of host work. wait open in
    [20, 30) and [60, 90), a fetch open in [70, 80): 30 ns starved."""
    program = _program(
        busy=[[0.0, 10.0], [30.0, 60.0]],
        spans={"pinot:pipeline.prepare": [(5.0, 15.0)],
               "pinot:pipeline.decode": [(25.0, 28.0)],
               "pinot:pipeline.wait": [(20.0, 30.0), (60.0, 90.0)],
               "pinot:pipeline.fetch": [(70.0, 80.0)]})
    ctx = _ctx([], {}, program)
    assert cells.load_reader("device.idle_host_busy_share")(ctx) == \
        pytest.approx(8.0)
    assert cells.load_reader("device.idle_starved_share")(ctx) == \
        pytest.approx(30.0)
    # a program that has scopes but none of a family reads a true zero
    assert cells.load_reader("kernels.decode_share")(ctx) == 0.0
    assert cells.load_reader("kernels.groupby_share")(ctx) == 0.0


def test_interval_helpers():
    a, b = [[0, 10], [20, 30]], [[5, 25]]
    assert program_trace.intersect(a, b) == [[5, 10], [20, 25]]
    assert program_trace.complement(a, 0, 40) == [[10, 20], [30, 40]]
    assert program_trace.complement([], 0, 5) == [[0, 5]]
    assert program_trace.length(a) == 20


def test_no_device_plane_means_nothing_to_read(tmp_path):
    """A CPU rehearsal's trace has host planes only: `reduce` gives None and
    so does every reader that needs the device."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.newest_xplane(str(tmp_path))
    assert any(p["name"].startswith("/host:CPU")
               for p in program_trace.load(path))
    assert program_trace.reduce(path) is None
    ctx = _ctx([], {}, None)
    for name in ("device.idle_host_busy_share", "device.idle_starved_share",
                 "kernels.decode_share", "kernels.groupby_share"):
        assert cells.load_reader(name)(ctx) is None


def test_slice_of_finds_the_runs_profile(tmp_path, monkeypatch):
    """`ctx` has no path: the slice is the newest .xplane.pb under a
    `.bench_work/<cell>/profile`; the solo replay's `profile_solo` and what
    an ended run left behind do not count; one run parses the file once."""
    import time
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))

    def fresh():
        return {"records": [], "counters": {}, "trace": {"busy_s": 1.0}}

    assert program_trace.slice_of(fresh()) is None      # no directory at all
    stale = _put_profile(tmp_path, PARENT, "an-ended-cell")
    os.utime(stale, (time.time() - 3600, time.time() - 3600))
    _put_profile(tmp_path, SLICE)
    _put_profile(tmp_path, PARENT, kind="profile_solo")  # newer, not the slice
    ctx = fresh()
    got = program_trace.slice_of(ctx)
    assert got is not None and got["spans"] and got["modules"]
    assert program_trace.slice_of(ctx) is got            # kept on ctx
    untraced = dict(fresh(), trace=None)
    assert program_trace.slice_of(untraced) is None


# -- PR 38: the idle split into classes that add up; the hop's fields ---------

PR38_IDLE = ("device.idle_in_program_share", "device.idle_gc_share",
             "device.idle_fetch_share", "device.idle_request_share",
             "device.idle_unattributed_share")
PR38_FIELDS = ("broker.wire_ms", "server.wake_ms", "server.acquire_merge_ms",
               "pipeline.prepare_cpu_share")
PR38_METRICS = PR38_IDLE + PR38_FIELDS


def _idle_ctx(spans, modules, busy):
    ctx = _ctx([], {}, _program(busy=busy, spans=spans))
    ctx["program_modules"] = modules
    return ctx


def _read_all(ctx, names):
    return {n: cells.load_reader(n)(ctx) for n in names}


def test_idle_classes_partition_a_slice_made_by_hand():
    """100 ns, the device idle in [10, 30), [40, 80), [85, 100): 75 ns.
    [10, 30): prepare [10, 14) and a decode [26, 28) are host work (7 with
    the prepare at [42, 43) below), wait without a fetch [14, 18) starves
    (4), the fetch and the gather hold [18, 30) but for the decode (10).
    [40, 80): a module runs to 45 (4: the prepare inside it wins), a full
    collection [45, 50) (5), a hand-off [50, 52) (fetch: 12 in all), then
    only the request's spans (28). [85, 100): the request to 95 (38 in all),
    nothing after (5). The seven add up to device.idle_share exactly."""
    ctx = _idle_ctx(
        spans={"pinot:pipeline.prepare": [(10.0, 14.0), (42.0, 43.0)],
               "pinot:pipeline.decode": [(26.0, 28.0)],
               "pinot:pipeline.wait": [(14.0, 20.0)],
               "pinot:pipeline.fetch": [(18.0, 24.0)],
               "pinot:pipeline.gather": [(24.0, 30.0)],
               "pinot:pipeline.handoff": [(50.0, 52.0)],
               "pinot:gc": [(45.0, 50.0)],
               "pinot:http.query": [(52.0, 95.0)],
               "pinot:server.execute": [(60.0, 70.0)]},
        modules=[[30.0, 45.0]],
        busy=[[0.0, 10.0], [30.0, 40.0], [80.0, 85.0]])
    got = _read_all(ctx, ("device.idle_host_busy_share",
                          "device.idle_starved_share") + PR38_IDLE)
    assert got == pytest.approx({
        "device.idle_host_busy_share": 7.0, "device.idle_starved_share": 4.0,
        "device.idle_in_program_share": 4.0, "device.idle_gc_share": 5.0,
        "device.idle_fetch_share": 12.0, "device.idle_request_share": 38.0,
        "device.idle_unattributed_share": 5.0})
    t = ctx["program_trace"]
    idle = 100.0 * program_trace.length(program_trace.complement(
        t["busy"], t["lo"], t["hi"])) / (t["hi"] - t["lo"])
    assert sum(got.values()) == pytest.approx(idle, abs=1e-12) == 75.0


def test_idle_classes_leave_out_what_the_two_older_ones_share():
    """A decode on the fetcher while the dispatcher waits (no fetch open)
    counts in both older classes; the five newer ones count it in neither,
    and the partition says how much the two share."""
    from benchmark.harness import idle_classes
    ctx = _idle_ctx(
        spans={"pinot:pipeline.wait": [(10.0, 20.0)],
               "pinot:pipeline.decode": [(15.0, 25.0)],
               "pinot:http.query": [(0.0, 100.0)]},
        modules=[], busy=[[0.0, 10.0], [50.0, 100.0]])
    got = _read_all(ctx, ("device.idle_host_busy_share",
                          "device.idle_starved_share") + PR38_IDLE)
    assert got["device.idle_host_busy_share"] == pytest.approx(10.0)
    assert got["device.idle_starved_share"] == pytest.approx(10.0)
    assert got["device.idle_request_share"] == pytest.approx(25.0)
    assert idle_classes.partition(ctx)["overlap"] == pytest.approx(5.0)
    assert sum(got.values()) == pytest.approx(40.0 + 5.0)


@pytest.mark.parametrize("name", PR38_METRICS)
def test_pr38_reader_returns_none_on_its_parents_run(name, recorded, sliced):
    """PR 26's chip slice has the pipeline's spans and none that PR 38 added;
    its answers have none of PR 38's fields. Nothing raises."""
    ctx = _ctx(recorded["responses"], recorded["counters"], sliced)
    ctx["program_modules"] = []
    assert cells.load_reader(name)(ctx) is None
    assert cells.load_reader(name)(_ctx([], {}, None)) is None


def test_pr38_field_readers_on_answers_made_by_hand():
    responses = [
        {"scatterSerializeMs": 0.1, "scatterDeserializeMs": 0.2,
         "serverDecodeMs": 0.3, "deviceWakeMs": 0.5, "serverAcquireMs": 0.4,
         "serverMergeMs": 0.6, "devicePrepareCpuMs": 2.0,
         "deviceLaunchCpuMs": 1.0, "devicePrepareMs": 4.0,
         "deviceLaunchMs": 2.0},
        {"scatterSerializeMs": 0.3, "scatterDeserializeMs": 0.4,
         "serverDecodeMs": 0.5, "deviceWakeMs": 1.5, "serverAcquireMs": 1.4,
         "serverMergeMs": 0.2, "devicePrepareCpuMs": 3.0,
         "deviceLaunchCpuMs": 0.0, "devicePrepareMs": 6.0,
         "deviceLaunchMs": 8.0}]
    got = _read_all(_ctx(responses, {}, None), PR38_FIELDS)
    assert got == pytest.approx({
        "broker.wire_ms": 0.9, "server.wake_ms": 1.0,
        "server.acquire_merge_ms": 1.3,
        "pipeline.prepare_cpu_share": 100.0 * 6.0 / 20.0})


def test_module_intervals_cover_the_devices_operations(recorded, sliced):
    """The "XLA Modules" line of the chip slice, walked alone: every
    operation of the first device runs inside a module's execution."""
    from benchmark.harness import idle_classes
    modules = idle_classes.module_intervals(SLICE, sliced["lo"], sliced["hi"])
    busy = sliced["busy"]
    assert modules and sum(sliced["modules"].values()) >= len(modules)
    inside = program_trace.intersect(busy, [list(m) for m in modules])
    assert program_trace.length(inside) == pytest.approx(
        program_trace.length(busy))


@pytest.mark.parametrize("name", PR38_METRICS)
def test_pr38_metric_is_declared_like_the_old(name):
    """Nine `per_layer` entries with no `workloads` key: every cell owes
    them; the `.json` beside each reader says the same."""
    meta = cells.read_json(cells.BENCH, "metrics", name + ".json")
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert "workloads" not in entry
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == meta[key], key
    assert meta["name"] == name and meta["what"]
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] not in PR38_METRICS}


def test_idle_map_counts_each_threads_innermost_span_once():
    """benchmark/idle_map.py on a slice made by hand: the device idle in
    [0, 30) of 100 ns. The handler's http.query holds scatter, which holds a
    serialize; the dispatcher's prepare holds its plan and inputs. Each
    instant of a thread goes to the span opened last there."""
    from benchmark import idle_map
    rows = {"pinot:http.query": [(0.0, 50.0, "h")],
            "pinot:broker.scatter": [(10.0, 40.0, "h")],
            "pinot:broker.serialize": [(12.0, 14.0, "h")],
            "pinot:pipeline.prepare": [(20.0, 30.0, "d")],
            "pinot:prepare.plan": [(20.0, 24.0, "d")],
            "pinot:prepare.inputs": [(24.0, 29.0, "d")],
            "pinot:pipeline.launch": [(29.5, 30.0, "d")]}
    t = {"lo": 0.0, "hi": 100.0, "busy": [[30.0, 100.0]], "ops": [],
         "modules": {}, "spans": {k: [(s, e, {}, line) for s, e, line in v]
                                  for k, v in rows.items()}}
    got = idle_map.idle_map(t, [])
    assert dict(got["innermost_by_idle"]) == pytest.approx({
        "pinot:http.query": 10.0, "pinot:broker.scatter": 18.0,
        "pinot:broker.serialize": 2.0, "pinot:prepare.plan": 4.0,
        "pinot:prepare.inputs": 5.0, "pinot:pipeline.prepare": 0.5,
        "pinot:pipeline.launch": 0.5})
    assert got["prepare_covered"] == pytest.approx(90.0)
    assert got["launch_covered"] == 0.0
    assert got["idle_share"] == 30.0 and got["host_busy"] == 10.0
    assert got["seven_minus_idle"] == pytest.approx(0.0)


# -- queries prepared while the batch before was fetched ----------------------

AHEAD_METRIC = "pipeline.prepared_ahead_share"


@pytest.mark.parametrize("counters,want", [
    # a window's deltas of /health's device block, made by hand
    ({"dispatched": 200, "batches": 100, "preparedAhead": 190}, 95.0),
    ({"dispatched": 8, "batches": 8, "preparedAhead": 0}, 0.0),
    # nothing dispatched: nothing to read
    ({"dispatched": 0, "batches": 0, "preparedAhead": 0}, None),
    # the parent's counters: no such key
    ({"dispatched": 200, "batches": 100, "batchesOfOne": 0}, None),
])
def test_prepared_ahead_share_reads_the_counter_delta(counters, want,
                                                      mesh_recorded, recorded):
    read = cells.load_reader(AHEAD_METRIC)
    got = read(_ctx([], counters, None))
    assert got == want if want is None else got == pytest.approx(want)
    for parent in (mesh_recorded["counters"], recorded["counters"]):
        assert "preparedAhead" not in parent
        assert read(_ctx([], parent, None)) is None


def test_prepared_ahead_share_is_declared_like_the_old():
    """A `per_layer` entry with no `workloads` key (every cell runs the
    pipeline) in the form of `pipeline.singleton_batch_share`."""
    meta = cells.read_json(cells.BENCH, "metrics", AHEAD_METRIC + ".json")
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == AHEAD_METRIC]
    (like,) = [m for m in bench["per_layer"]
               if m["name"] == "pipeline.singleton_batch_share"]
    assert "workloads" not in entry
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == meta[key], key
    for key in ("unit", "source", "layer", "moves"):
        assert entry[key] == like[key], key
    assert meta["name"] == AHEAD_METRIC and meta["what"]


# A GROUP BY past the dense key space runs under `pinot.groupby.sparse`,
# and the ORDER BY ... LIMIT cut on the device under `pinot.trim` inside it
@pytest.mark.parametrize("tf_op", [
    "jit(pinot_groupby)/jit(shmap_body)/pinot.groupby.key/add:",
    "jit(pinot_groupby)/jit(shmap_body)/pinot.groupby.sparse/"
    "pinot.groupby.sparse.presort/reduce_sum:",
    "jit(pinot_groupby)/jit(shmap_body)/pinot.groupby.sparse/cond/"
    "branch_1_fun/pinot.groupby.sparse.sort/sort:",
    "jit(pinot_groupby)/jit(shmap_body)/pinot.groupby.sparse/"
    "pinot.groupby.sparse.groups/reduce_min:",
    "jit(pinot_groupby)/jit(shmap_body)/pinot.groupby.sparse/pinot.trim/"
    "while/body/reduce_sum:",
])
def test_sparse_and_trim_scopes_count_as_groupby(tf_op):
    """The outermost scope of each of the regime's operations is a
    `pinot.groupby.*` one, so `kernels.groupby_share` (their union over the
    device's busy time) counts the sorted groups and the cut with no edit."""
    scope = program_trace.scope_of(tf_op)
    assert scope.startswith("pinot.groupby.")
    read = cells.load_reader("kernels.groupby_share")
    program = _program([(0.0, 40.0)], {},
                       ops=[(0.0, 30.0, scope), (30.0, 40.0, "pinot.agg")])
    assert read(_ctx([], {}, program)) == pytest.approx(75.0)


@pytest.mark.parametrize("counters,want", [
    ({"launches": 600, "sparseGroupByLaunches": 600,
      "deviceTrimmedLaunches": 600}, 100.0),
    ({"launches": 8, "sparseGroupByLaunches": 2}, 25.0),
    ({"launches": 8, "sparseGroupByLaunches": 0}, 0.0),
    ({"launches": 0, "sparseGroupByLaunches": 0}, None),
    # a program without the regime counts no such launch
    ({"launches": 600, "maskedGroupByLaunches": 0}, None),
])
def test_sparse_groupby_share_reads_the_counter_delta(counters, want,
                                                      mesh_recorded, recorded):
    read = cells.load_reader("kernels.sparse_groupby_share")
    got = read(_ctx([], counters, None))
    assert got == want if want is None else got == pytest.approx(want)
    for parent in (mesh_recorded["counters"], recorded["counters"]):
        assert "sparseGroupByLaunches" not in parent
        assert read(_ctx([], parent, None)) is None
