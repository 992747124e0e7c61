"""Ask the TPU compiler before the chip: the served path's kernels, compiled
for a DESCRIBED v5e:2x2 at chip_smoke.py's real widths (no chip attached,
nothing runs — a compile that passes is not a chip run).

The plans come from the real planner over small segments drawn from
chip_smoke's own generator (same schema, distributions and key spaces, so the
dictionary widths and regime choices are the real ones); only the stacked
block's [segments, rows] extent is replaced by the smoke's, as abstract
`ShapeDtypeStruct`s placed on the described devices.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and every xdist worker imports this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import chip_smoke
from pinot_tpu.engine import kernels
from pinot_tpu.parallel import combine
from pinot_tpu.parallel.combine import MeshQueryExecutor
from pinot_tpu.parallel.mesh import SEGMENT_AXIS, default_mesh
from pinot_tpu.query.context import compile_query
from pinot_tpu.segment import load_segment
from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig

SMOKE_SEGS = chip_smoke.DEFAULT_ROWS // chip_smoke.SEGMENT_ROWS   # 16
SEG_ROWS = chip_smoke.SEGMENT_ROWS                                # 4Mi
#: the largest stacked block (16Mi rows) the matmul regimes run as ONE slab;
#: past it they go slab by slab inside a loop (`kernels._slab_sums`)
MATMUL_SEGS = kernels.SLAB_ROWS // SEG_ROWS
FIXTURE_ROWS = 1 << 20          # 500k keys stay dictionary-encoded (<= 0.7 x rows)
SQL = dict(chip_smoke.QUERIES)
#: inputs replicated over the mesh (everything else carries the segment axis)
_REPLICATED = ("luts", "iscal", "fscal", "strides", "route_start")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def segments(tmp_path_factory):
    """Two small segments from the smoke's generator at its real key spaces."""
    out = tmp_path_factory.mktemp("chipcompile")
    names = np.array(chip_smoke.REGIONS, dtype=object)
    builder = SegmentBuilder(chip_smoke.lineorder_schema(),
                             SegmentGeneratorConfig(
                                 inverted_index_columns=["lo_region"]))
    segs = []
    for i in range(2):
        cols = chip_smoke.segment_columns(0, i, FIXTURE_ROWS,
                                          chip_smoke.SUPPKEYS,
                                          chip_smoke.CUSTKEYS)
        segs.append(load_segment(builder.build(
            dict(cols, lo_region=names[cols["lo_region"]]), str(out),
            f"lineorder_{i}")))
    return segs


@pytest.fixture(scope="module")
def cpu_exec():
    """Plans and stages the small segments on one CPU device."""
    return MeshQueryExecutor(default_mesh(1))


def _mesh(topo, n):
    return Mesh(np.array(topo.devices[:n]), (SEGMENT_AXIS,),
                axis_types=(jax.sharding.AxisType.Auto,))


def _abstract(inputs, small, real, mesh):
    """The prepared inputs' tree as ShapeDtypeStructs on `mesh`, the stacked
    block extent `small` = (s_pad, rows) replaced by `real`."""
    (s0, r0), (s1, r1) = small, real
    out = {}
    for key, val in inputs.items():
        sharded = key not in _REPLICATED

        def leaf(x, sharded=sharded):
            shape = tuple(x.shape)
            if sharded:
                assert shape[0] == s0, (key, shape)
                shape = (s1,) + shape[1:]
                if len(shape) > 1 and shape[1] == r0:
                    shape = (s1, r1) + shape[2:]
            return jax.ShapeDtypeStruct(
                shape, x.dtype,
                sharding=NamedSharding(mesh, P(SEGMENT_AXIS) if sharded
                                       else P()))
        out[key] = jax.tree_util.tree_map(leaf, val)
    return out


def _prepare(cpu_exec, segments, name):
    ctx = compile_query(SQL[name], segments[0].schema)
    p = cpu_exec.prepare_partial(ctx, segments)
    assert p is not None, f"{name}: the plan is not device-eligible"
    return p


def _real_spec(spec):
    return kernels.KernelSpec(spec.filter, spec.group_cols, spec.num_keys_pad,
                              spec.aggs, spec.distinct_lut_sizes, SEG_ROWS,
                              mv_cols=spec.mv_cols,
                              bitmap_leaves=spec.bitmap_leaves,
                              fused_cols=spec.fused_cols,
                              int_ranges=spec.int_ranges)


def _compile_agg(topo, cpu_exec, segments, name, segs, n_devices=1):
    """Compile the served shard kernel of QUERIES[name] at [segs, SEG_ROWS]
    on `n_devices` described chips; returns (prepared, compiled)."""
    p = _prepare(cpu_exec, segments, name)
    mesh = _mesh(topo, n_devices)
    ax = _abstract(p.inputs, (p.s_pad, p.rows), (segs, SEG_ROWS), mesh)
    chip_exec = MeshQueryExecutor(mesh)
    fn = chip_exec._build_shard_kernel(_real_spec(p.spec))
    return p, fn.jitted_for(ax).lower(ax).compile()


def _fits(compiled, hbm_bytes=16e9):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < hbm_bytes, f"program needs {total / 1e9:.1f} GB"


# (smoke query, stacked segments, whether it is the sort regime's program)
ONEHOT = "group-by region x quantity"     # 256 padded keys: SSB Q4.1's shape
AGG_CASES = [
    pytest.param("q1.1 filter+sum", SMOKE_SEGS, False, id="q1.1-fused-scan"),
    # 5 keys: the masked VPU reduce (PR 37), which builds no loop at any size
    pytest.param("group-by region", MATMUL_SEGS, False, id="lowcard-masked"),
    pytest.param("group-by region", SMOKE_SEGS, False,
                 id="lowcard-masked-64Mi-rows"),
    pytest.param(ONEHOT, MATMUL_SEGS, False, id="lowcard-onehot"),
    pytest.param("group-by 20k keys", MATMUL_SEGS, False, id="20k-chunk64"),
    # the one-chip smoke's 64Mi rows: the same two regimes over four slabs
    # (PR 31; a few seconds of compile each, where the sort took 22-37)
    pytest.param(ONEHOT, SMOKE_SEGS, False, id="lowcard-onehot-4-slabs"),
    pytest.param("group-by 20k keys", SMOKE_SEGS, False,
                 id="20k-chunk64-4-slabs"),
    # past `chunk_cap` keys the sort regime, at any row count (~45 s of
    # compile, on the chip too)
    pytest.param("group-by 500k keys", SMOKE_SEGS, True,
                 id="500k-partitioned"),
    pytest.param("bitmap-filter count", SMOKE_SEGS, False, id="lut-count"),
    pytest.param("distinctcounthll", SMOKE_SEGS, False, id="hll-presence"),
]


@pytest.mark.parametrize("name,segs,sort_regime", AGG_CASES)
def test_served_agg_kernel_compiles_for_v5e(topo, cpu_exec, segments, name,
                                            segs, sort_regime):
    p, compiled = _compile_agg(topo, cpu_exec, segments, name, segs)
    _fits(compiled)
    if name == "q1.1 filter+sum":
        # the served scan decodes its dict columns in-register
        assert p.spec.fused_cols, "q1.1 no longer rides the fused decode"
    if "group-by" in name and not sort_regime:
        # one loop over the slabs where a matmul regime has more than one
        # (the masked reduce has none and no contraction at all); no
        # contraction over more than a slab's rows, and no sort
        text = compiled.as_text()
        rung = kernels.masked(p.spec)
        assert rung == (name == "group-by region")
        assert ("pinot.groupby.masked" in text) == rung
        assert ("pinot.groupby.onehot" in text) == (name == ONEHOT)
        assert kernels.slabbed(_real_spec(p.spec), segs * SEG_ROWS) == (
            segs > MATMUL_SEGS and not rung)
        assert (" while(" in text) == (segs > MATMUL_SEGS and not rung)
        assert (" convolution(" in text) == (not rung)
        assert " sort(" not in text
        assert f"[{SMOKE_SEGS * SEG_ROWS}]" not in "".join(
            ln for ln in text.splitlines() if " convolution(" in ln)
    if sort_regime:
        # 500k keys: one conditional at the top holds the sorts (PR 33): the
        # rows that passed, compacted tile by tile (n / 64 rows, or n / 16,
        # by one more inside), or all of them, whose branch holds both
        # decodes of the sorted rows (PR 29)
        text = compiled.as_text()
        entry = text[text.index("ENTRY"):]
        assert entry.count(" conditional(") == 1 and " sort(" not in entry
        for scope in ("presort", "sort", "compact", "dense"):
            assert f"pinot.groupby.partitioned.{scope}/" in text, scope
        rows = segs * SEG_ROWS
        sorts = [ln for ln in text.splitlines() if " sort(" in ln]
        assert all(any(f"[{n}]" in ln for ln in sorts)
                   for n in (rows, rows // 64, rows // 16))


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    """Two small segments of the benchmark's `tpch10-lineitem` table, built as
    a run builds them (its generator, schema and raw price column)."""
    from benchmark.harness import build, cells
    config = cells.read_json(cells.BENCH, "configs", "tpch10-lineitem.json")
    out = tmp_path_factory.mktemp("chipcompile_tpch")
    return [load_segment(build.build_segment(
        {"config": config, "seed": 36, "index": i, "rows": 1 << 16,
         "out_dir": str(out)})["seg_dir"]) for i in range(2)]


@pytest.mark.parametrize("template,literals", [
    ("q1", {"d": 19980902}),
    ("q6", {"lo": 19940101, "hi": 19950101, "dlo": 5, "dhi": 7, "q": 24}),
])
def test_tpch_program_compiles_for_v5e_at_67m_rows(topo, cpu_exec, lineitem,
                                                   template, literals):
    """TPC-H Q1 and Q6 as the `tpch10-lineitem.tpch-q1q6-c4` cell serves
    them, at its [16, 4Mi] rows on one described chip (PR 36). Q1: seven
    value rows and a count over 9 key cells as the masked VPU reduce (PR 37:
    no loop, no contraction, no slabs), its charge widened to float32; Q6 is
    the fused dictionary scan, its product the int32 it fits."""
    from benchmark.harness import cells
    sql = cells.read_json(cells.BENCH, "queries", "tpch",
                          template + ".json")["sql"].format(**literals)
    p = cpu_exec.prepare_partial(compile_query(sql, lineitem[0].schema),
                                 lineitem)
    assert p is not None, f"{template}: the plan is not device-eligible"
    mesh = _mesh(topo, 1)
    ax = _abstract(p.inputs, (p.s_pad, p.rows), (SMOKE_SEGS, SEG_ROWS), mesh)
    spec = _real_spec(p.spec)
    fn = MeshQueryExecutor(mesh)._build_shard_kernel(spec)
    compiled = fn.jitted_for(ax).lower(ax).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert p.spec.fused_cols, "the served scan decodes in-register"
    q1 = template == "q1"
    assert kernels.widened(spec) == q1 and kernels.masked(spec) == q1
    assert not kernels.slabbed(spec, SMOKE_SEGS * SEG_ROWS)
    assert ("pinot.groupby.masked" in text) == q1
    assert "pinot.groupby.onehot" not in text
    for op in (" while(", " convolution(", " sort("):
        assert op not in text, op


# (smoke query, the window's slots of the 16 resident, sort regime)
WINDOW_CASES = [
    pytest.param("group-by 500k keys", 8, True, id="500k-partitioned-window8"),
    pytest.param("group-by 20k keys", 8, False, id="20k-chunk64-window8"),
    pytest.param("q1.1 filter+sum", 2, False, id="q1.1-window2"),
]


@pytest.mark.parametrize("name,window,sort_regime", WINDOW_CASES)
def test_routed_window_program_compiles_for_v5e(topo, cpu_exec, segments,
                                                name, window, sort_regime):
    """A query routed to some of the 16 resident segments (PR 32): the program
    reads `window` slots of the 67M resident rows from a runtime start. The
    sort runs over the window's rows, the slab loop over the window's slabs,
    and the slice of the slot axis is no pass of its own."""
    ctx = compile_query(SQL[name], segments[0].schema)
    p = cpu_exec.prepare_partial(ctx, segments[:1], segments)
    assert p is not None and p.window == 1 and "route" in p.inputs
    mesh = _mesh(topo, 1)
    ax = _abstract(p.inputs, (p.s_pad, p.rows), (SMOKE_SEGS, SEG_ROWS), mesh)
    fn = MeshQueryExecutor(mesh)._build_shard_kernel(_real_spec(p.spec),
                                                     window=window)
    compiled = fn.jitted_for(ax).lower(ax).compile()
    _fits(compiled)
    text = compiled.as_text()
    rows = window * SEG_ROWS
    assert "pinot.route" in text
    if sort_regime:
        sorts = [ln for ln in text.splitlines() if " sort(" in ln]
        # the full sort over the window's rows, the compacted ones over a
        # 64th and a 16th of them (PR 33)
        assert sorts and all(any(f"[{n}]" in ln for n in
                                 (rows, rows // 64, rows // 16))
                             for ln in sorts)
        assert any(f"[{rows // 64}]" in ln for ln in sorts)
        assert not any(f"[{SMOKE_SEGS * SEG_ROWS}]" in ln for ln in sorts)
    elif "group-by" in name:
        assert (" while(" in text) == (rows > kernels.SLAB_ROWS)
        assert " sort(" not in text
    # the window is cut inside the fusions that read it: no top-level
    # dynamic-slice writes a copy of a column's window
    entry = text[text.index("ENTRY"):]
    copies = [ln for ln in entry.splitlines()
              if " dynamic-slice(" in ln and f"{SEG_ROWS}]" in ln]
    assert not copies, copies[:3]


def test_topk_kernel_compiles_for_v5e(topo, cpu_exec, segments):
    p = _prepare(cpu_exec, segments, "top-k")
    assert p.kind == "topk"
    ctx = compile_query(SQL["top-k"], segments[0].schema)
    order = ctx.order_by[0]
    from pinot_tpu.query.executor import ServerQueryExecutor
    from pinot_tpu.query.planner import plan_segment
    plan = plan_segment(ctx, segments[0])
    spec = kernels.KernelSpec(plan.filter_prog, (), 1, (), {}, SEG_ROWS)
    fn, _ = kernels.topk_kernel(
        spec, order.expr, order.desc,
        ctx.limit + ServerQueryExecutor.TOPK_SLACK,
        total_rows=SMOKE_SEGS * SEG_ROWS)
    mesh = _mesh(topo, 1)
    ax = _abstract(p.inputs, (p.s_pad, p.rows), (SMOKE_SEGS, SEG_ROWS), mesh)
    compiled = fn.__wrapped__.lower(
        ax["ids"], ax["vals"], ax["luts"], ax["iscal"], ax["fscal"],
        ax["nulls"], ax["valid"], ()).compile()
    _fits(compiled)


def test_pack_kernel_compiles_for_v5e(topo, cpu_exec, segments):
    """The device-side output concatenation, with the key-axis trim of the
    500k-key partial (the slice jax 0.9's explicit axes refused)."""
    p = _prepare(cpu_exec, segments, "group-by 500k keys")
    pad, real = p.trim_keys
    assert pad and real < pad
    mesh = _mesh(topo, 1)
    ax = _abstract(p.inputs, (p.s_pad, p.rows), (SMOKE_SEGS, SEG_ROWS), mesh)
    fn = MeshQueryExecutor(mesh)._build_shard_kernel(_real_spec(p.spec))
    outs = jax.eval_shape(fn.jitted_for(ax), ax)
    repl = NamedSharding(mesh, P())
    outs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=repl)
            for k, v in outs.items()}
    meta = tuple(sorted((k, tuple(v.shape), v.dtype.str)
                        for k, v in outs.items()))
    compiled = combine._pack_kernel(meta, p.trim_keys, False).lower(
        outs).compile()
    _fits(compiled)


def test_bitmap_word_kernel_compiles_for_v5e(topo, segments):
    """The packed-word COUNT kernel of the per-segment engine path at one
    4Mi-row segment (the mesh path evaluates the same filter as a LUT leaf)."""
    from jax.sharding import SingleDeviceSharding
    from pinot_tpu.query.planner import plan_segment, select_bitmap_leaves
    ctx = compile_query(SQL["bitmap-filter count"], segments[0].schema)
    plan = plan_segment(ctx, segments[0])
    leaves = select_bitmap_leaves(plan, segments[0])
    assert leaves, "lo_region = 'ASIA' no longer qualifies for the bitmap"
    spec = kernels.KernelSpec(plan.filter_prog, (), 1, (), {}, SEG_ROWS,
                              bitmap_leaves=leaves)
    fn = kernels.filter_count_kernel(spec)
    assert fn is not None
    one = SingleDeviceSharding(topo.devices[0])
    words = jax.ShapeDtypeStruct((SEG_ROWS // 32,), jnp.uint32, sharding=one)
    bitmaps = tuple(jax.ShapeDtypeStruct((1, SEG_ROWS // 32), jnp.uint32,
                                         sharding=one) for _ in leaves)
    _fits(fn.__wrapped__.lower(words, bitmaps).compile())


@pytest.mark.parametrize("name,collective", [
    ("q1.1 filter+sum", "all-reduce"),
    ("group-by 20k keys", "reduce-scatter"),
    # the sort regime under `shard_map`: each chip its own conditional on its
    # own tiles' counts (PR 33), the flags `pmin`ned, the sums reduce-scattered
    ("group-by 500k keys", "reduce-scatter"),
])
def test_four_chip_program_compiles_with_its_collective(topo, cpu_exec,
                                                        segments, name,
                                                        collective):
    """The four-chip program: the stacked [S, P] shard kernel on a Mesh of the
    described devices; >= SCATTER_MIN_KEYS dense sums reduce-scatter."""
    _, compiled = _compile_agg(topo, cpu_exec, segments, name, SMOKE_SEGS,
                               n_devices=4)
    _fits(compiled)
    text = compiled.as_text()
    if collective == "reduce-scatter":
        # the compiler may lower a small reduce-scatter as all-reduce + slice
        assert "reduce-scatter" in text or (
            "all-reduce" in text and "dynamic-slice" in text)
        # each device keeps 1/4 of the key space: the outputs stay sharded
        # (the sort regime's two scalar flags are `pmin`ned and replicated)
        from pinot_tpu.query.stats import DECODE_FLAGS
        assert all(s.spec == P(SEGMENT_AXIS)
                   for out, s in compiled.output_shardings.items()
                   if out not in DECODE_FLAGS)
    else:
        assert collective in text
    if "500k" in name:
        rows = SMOKE_SEGS * SEG_ROWS // 4
        sorts = [ln for ln in text.splitlines() if " sort(" in ln]
        assert any(f"[{rows}]" in ln for ln in sorts)
        assert any(f"[{rows // 64}]" in ln for ln in sorts)
        assert any(f"[{rows // 16}]" in ln for ln in sorts)
        assert "pinot.groupby.partitioned.presort/" in text


@pytest.mark.parametrize("width,decode", [
    (16, "select"),                                  # lo_discount's own table
    (2 * kernels.SELECT_DECODE_CAP, "gather"),       # the same plan, wide table
])
def test_q11_small_table_decode_fuses_into_the_scan(topo, cpu_exec, segments,
                                                    width, decode):
    """The Q1.1-shaped fused scan at 16Mi rows, compiled for the v5e: with its
    16-entry table the program holds no gather, names `pinot.decode.select`
    and writes no rows-sized decoded column; over the cap it keeps the gather
    (a fusion of its own whose output is the decoded column) under
    `pinot.decode.gather`."""
    p = _prepare(cpu_exec, segments, "q1.1 filter+sum")
    assert dict(p.spec.fused_cols) == {"lo_discount": "dict"}
    assert p.inputs["vals"]["lo_discount"].shape == (p.s_pad, 16)
    mesh = _mesh(topo, 1)
    ax = _abstract(p.inputs, (p.s_pad, p.rows), (MATMUL_SEGS, SEG_ROWS), mesh)
    table = ax["vals"]["lo_discount"]
    ax["vals"]["lo_discount"] = jax.ShapeDtypeStruct(
        (MATMUL_SEGS, width), table.dtype, sharding=table.sharding)
    fn = MeshQueryExecutor(mesh)._build_shard_kernel(_real_spec(p.spec))
    compiled = fn.jitted_for(ax).lower(ax).compile()
    _fits(compiled)
    text = compiled.as_text()
    rows = MATMUL_SEGS * SEG_ROWS
    entry = text[text.index("ENTRY"):]
    # fusions whose result is a whole int32 column (a prefetch copy of an
    # input column into the chip's other memory space is not one)
    decoded = [ln.strip() for ln in entry.splitlines()
               if " fusion(" in ln and any(
                   f" = s32[{shape}]" in ln for shape in
                   (rows, f"{MATMUL_SEGS},{SEG_ROWS}",
                    f"1,{MATMUL_SEGS},{SEG_ROWS}"))]
    assert f"pinot.decode.{decode}" in text
    if decode == "select":
        assert " gather(" not in text
        assert "pinot.decode.gather" not in text
        assert not decoded, decoded
    else:
        assert " gather(" in text
        assert "pinot.decode.select" not in text
        assert decoded, "the gather no longer writes the decoded column?"
