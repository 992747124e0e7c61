"""The pool workers' two jobs, one segment each: `build_segment` (set-up)
generates segment i from [seed, i] and builds it with the program's
SegmentBuilder; `reference_segment` (after the window) generates it again and
evaluates the reference's part of every answer of the query pool on it.

Imports `pinot_tpu.segment.writer` and `pinot_tpu.schema`, which do not pull
in jax, so the pool can start before the parent touches the chip.
"""

import os
import sys
import time

import numpy as np

from . import cells, reference


def make_schema(config: dict):
    from pinot_tpu.schema import DataType, Schema, date_time, dimension, metric
    make = {"dimension": dimension, "metric": metric, "date_time": date_time}
    return Schema(config["table"], [
        make[c["role"]](c["name"], DataType[c["type"]])
        for c in config["schema"]])


def build_segment(job: dict) -> dict:
    """job: config, seed, index, rows, out_dir."""
    sys.path.insert(0, cells.ROOT)
    from pinot_tpu.schema import DataType
    from pinot_tpu.segment.dictionary import Dictionary
    from pinot_tpu.segment.writer import SegmentBuilder, SegmentGeneratorConfig
    config = job["config"]
    gen = cells.load_generator(config)
    t0 = time.perf_counter()
    tables = gen.tables(config)
    cols = gen.segment(config, job["seed"], job["index"], job["rows"])
    t1 = time.perf_counter()
    # strings go in as fixed-width numpy arrays against a fixed dictionary:
    # the builder's searchsorted then runs in C, not once a python string
    raw, fixed = {}, {}
    for c in config["schema"]:
        name = c["name"]
        if name in tables:
            raw[name] = tables[name][cols[name]]
            if c["type"] == "STRING":
                fixed[name] = Dictionary([str(v) for v in tables[name]],
                                         DataType.STRING)
        else:
            raw[name] = cols[name]
    builder = SegmentBuilder(make_schema(config), SegmentGeneratorConfig(
        no_dictionary_columns=list(config.get("no_dictionary_columns", []))))
    seg_dir = builder.build(raw, job["out_dir"],
                            f"{config['table']}_{job['index']}",
                            fixed_dictionaries=fixed)
    t2 = time.perf_counter()
    return {"index": job["index"], "seg_dir": seg_dir,
            "bytes": sum(os.path.getsize(os.path.join(d, f))
                         for d, _, fs in os.walk(seg_dir) for f in fs),
            "generate_s": t1 - t0, "build_s": t2 - t1}


def reference_segment(job: dict) -> dict:
    """job: config, seed, index, rows, pool (bound specs), control. The
    segment again from the seed, and the reference's part of every answer of
    the pool over it; with `control` also at bfloat16."""
    config = job["config"]
    gen = cells.load_generator(config)
    tables = gen.tables(config)
    cols = gen.segment(config, job["seed"], job["index"], job["rows"])
    parts = [reference.partial(spec, cols, tables) for spec in job["pool"]]
    control = ([reference.partial(spec, cols, tables, precision="bf16")
                for spec in job["pool"]] if job.get("control") else None)
    return {"index": job["index"], "parts": parts, "control": control}
