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
from collections.abc import Mapping

from . import cells, reference


class LazyColumns(Mapping):
    """The builder's `columns`: each column's values are made when the builder
    asks for that column and dropped after it, so a worker never holds all the
    string columns of a 4Mi-row segment at once (1.7 GB as fixed-width
    arrays)."""

    def __init__(self, names, n, make):
        self._names, self._n, self._make = list(names), n, make

    def __getitem__(self, name):
        if name not in self._names:
            raise KeyError(name)
        return self._make(name)

    def __iter__(self):
        return iter(self._names)

    def __len__(self):
        return len(self._names)

    def values(self):       # the builder only takes their lengths
        return [range(self._n)] * len(self._names)


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
    fixed = {c["name"]: Dictionary([str(v) for v in tables[c["name"]]],
                                   DataType.STRING)
             for c in config["schema"] if c["type"] == "STRING"}
    raw = LazyColumns(
        [c["name"] for c in config["schema"]], job["rows"],
        lambda name: tables[name][cols[name]] if name in tables else cols[name])
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
    the pool over it; with `control` also at each lowered precision
    (`reference.ROUNDINGS`)."""
    config = job["config"]
    gen = cells.load_generator(config)
    tables = gen.tables(config)
    cols = gen.segment(config, job["seed"], job["index"], job["rows"])
    parts = [reference.partial(spec, cols, tables) for spec in job["pool"]]
    out = {"index": job["index"], "parts": parts}
    if job.get("control"):
        for name in reference.ROUNDINGS:
            out[name] = [reference.partial(spec, cols, tables, precision=name)
                         for spec in job["pool"]]
    return out
