"""Finds a cell's files by the names in BENCHMARK.json."""

import importlib.util
import json
import os

from . import readers

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_py(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell: its BENCHMARK.json entry, configuration, traffic mix, query
    templates and the metrics it owes with and without a trace."""
    bench = read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[workload]
    config = read_json(BENCH, "configs", cell["config"] + ".json")
    traffic = read_json(BENCH, "traffic", cell["traffic"] + ".json")
    templates = [read_json(BENCH, "queries", t + ".json")
                 for t in traffic["templates"]]

    def owed(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return {"name": workload, "chips": cell["chips"], "config": config,
            "traffic": traffic, "templates": templates,
            "end_to_end": owed(bench["end_to_end"]),
            "per_layer": owed(bench["per_layer"])}


def load_generator(config: dict):
    return load_py(os.path.join(BENCH, "generators",
                                config["generator"] + ".py"))


def load_reader(metric_name: str):
    """A per-layer metric's reader: `read(ctx) -> float | None`, from the
    `.py` beside `metrics/<name>.json` or the function of harness/readers.py
    that the `.json` names."""
    meta = read_json(BENCH, "metrics", metric_name + ".json")
    beside = os.path.join(BENCH, "metrics", metric_name + ".py")
    if os.path.exists(beside):
        return load_py(beside).read
    return getattr(readers, meta["reader"])


def peaks(device_kind: str) -> dict:
    table = read_json(BENCH, "harness", "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no published peaks for device kind {device_kind!r} "
                         "in benchmark/harness/peaks.json")
    return table[device_kind]
