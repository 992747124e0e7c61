#!/usr/bin/env python3
"""The benchmark's own checks, on the CPU: `python3 benchmark/selftest.py [--quick]`.

  1. the generic reference evaluator against a brute-force python loop over a
     tiny table, for every template of every family;
  2. the least-bytes arithmetic of one template, by hand;
  3. the trace reducer: interval arithmetic on a made-up trace, and the recorded
     chip trace under fixtures/ against the numbers written down beside it;
  4. the controls come out as not correct: the reference at bfloat16, rounded
     and truncated, fails the sum limit (the truncated one also where a group
     has so many rows that the rounded one's errors cancel), the reference
     with one segment left out fails counts and rows;
  5. (not with --quick) two whole rehearsal runs through run.py, which skip
     only the look for a chip: a clean one with `--control 1`, whose last line
     must have the contract's shape and `correct` true, and each control's own
     `correct` false; and one with the timed path broken underneath (the
     broker's answers altered where they are produced), which must come out
     `correct` false, each fault by its own number. No cell of BENCHMARK.json
     answers a COUNT(*), so a count one too high is shown to the comparison
     itself (1).

Exits 0 only if every check passed. Nothing here is a measurement.
"""

import contextlib
import io
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.harness import (cells, readers, reference, traffic,  # noqa: E402
                               trace_reduce)

SEED = 2_147_483_777
KEPT_AS_DATA_ON = "ssb10-flat"     # the configuration of a mix no cell uses


def template(name: str) -> dict:
    return cells.read_json(cells.BENCH, "queries", name + ".json")


def templates_by_config() -> dict:
    """{configuration name: its templates}: every traffic mix's templates on
    the configuration of the first cell that uses the mix (a mix kept as data,
    which no cell uses, on `KEPT_AS_DATA_ON`). Found by name, so that a PR
    that adds a schema with its cell is covered without an edit here."""
    bench = cells.read_json(cells.ROOT, "BENCHMARK.json")
    out, seen = {}, set()
    d = os.path.join(cells.BENCH, "traffic")
    for f in sorted(os.listdir(d)):
        mix = cells.read_json(d, f)
        config = next((w["config"] for w in bench["workloads"]
                       if w["traffic"] == mix["name"]), KEPT_AS_DATA_ON)
        generator = cells.read_json(cells.BENCH, "configs",
                                    config + ".json")["generator"]
        for name in mix["templates"]:
            if (generator, name) not in seen:
                seen.add((generator, name))
                out.setdefault(config, []).append(template(name))
    return out


def brute_force(spec, cols, tables) -> list:
    """The same answer by a python loop over the rows, sharing no code with
    reference.partial/merge/finish except the final ordering rule. Every
    aggregate in python's own whole numbers; an AVG divided at the end."""
    def value(c, i):
        return tables[c][cols[c][i]] if c in tables else cols[c][i]
    ops = {"eq": lambda v, a: v == a[0], "lt": lambda v, a: v < a[0],
           "le": lambda v, a: v <= a[0], "gt": lambda v, a: v > a[0],
           "ge": lambda v, a: v >= a[0],
           "between": lambda v, a: a[0] <= v <= a[1],
           "in": lambda v, a: v in a}
    aggs = spec.get("aggregates") or [dict(spec["aggregate"], name="agg")]
    groups = {}
    n = len(next(iter(cols.values())))
    for i in range(n):
        if not all(ops[f["op"]](value(f["column"], i), f["args"])
                   for f in spec.get("filters", [])):
            continue
        key = tuple(value(c, i) for c in spec.get("group_by", []))
        held = groups.setdefault(key, {"rows": 0})
        held["rows"] += 1
        for a in aggs:
            if a["fn"] in ("sum", "avg"):
                add = 0
                for t in a["terms"]:
                    term = int(t.get("coef", 1))
                    for c in t["columns"]:
                        term *= int(value(c, i))
                    add += term
                held[a["name"]] = held.get(a["name"], 0) + add
            elif a["fn"] in ("min", "max"):
                v = int(value(a["column"], i))
                held[a["name"]] = (v if a["name"] not in held else
                                   min(held[a["name"]], v) if a["fn"] == "min"
                                   else max(held[a["name"]], v))

    def row_of(key, held):
        named = dict(zip(spec["group_by"], key))
        for a in aggs:
            named[a["name"]] = (held["rows"] if a["fn"] == "count" else
                                held[a["name"]] / held["rows"]
                                if a["fn"] == "avg" else held[a["name"]])
        return named
    if not spec.get("group_by"):
        if () not in groups:    # over no rows: COUNT 0, SUM 0, NULL the others
            over_none = {a["name"]: {"count": 0, "sum": 0}.get(a["fn"])
                         for a in aggs}
            return [[over_none[c] for c in spec["select"]]]
        return [[row_of((), groups[()])[c] for c in spec["select"]]]
    rows = [row_of(key, held) for key, held in groups.items()]
    for col, direction in reversed(spec.get("order_by", [])):
        rows.sort(key=lambda r: r[col], reverse=(direction == "desc"))
    rows = rows[:spec.get("limit", len(rows))]
    return [[r[c] for c in spec["select"]] for r in rows]


def table_of(config_name: str, rows: int, segments: int):
    """(config, tables, [segment...]) of a configuration at a test's size."""
    config = cells.read_json(cells.BENCH, "configs", config_name + ".json")
    gen = cells.load_generator(config)
    return config, gen.tables(config), [gen.segment(config, SEED, i, rows)
                                        for i in range(segments)]


def evaluate(spec, segs, tables, precision="exact", evaluator=reference) -> list:
    """The evaluator's answer over the segments: parts, merged, finished."""
    return evaluator.finish(spec, evaluator.merge(
        [evaluator.partial(spec, s, tables, precision) for s in segs]), tables)


def plain(row) -> list:
    return [x if x is None else str(x) if isinstance(x, (str, np.str_))
            else float(x) for x in row]


def check_reference() -> None:
    rng = np.random.default_rng(SEED)
    seen = []
    for config_name, templates in templates_by_config().items():
        _, tables, segs = table_of(config_name, 3000, 2)
        whole = {c: np.concatenate([s[c] for s in segs]) for c in segs[0]}
        for t in templates:
            seen.append(f"{t['family']}/{t['name']}")
            for _ in range(3):
                holes = traffic.draw_holes(t, tables, rng)
                # wide literals so that a tiny table still has rows to group
                spec = reference.bind(t["reference"], holes)
                got = evaluate(spec, segs, tables)
                want = brute_force(spec, whole, tables)
                assert len(got) == len(want), (t["name"], len(got), len(want))
                for g, w in zip(got, want):
                    assert plain(g) == plain(w), (t["name"], g, w)
                c = reference.compare(spec, json.loads(json.dumps(got)), got,
                                      1e-6)
                assert not c["wrong"] and not c["count_wrong"] \
                    and c["sum_gap"] == 0.0, (t["name"], c)
    assert {"check/q1-shape", "check/minmax", "ssb/q1.1"} <= set(seen), seen
    # the comparison itself: each kind of wrong answer is seen
    _, tables, segs = table_of("ssb10-flat", 3000, 2)
    spec = reference.bind(template("ssb/q3.1")["reference"],
                          {"region": "ASIA", "y0": 1992, "y1": 1997})
    want = evaluate(spec, segs, tables)
    assert len(want) > 3
    agg = spec["select"].index("agg")
    scaled = [list(r) for r in want]
    scaled[1][agg] *= 1 + 1e-4
    assert 0.9e-4 < reference.compare(spec, scaled, want, 1e-6)["sum_gap"] < 1.1e-4
    assert reference.compare(spec, want[:-1], want, 1e-6)["wrong"]
    assert reference.compare(spec, want[1:] + want[:1], want, 1e-6)["wrong"]
    renamed = [list(r) for r in want]
    renamed[0][0] = "NOWHERE"
    assert reference.compare(spec, renamed, want, 1e-6)["wrong"]
    spec = reference.bind(template("tiles/region")["reference"],
                          {"region": "ASIA"})
    want = evaluate(spec, segs, tables)
    assert want[0][0] > 0
    assert reference.compare(spec, [[want[0][0] + 1]], want, 1e-6)["count_wrong"]
    # a row of several aggregates: every aggregate cell is judged
    spec = reference.bind(template("check/q1-shape")["reference"],
                          {"d": 19980603})
    want = evaluate(spec, segs, tables)
    sel = spec["select"]
    for name, scale, number in (("sum_charge", 1 + 1e-4, "sum_gap"),
                                ("avg_disc", 1 + 1e-4, "sum_gap"),
                                ("count_order", None, "count_wrong")):
        bad = [list(r) for r in want]
        cell = bad[-1][sel.index(name)]
        bad[-1][sel.index(name)] = cell * scale if scale else cell + 1
        c = reference.compare(spec, bad, want, 1e-6)
        assert c[number] > 0.9e-4 and not c["wrong"], (name, c)
    print(f"ok reference: {len(seen)} templates equal the brute-force loop; "
          "the comparison sees a scaled sum, a missing row, a wrong order, a "
          "wrong key, a count one too high, and in a row of eight aggregates "
          "a scaled SUM, a scaled AVG and a COUNT one too high")


def check_least_bytes() -> None:
    config = cells.read_json(cells.BENCH, "configs", "ssb10-flat.json")
    t = template("ssb/q2.1")
    # Q2.1 reads lo_revenue (81,000..10,000,000: 4 bytes), d_year (7 values: 1),
    # p_brand1 (1,000 values: 2), p_category (25: 1), s_region (5: 1) = 9 bytes
    # a row, times 67,108,864 rows
    assert reference.columns_read(t["reference"]) == [
        "d_year", "lo_revenue", "p_brand1", "p_category", "s_region"]
    assert readers.least_bytes(t["reference"], config) == 9 * 67_108_864
    t = template("ssb/q1.1")
    # d_year 1 + lo_discount 1 + lo_quantity 1 + lo_extendedprice 4
    assert readers.least_bytes(t["reference"], config) == 7 * 67_108_864
    print("ok least bytes: Q2.1 = 9 bytes a row, Q1.1 = 7 bytes a row")


def check_trace_reduce() -> None:
    merged = trace_reduce.union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert merged == [[0, 20], [30, 45]], merged
    assert trace_reduce.covered(merged, 10, 35) == 15
    fixture = os.path.join(cells.BENCH, "fixtures", "quarter-flights.xplane.pb")
    expect = os.path.join(cells.BENCH, "fixtures", "quarter-flights.json")
    if not os.path.exists(fixture):
        print("ok trace reducer: interval arithmetic (no recorded trace here)")
        return
    want = cells.read_json(expect)
    got = trace_reduce.reduce(fixture)
    assert got["devices"] == want["devices"], got["devices"]
    for k in ("window_s", "busy_s", "idle_share"):
        assert abs(got[k] - want[k]) <= 1e-9 * max(1.0, abs(want[k])), (k, got[k])
    assert [n for n, _ in got["device_ops"]] == \
        [n for n, _ in want["device_ops"]], got["device_ops"]
    assert 0.0 < got["busy_s"] <= got["window_s"]
    print(f"ok trace reducer: the recorded chip trace reduces to busy "
          f"{got['busy_s']:.3f} s of {got['window_s']:.3f} s, as written down")


def check_controls() -> None:
    """Every control, each configuration's templates by its own limit; each
    has to fail an answer of several aggregates too, and the truncated
    bfloat16 one where the rounded one cannot."""
    rng = np.random.default_rng(SEED + 1)
    worst, counts_wrong, rows_wrong, several = {}, 0, 0, {}
    for config_name, templates in templates_by_config().items():
        config, tables, segs = table_of(config_name, 65_536, 4)
        limit = float(config["guarantees"]["sum_rel_gap"])
        here = dict.fromkeys(reference.ROUNDINGS, 0.0)
        for t in templates:
            holes = traffic.draw_holes(t, tables, rng)
            spec = reference.bind(t["reference"], holes)
            want = evaluate(spec, segs, tables)
            for name in here:
                c = reference.compare(spec, evaluate(spec, segs, tables, name),
                                      want, limit)
                here[name] = max(here[name], c["sum_gap"])
            c = reference.compare(spec, evaluate(spec, segs[:3], tables), want,
                                  limit)
            counts_wrong += c["count_wrong"]
            rows_wrong += c["wrong"] or not c["sum_gap"] <= limit
            several[t["name"]] = c["count_wrong"]
        assert min(here.values()) > 3 * limit, (config_name, here)
        worst[config_name] = here
    assert counts_wrong >= 1 and rows_wrong >= 1, (counts_wrong, rows_wrong)
    assert several["q1-shape"] and several["minmax"], several
    # Q1's eight aggregates at once. A rounding to the nearest bfloat16 errs
    # evenly, so a group's errors cancel as the root of its rows: at 240 rows
    # a group it fails the limit, at 168,000 (4 x 1Mi rows in 25 groups) it
    # does not separate from it. The truncation errs one way and reads the
    # same at both sizes.
    config, tables, small = table_of(KEPT_AS_DATA_ON, 3000, 2)
    limit = float(config["guarantees"]["sum_rel_gap"])
    spec = reference.bind(template("check/q1-shape")["reference"],
                          {"d": 19980603})
    read = {}
    for size, segs in (("small", small),
                       ("large", table_of(KEPT_AS_DATA_ON, 1 << 20, 4)[2])):
        want = evaluate(spec, segs, tables)
        for name in reference.ROUNDINGS:
            got = evaluate(spec, segs, tables, name)
            by_name = reference.gaps_by_name(spec, got, want)
            read[size, name] = reference.compare(spec, got, want,
                                                 limit)["sum_gap"]
            assert read[size, name] == max(by_name.values()), by_name
            assert by_name["sum_qty"] == 0.0, by_name   # 1..50: eight bits hold
            if (size, name) != ("large", "bf16"):
                assert by_name["avg_price"] > 3 * limit \
                    and by_name["sum_charge"] > 3 * limit, (size, name, by_name)
    assert read["large", "bf16"] < 3 * limit, read
    assert read["large", "bf16_truncated"] > 100 * limit, read
    print(f"ok controls: by configuration {worst} (limit {limit:.0e}); on "
          f"Q1's eight aggregates the rounded bfloat16 reads "
          f"{read['small', 'bf16']:.2e} at 240 rows a group and "
          f"{read['large', 'bf16']:.2e} at 168,000, where it no longer "
          f"separates, the truncated one {read['small', 'bf16_truncated']:.2e} "
          f"and {read['large', 'bf16_truncated']:.2e}; with a segment left out "
          f"{counts_wrong} counts (Q1's shape and MIN/MAX among them) and "
          f"{rows_wrong} other answers are wrong")


def rehearse(workload: str, fault=None, control: int = 0) -> dict:
    """One whole run through run.main with --rehearse; `fault(n, rows)` alters
    the broker's n-th answer where it is produced."""
    import benchmark.run as run
    from pinot_tpu.cluster.broker import Broker
    original = Broker.handle_query
    counter = [0]

    def broken(self, sql, stmt=None):
        result = original(self, sql, stmt)
        if sql.strip() == "SELECT COUNT(*) FROM lineorder":
            return result            # the harness's own load probe
        counter[0] += 1
        result.rows = fault(counter[0], [list(r) for r in result.rows],
                            result.columns)
        return result

    if fault:
        Broker.handle_query = broken
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", workload, "--seed", str(SEED),
                           "--seconds", "5", "--trace", "0", "--rehearse",
                           "--control", str(control)])
    finally:
        Broker.handle_query = original
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def check_runs() -> None:
    quarter = "ssb10-flat-quarter.flights-c4"
    line = rehearse(quarter, control=1)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(line)[-1] == "checked", list(line)
    assert line["correct"] is True and line["failed"] == 0 \
        and line["attempted"] > 0, line
    assert set(line["metrics"]) == {"qps", "mean_ms", "setup_s"}, line["metrics"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checked"].values():
        assert set(c) == {"value", "limit"}
    for name in (*reference.ROUNDINGS, "segment_left_out"):
        assert line["control"][name]["correct"] is False, line["control"]
    print("ok last line: the contract's keys, `checked` last, correct true "
          "on a clean rehearsal; every control in its place correct false")

    def is_number(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def three_faults(n, rows, columns):
        agg = [i for i, c in enumerate(columns) if "(" in c]
        if n % 3 == 0 and rows and agg and is_number(rows[0][agg[0]]):
            rows[0][agg[0]] = rows[0][agg[0]] * (1 + 1e-3)   # a sum altered
        elif n % 3 == 1 and len(rows) > 1:
            rows = rows[:-1]                                 # a row lost
        elif n % 3 == 2 and len(rows) > 1:
            rows[0], rows[-1] = rows[-1], rows[0]            # the order broken
        return rows
    line = rehearse(quarter, three_faults)
    chk = line["checked"]
    assert line["correct"] is False and line["failed"] > 0, line
    assert chk["sum_rel_gap_max"]["value"] > 10 * chk["sum_rel_gap_max"]["limit"]
    assert chk["wrong_rows"]["value"] >= 2, chk
    print(f"ok faults (flights): a sum altered reads "
          f"{chk['sum_rel_gap_max']['value']:.2e}, rows lost or out of order "
          f"{chk['wrong_rows']['value']}; correct false")


def main() -> int:
    check_reference()
    check_least_bytes()
    check_trace_reduce()
    check_controls()
    if "--quick" not in sys.argv:
        check_runs()
    print("selftest passed (cpu; nothing here is a measurement)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
