"""Table config tuner: recommend indexing/encoding from observed data shape.

Analog of the reference's config recommendation engine
(`pinot-controller/.../recommender/`): given a built segment (the data's
statistical profile) and optionally the query shapes, propose an
IndexingConfig — which columns want inverted/range/bloom indexes, which
metrics should skip the dictionary, where a star-tree pays off.

Heuristics mirror the reference's rules engine, adapted to THIS engine's cost
model: dictionary LUT filters are nearly free on the device (id-interval
compares), so inverted indexes matter mainly for very selective host-path
lookups; no-dictionary raw encoding matters for high-cardinality numerics
(dict adds an indirection the device path must host-materialize anyway).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from ..segment.reader import ImmutableSegment, load_segment
from ..table import IndexingConfig


def analyze_segment(seg_or_dir) -> Dict[str, Dict[str, Any]]:
    """Per-column profile: cardinality ratio, type, encoding, MV-ness."""
    seg: ImmutableSegment = (seg_or_dir if isinstance(seg_or_dir, ImmutableSegment)
                             else load_segment(seg_or_dir))
    out: Dict[str, Dict[str, Any]] = {}
    n = max(seg.num_docs, 1)
    for name in seg.column_names:
        r = seg.column(name)
        card = r.cardinality if r.has_dictionary else None
        out[name] = {
            "dataType": r.data_type.value,
            "numeric": r.data_type.is_numeric,
            "hasDictionary": r.has_dictionary,
            "cardinality": card,
            "cardinalityRatio": (card / n) if card is not None else 1.0,
            "multiValue": getattr(r, "is_multi_value", False),
            "sorted": r.is_sorted,
            "indexes": list(r.index_types),
        }
    return out


def recommend(seg_or_dir, filter_columns: Optional[List[str]] = None,
              group_by_columns: Optional[List[str]] = None,
              agg_columns: Optional[List[str]] = None) -> Dict[str, Any]:
    """IndexingConfig proposal + per-recommendation rationale.

    `filter_columns`/`group_by_columns`/`agg_columns` describe the workload
    (the reference feeds query patterns into its rules engine); omitted, every
    dimension is assumed filterable.
    """
    profile = analyze_segment(seg_or_dir)
    filt = set(filter_columns if filter_columns is not None else
               [c for c, p in profile.items() if not p["numeric"]])
    group = set(group_by_columns or [])
    aggs = set(agg_columns or [])

    cfg = IndexingConfig()
    why: List[str] = []
    for col, p in profile.items():
        ratio = p["cardinalityRatio"]
        if p["numeric"] and not p["multiValue"] and ratio > 0.7 \
                and col not in group:
            cfg.no_dictionary_columns.append(col)
            why.append(f"{col}: cardinality ratio {ratio:.2f} > 0.7 — raw "
                       f"encoding (dictionary adds indirection without reuse); "
                       f"range predicates ride device compares + min/max "
                       f"metadata pruning (range indexes need dict ids)")
            if col in filt:
                cfg.bloom_filter_columns.append(col)
                why.append(f"{col}: raw + filtered — bloom filter folds "
                           f"absent-value EQ to constant false at plan time")
            continue
        if col in filt and p["hasDictionary"] and p["numeric"] \
                and not p["multiValue"] and 0.1 <= ratio <= 0.7:
            cfg.range_index_columns.append(col)
            why.append(f"{col}: dict-encoded filtered numeric — range index "
                       f"for selective host-path range predicates")
        if col in filt and p["hasDictionary"]:
            if p["cardinality"] is not None and p["cardinality"] <= 10_000 \
                    and ratio < 0.1:
                cfg.inverted_index_columns.append(col)
                why.append(f"{col}: low-cardinality filtered dimension — "
                           f"inverted index for very selective host lookups "
                           f"(device LUT filters stay free either way)")
    # star-tree: a few low-cardinality group dimensions + numeric aggregations
    st_dims = [c for c in group
               if profile.get(c, {}).get("cardinality") is not None
               and profile[c]["cardinality"] <= 1000
               and not profile[c]["multiValue"]]
    if st_dims and aggs:
        pairs = [f"SUM__{a}" for a in sorted(aggs)
                 if profile.get(a, {}).get("numeric")]
        if pairs:
            cfg.star_tree_configs.append({
                "dimensionsSplitOrder": sorted(st_dims),
                "functionColumnPairs": pairs,
                "maxLeafRecords": 10_000,
            })
            why.append(f"star-tree over {sorted(st_dims)}: repeated group-bys "
                       f"with bounded key space pre-aggregate well")
    return {"indexing": cfg.to_json(), "rationale": why, "profile": profile}


# ---------------------------------------------------------------------------
# workload-driven advisors (reference: the recommender's rules engine inputs —
# schema + query patterns + throughput numbers,
# pinot-controller/.../recommender/rules/impl/*.java)
# ---------------------------------------------------------------------------

def analyze_workload(queries: List[str]) -> Dict[str, Any]:
    """Parse representative queries into per-column usage stats (the
    reference's `QueryWithWeightAndRules` input): EQ/IN filter hits, range
    filter hits, group-by hits, aggregation args, JSON_MATCH/TEXT_MATCH use."""
    from ..sql.ast import Function, Identifier, walk
    from ..sql.parser import parse_query

    stats: Dict[str, Dict[str, int]] = {}

    def bump(col: str, kind: str) -> None:
        stats.setdefault(col, {"eq": 0, "range": 0, "group": 0, "agg": 0,
                               "json": 0, "text": 0})[kind] += 1

    for sql in queries:
        stmt = parse_query(sql)
        if stmt.where is not None:
            for node in walk(stmt.where):
                if not isinstance(node, Function):
                    continue
                args = node.args
                col = (args[0].name if args and isinstance(args[0], Identifier)
                       else None)
                if col is None:
                    continue
                if node.name in ("eq", "in", "in_id_set"):
                    bump(col, "eq")
                elif node.name in ("gt", "gte", "lt", "lte", "between"):
                    bump(col, "range")
                elif node.name == "json_match":
                    bump(col, "json")
                elif node.name == "text_match":
                    bump(col, "text")
        for e in stmt.group_by:
            if isinstance(e, Identifier):
                bump(e.name, "group")
        for e, _alias in stmt.select:
            if isinstance(e, Function):
                for a in e.args:
                    if isinstance(a, Identifier) and a.name != "*":
                        bump(a.name, "agg")
    return stats


def recommend_partitioning(seg_or_dir, queries: List[str],
                           num_servers: int = 2,
                           target_qps: float = 0.0) -> Dict[str, Any]:
    """Partition-column + count advice (reference: PinotTablePartitionRule /
    KafkaPartitionRule): the best partition column is the most EQ-filtered
    column whose cardinality comfortably exceeds the partition count — then
    every EQ query prunes to 1/N of segments, multiplying broker QPS."""
    from ..sql.ast import Function, Identifier, walk
    from ..sql.parser import parse_query
    profile = analyze_segment(seg_or_dir)
    # per-QUERY presence (not predicate hits): the score is "what fraction of
    # queries would prune on this column" — a query EQ-filtering the column
    # five times still prunes exactly once
    queries_with_eq: Dict[str, int] = {}
    for sql in queries:
        stmt = parse_query(sql)
        cols = set()
        if stmt.where is not None:
            for node in walk(stmt.where):
                if isinstance(node, Function) \
                        and node.name in ("eq", "in", "in_id_set") \
                        and node.args and isinstance(node.args[0], Identifier):
                    cols.add(node.args[0].name)
        for c in cols:
            queries_with_eq[c] = queries_with_eq.get(c, 0) + 1
    total_q = max(len(queries), 1)
    # one partition per server core-equivalent; pow2 for stable hashing
    num_partitions = 1
    while num_partitions < num_servers * 4:
        num_partitions *= 2
    best, best_score = None, 0.0
    for col, nq in queries_with_eq.items():
        p = profile.get(col)
        if p is None or p["multiValue"]:
            continue
        card = p["cardinality"] if p["cardinality"] is not None else 1 << 30
        if card < num_partitions * 4:
            continue   # skewed partitions: too few distinct values
        score = nq / total_q
        if score > best_score:
            best, best_score = col, score
    out: Dict[str, Any] = {"numPartitions": num_partitions, "rationale": []}
    if best is None or best_score < 0.2:
        out["partitionColumn"] = None
        out["rationale"].append(
            "no column is EQ-filtered in >=20% of queries with enough "
            "cardinality — partitioning would not prune, skip it")
    else:
        out["partitionColumn"] = best
        out["rationale"].append(
            f"{best}: EQ-filtered in {best_score:.0%} of queries with "
            f"cardinality {profile[best]['cardinality']} >= "
            f"4x{num_partitions} partitions — EQ queries prune to "
            f"1/{num_partitions} of segments")
        if target_qps:
            out["rationale"].append(
                f"at {target_qps:.0f} qps, pruned fan-out cuts per-server "
                f"query load ~{num_partitions}x on the partitioned column")
    return out


# measured single-partition realtime consume rate of THIS engine
# (kafkalite fetch->decode->MutableSegment.index, on the CPU)
ENGINE_CONSUME_ROWS_PER_SEC = 25_000.0


def recommend_realtime_provisioning(events_per_sec: float, avg_row_bytes: int,
                                    retention_hours: int = 72,
                                    host_memory_gb: float = 16.0,
                                    num_hosts: int = 2,
                                    flush_target_mb: int = 200
                                    ) -> Dict[str, Any]:
    """Realtime provisioning advice (reference: RealtimeProvisioningRule +
    MemoryEstimator): stream partitions from the consume-rate budget,
    flush threshold from the target completed-segment size, per-host memory
    from consuming + retained completed segments."""
    partitions = max(1, -(-int(events_per_sec) //
                          int(ENGINE_CONSUME_ROWS_PER_SEC)))
    flush_rows = max(10_000, int(flush_target_mb * (1 << 20) /
                                 max(avg_row_bytes, 1)))
    # consuming memory: the mutable segment holds flush_rows rows (+indexes,
    # ~2x raw) per partition; partitions spread across hosts. Completed
    # segments live on DISK; what stays memory-resident is the scan-hot
    # working set (stacked device/HBM columns — SegmentSetBlock), estimated
    # as a fraction of retained bytes.
    HOT_FRACTION = 0.2
    consuming_mb = (flush_rows * avg_row_bytes * 2) / (1 << 20)
    parts_per_host = -(-partitions // max(num_hosts, 1))
    retained_rows = events_per_sec * retention_hours * 3600
    retained_mb = retained_rows * avg_row_bytes / (1 << 20)
    per_host_mb = (parts_per_host * consuming_mb
                   + retained_mb * HOT_FRACTION / max(num_hosts, 1))
    fits = per_host_mb < host_memory_gb * 1024 * 0.7
    out = {
        "numPartitions": partitions,
        "flushThresholdRows": flush_rows,
        "consumingMemoryMbPerPartition": round(consuming_mb, 1),
        "estimatedPerHostMb": round(per_host_mb, 1),
        "retainedDiskMbPerHost": round(retained_mb / max(num_hosts, 1), 1),
        "fitsInMemory": fits,
        "rationale": [
            f"{partitions} partitions: {events_per_sec:.0f} events/s over a "
            f"measured ~{ENGINE_CONSUME_ROWS_PER_SEC:.0f} rows/s per-partition "
            f"consume rate",
            f"flush at {flush_rows} rows: completed segments land near "
            f"{flush_target_mb}MB ({avg_row_bytes}B/row)",
        ],
    }
    if not fits:
        need = -(-per_host_mb * num_hosts //
                 int(host_memory_gb * 1024 * 0.7))
        out["recommendedNumHosts"] = int(need)
        out["rationale"].append(
            f"estimated {per_host_mb:.0f}MB/host (consuming + ~"
            f"{HOT_FRACTION:.0%} hot working set of retained data) exceeds "
            f"70% of {host_memory_gb:.0f}GB — scale to ~{int(need)} hosts, "
            f"shorten retention, or tier old segments")
    return out


def recommend_from_workload(seg_or_dir, queries: List[str],
                            num_servers: int = 2,
                            target_qps: float = 0.0) -> Dict[str, Any]:
    """Full workload-driven recommendation: index advice (bloom/inverted/
    range/no-dictionary/json/star-tree) from PARSED query patterns + the
    partition advisor, one report (reference: the recommender endpoint taking
    schema + queriesWithWeights)."""
    usage = analyze_workload(queries)
    filt = [c for c, u in usage.items()
            if u["eq"] or u["range"] or u["json"] or u["text"]]
    group = [c for c, u in usage.items() if u["group"]]
    aggs = [c for c, u in usage.items() if u["agg"]]
    rec = recommend(seg_or_dir, filter_columns=filt, group_by_columns=group,
                    agg_columns=aggs)
    profile = rec["profile"]
    # JSON index rule (reference: JsonIndexRule): JSON_MATCH-ed columns
    for col, u in usage.items():
        if u["json"] and col in profile \
                and col not in rec["indexing"]["jsonIndexColumns"]:
            rec["indexing"]["jsonIndexColumns"].append(col)
            rec["rationale"].append(
                f"{col}: JSON_MATCH in the workload — json index turns the "
                f"path predicate into a posting-list lookup")
        if u["text"] and col in profile \
                and col not in rec["indexing"]["textIndexColumns"]:
            rec["indexing"]["textIndexColumns"].append(col)
            rec["rationale"].append(
                f"{col}: TEXT_MATCH in the workload — text index required")
    # sorted column rule (reference: InvertedSortedIndexJointRule): the most
    # EQ-filtered low-ratio column pays for sorting at build time
    eq_cols = sorted((u["eq"], c) for c, u in usage.items()
                     if u["eq"] and c in profile
                     and not profile[c]["multiValue"]
                     and profile[c]["cardinalityRatio"] < 0.5)
    if eq_cols and rec["indexing"].get("sortedColumn") is None:
        rec["indexing"]["sortedColumn"] = eq_cols[-1][1]
        rec["rationale"].append(
            f"{eq_cols[-1][1]}: most EQ-filtered column — sorting makes its "
            f"EQ/range predicates contiguous doc ranges (no index needed)")
    rec["partitioning"] = recommend_partitioning(
        seg_or_dir, queries, num_servers=num_servers, target_qps=target_qps)
    return rec
