"""Readers of the per-layer metrics: `read(ctx) -> float | None`. A reader that
finds nothing to read returns None and the harness leaves the metric out.

ctx: {"records": the window's answered queries, each {"latency_ms", "pool",
      "response"}; "templates": the template's name by pool index;
      "counters": the pipeline's counter deltas over the window;
      "trace": trace_reduce.reduce() of the traced slice, or None;
      "solo": [{"template", "least_bytes", "busy_s", "latency_ms"}] from the
      solo replay; "peaks": this device kind's row of peaks.json}
"""

import statistics

from . import reference


def _responses(ctx):
    return [r for r in ctx["records"] if r.get("response")]


def _mean_of(ctx, pick):
    vals = [v for v in (pick(r["response"]) for r in _responses(ctx))
            if v is not None]
    return statistics.fmean(vals) if vals else None


def http_overhead_ms(ctx):
    """Median of the client's latency less the broker's own timeUsedMs."""
    vals = [r["latency_ms"] - float(r["response"]["timeUsedMs"])
            for r in _responses(ctx) if "timeUsedMs" in r["response"]]
    return statistics.median(vals) if vals else None


def broker_plan_reduce_ms(ctx):
    def pick(resp):
        p = resp.get("phaseTimesMs")
        if not p:
            return None
        return float(p.get("compile", 0.0)) + float(p.get("reduce", 0.0))
    return _mean_of(ctx, pick)


def broker_server_hop_ms(ctx):
    """What is left of the broker's timeUsedMs after its own plan and reduce,
    the pipeline's queue wait and the device sync: scatter, the server's HTTP
    hop, serialisation, and waiting for the interpreter."""
    def pick(resp):
        p = resp.get("phaseTimesMs") or {}
        if "timeUsedMs" not in resp:
            return None
        return (float(resp["timeUsedMs"]) - float(p.get("compile", 0.0))
                - float(p.get("reduce", 0.0))
                - float(resp.get("queueWaitMs", 0.0))
                - float(resp.get("deviceFetchMs", 0.0)))
    return _mean_of(ctx, pick)


def pipeline_queue_wait_ms(ctx):
    return _mean_of(ctx, lambda r: float(r["queueWaitMs"])
                    if "queueWaitMs" in r else None)


def pipeline_mean_batch(ctx):
    c = ctx["counters"]
    return c["dispatched"] / c["batches"] if c.get("batches") else None


def pipeline_host_answered_share(ctx):
    n = len(_responses(ctx))
    return 100.0 * ctx["counters"].get("fallbacks", 0) / n if n else None


def executor_device_sync_ms(ctx):
    return _mean_of(ctx, lambda r: float(r["deviceFetchMs"])
                    if "deviceFetchMs" in r else None)


def kernels_hbm_roofline(ctx):
    """Sum of the least bytes each template must read / peak HBM bytes/s, over
    the device-busy time of the same templates each sent once alone."""
    solo = [s for s in ctx.get("solo") or [] if s["busy_s"] > 0]
    if not solo or not ctx.get("peaks"):
        return None
    least_s = sum(s["least_bytes"] for s in solo) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / sum(s["busy_s"] for s in solo)


def device_idle_share(ctx):
    t = ctx.get("trace")
    return t["idle_share"] if t else None


def least_bytes(spec: dict, config: dict) -> int:
    """The least a scan of the columns this template reads must move: rows x
    the narrowest of 1, 2 or 4 bytes that holds each column's cardinality or
    value range. From the configuration file alone."""
    by_name = {c["name"]: c for c in config["schema"]}
    total = 0
    for col in reference.columns_read(spec):
        c = by_name[col]
        span = c.get("cardinality") or (c["max"] - c["min"] + 1)
        total += 1 if span <= 1 << 8 else 2 if span <= 1 << 16 else 4
    return total * int(config["rows"])
