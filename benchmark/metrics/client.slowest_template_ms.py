"""client.slowest_template_ms: the largest of the templates' median latencies
over the window. A template needs three answers in the window to count."""

import statistics


def read(ctx):
    by_template = {}
    for r in ctx["records"]:
        by_template.setdefault(ctx["templates"][r["pool"]], []).append(
            r["latency_ms"])
    medians = [statistics.median(v) for v in by_template.values()
               if len(v) >= 3]
    return max(medians) if medians else None
