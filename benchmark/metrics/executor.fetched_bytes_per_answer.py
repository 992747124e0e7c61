"""executor.fetched_bytes_per_answer: what it reads is in the `.json` beside
it. None where the program has no `sparseGroupByLaunches` counter (a program
that puts no fetched bytes on a served answer) or no answer arrived."""

import statistics


def read(ctx):
    if "sparseGroupByLaunches" not in ctx["counters"]:
        return None
    vals = [float(r["response"]["bytesFetched"]) for r in ctx["records"]
            if r.get("response") and "bytesFetched" in r["response"]]
    return statistics.fmean(vals) if vals else None
