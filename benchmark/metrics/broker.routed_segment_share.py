"""broker.routed_segment_share: what it reads is in the `.json` beside it.
None where no answer of the window carries the broker's two fields."""

from benchmark.harness import readers


def read(ctx):
    queried = pruned = 0
    seen = False
    for r in readers._responses(ctx):
        resp = r["response"]
        if "numSegmentsQueried" not in resp:
            continue
        seen = True
        queried += int(resp["numSegmentsQueried"])
        pruned += int(resp.get("numSegmentsPruned", 0))
    if not seen or not queried + pruned:
        return None
    return 100.0 * queried / (queried + pruned)
