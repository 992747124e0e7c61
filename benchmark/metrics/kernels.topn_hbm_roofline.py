"""kernels.topn_hbm_roofline: what it reads is in the `.json` beside it. None
where the run's mix has no `top_100_parts_details` template, or its solo
replay saw no device time or no peaks (a rehearsal)."""


def read(ctx):
    solo = [s for s in ctx.get("solo") or []
            if s["template"] == "top_100_parts_details" and s["busy_s"] > 0]
    if not solo or not ctx.get("peaks"):
        return None
    least_s = sum(s["least_bytes"] for s in solo) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / sum(s["busy_s"] for s in solo)
