"""mesh.devices_busy: what it reads is in the `.json` beside it.
None where the run has no trace or the trace no device plane."""


def read(ctx):
    return (ctx.get("trace") or {}).get("devices") or None
