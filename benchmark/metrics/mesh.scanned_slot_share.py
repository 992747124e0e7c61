"""mesh.scanned_slot_share: what it reads is in the `.json` beside it. None
where the program has no such counters (PR 32's parent) or no launch was
built over a resident block."""


def read(ctx):
    c = ctx["counters"]
    if "scannedSlots" not in c or not c.get("residentSlots"):
        return None
    return 100.0 * c["scannedSlots"] / c["residentSlots"]
