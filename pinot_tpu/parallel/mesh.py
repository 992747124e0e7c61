"""Device mesh helpers: the 1-D segment mesh plus chip-aware placement.

Placement policy (README "Multi-chip execution"): segments are assigned to
device slots LPT-style — sorted by descending doc count, each segment goes to
the least-loaded device that still has a free slot. Per-device capacity is
bounded at `s_pad / n_devices` so the shard_map block stays rectangular; the
residual imbalance (the biggest device's doc load over the mean) is what
`deviceSkewPct` reports, since the slowest chip bounds every collective.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax

SEGMENT_AXIS = "seg"


def pad_slots(n_segments: int, n_devices: int) -> int:
    """Slot count for a stacked segment block: per-device slots quantized to
    the next power of two on multi-device meshes, so ragged segment-count
    tails share a compile-cache bucket (log2 variants) instead of retracing
    the shard kernel per distinct count. Single-device blocks keep the exact
    count — there is no cross-device rectangularity to buy and padding slots
    would only add masked scan rows."""
    per = -(-n_segments // n_devices)
    if n_devices > 1 and per > 1:
        per = 1 << (per - 1).bit_length()
    return per * n_devices


def placement_slots(seg_docs: Sequence[int], s_pad: int, n_devices: int
                    ) -> Tuple[List[int], List[int]]:
    """LPT assignment of segments to block slots.

    Returns (slots, loads): `slots[i]` is segment i's row in the stacked
    [s_pad, rows] block (slot // (s_pad/n_devices) is its device), `loads[d]`
    the total docs device d scans. Biggest segments place first onto the
    least-loaded device with free capacity, so an uneven set (one fat segment
    + many small ones) doesn't serialize the mesh behind one chip."""
    k = max(s_pad // max(n_devices, 1), 1)
    order = sorted(range(len(seg_docs)), key=lambda i: (-seg_docs[i], i))
    loads = [0] * n_devices
    used = [0] * n_devices
    slots = [0] * len(seg_docs)
    for i in order:
        free = [d for d in range(n_devices) if used[d] < k]
        d = min(free, key=lambda d: (loads[d], d))
        slots[i] = d * k + used[d]
        used[d] += 1
        loads[d] += int(seg_docs[i])
    return slots, loads


def skew_pct(loads: Sequence[int]) -> float:
    """Percent by which the most-loaded device exceeds the mean load (0 for a
    perfectly balanced or empty mesh) — the per-launch `deviceSkewPct`."""
    total = sum(loads)
    if not loads or total <= 0:
        return 0.0
    mean = total / len(loads)
    return (max(loads) / mean - 1.0) * 100.0


def default_mesh(n_devices: Optional[int] = None, axis: str = SEGMENT_AXIS) -> jax.sharding.Mesh:
    """1-D mesh over available devices; the single parallel axis is segment scatter
    (the reference's only data-parallel dimension — SURVEY.md §2.11 row 'DP')."""
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"requested {n} devices, only {len(devices)} available")
    # Auto axis type: the kernels are plain jitted code over mesh-placed arrays
    # and leave sharding propagation to the compiler; jax 0.9's default
    # (Explicit) would type-check every gather/slice of a sharded dim instead.
    return jax.make_mesh((n,), (axis,), devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,))
