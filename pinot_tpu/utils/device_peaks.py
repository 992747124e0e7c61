"""Published per-chip peaks, keyed by jax `device_kind` — the one table the
roofline denominator (engine/kernels.py) reads. A device that is not in the
table is an error, not a default: a roofline share against another part's
bandwidth is a wrong number that looks right.

Source: Google Cloud documentation, "TPU v5e" (16 GB of HBM at 819 GB/s per
chip). jax reports that chip's `device_kind` as "TPU v5 lite".
"""

from __future__ import annotations

from typing import Dict

#: device_kind -> {"hbm_gbps": peak HBM bandwidth GB/s, "hbm_bytes": HBM size}
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "hbm_bytes": 16e9},
}

#: what the CPU backend (tests, rehearsals) models: the v5e the code targets,
#: so modeled roofline percentages stay comparable across CPU runs
_CPU_MODELS = "TPU v5 lite"


def device_peak() -> Dict[str, float]:
    """Peaks of the device this process runs on. The CPU backend models the
    v5e; any other backend with a `device_kind` missing from DEVICE_PEAKS
    raises."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return DEVICE_PEAKS[_CPU_MODELS]
    peak = DEVICE_PEAKS.get(dev.device_kind)
    if peak is None:
        raise RuntimeError(
            f"unknown device kind {dev.device_kind!r} on platform "
            f"{dev.platform!r}: add its published peaks to "
            "pinot_tpu/utils/device_peaks.py (known: "
            f"{sorted(DEVICE_PEAKS)})")
    return peak
