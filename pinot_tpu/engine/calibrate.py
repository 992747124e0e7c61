"""Calibrated kernel-regime caps: measured crossovers instead of baked constants.

The group-by dispatch ladder in `engine/kernels.py` picks between four
formulations — skinny one-hot matmul, chunked 64x64-tile matmul
(`_grouped_chunk64`), the radix/rank-partitioned sort kernel
(`_grouped_partitioned`), and the pure sort + segmented-scan kernel
(`_grouped_sorted`) — by comparing the padded key count against caps. The
historical constants (`MATMUL_KEY_CAP`, `CHUNK_KEY_CAP`) were measured on ONE
TPU generation (v5e) and silently mis-dispatch on
anything else. This module owns those caps:

    caps = get_caps()                # resolved once per process, cached
    caps.matmul_cap                  # skinny matmul  -> chunked crossover
    caps.chunk_cap                   # chunked matmul -> sort-based crossover
    caps.high_card_regime            # "partitioned" | "sorted" | "scatter"

Resolution order (later wins):
    1. built-in defaults (the measured v5e numbers);
    2. a persisted calibration cache (JSON keyed by backend + device kind),
       ignored wholesale if malformed or out of range;
    3. a fresh micro-bench when PINOT_TPU_CALIBRATE=1 (persisted back to the
       cache);
    4. explicit env overrides (PINOT_TPU_MATMUL_CAP / PINOT_TPU_CHUNK_CAP /
       PINOT_TPU_GROUPBY_REGIME / PINOT_TPU_MINMAX_BCAST_CAP /
       PINOT_TPU_PARTITION_BLOCK).

`KernelSpec.signature()` folds `get_caps().token()` into the jit cache key, so
`set_caps()` (tests, bench regime forcing) recompiles instead of silently
reusing kernels built under different caps.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import asdict, dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

HIGH_CARD_REGIMES = ("partitioned", "sorted", "scatter")

# caps the cache validator accepts; anything outside means a stale/corrupt
# cache (or one written by a different build) and falls back to defaults
_MATMUL_CAP_RANGE = (64, 1 << 14)
_CHUNK_CAP_RANGE = (4096, 1 << 22)
_BCAST_CAP_RANGE = (64, 1 << 16)
_BLOCK_RANGE = (256, 1 << 16)
_FUSED_LUT_CAP_RANGE = (64, 1 << 22)

CACHE_ENV = "PINOT_TPU_CALIBRATE_CACHE"
_DEFAULT_CACHE = os.path.join("~", ".cache", "pinot_tpu", "kernel_caps.json")


@dataclass(frozen=True)
class KernelCaps:
    """Regime-crossover caps for the fused group-by kernels."""

    matmul_cap: int = 512        # skinny one-hot matmul up to here
    chunk_cap: int = 131072      # chunked 64x64 matmul up to here
    minmax_bcast_cap: int = 1024  # broadcast-reduce min/max up to here
    high_card_regime: str = "partitioned"  # above chunk_cap
    partition_block: int = 4096  # sorted-rank block length (multiple of 64)
    # bitmap-vs-gather filter regime: a dict-column filter leaf takes the
    # packed-word bitmap path when its estimated selectivity (matched docs /
    # docs) is at or below this fraction; denser predicates keep the
    # interval-compare / one-hot LUT path
    bitmap_sel_cap: float = 0.25
    # fused-vs-staged execution regime (PR 16): when enabled, eligible plans
    # decode compressed forms (dict-id LUT gather, FOR base+delta) inside the
    # single fused kernel instead of staging decoded columns through HBM.
    # fused_lut_cap bounds the decode-table length (padded entries) a fused
    # plan may gather from in-kernel; columns with larger dictionaries fall
    # back to the staged two-launch ladder.
    fused_enabled: bool = True
    fused_lut_cap: int = 1 << 16
    # device hash-join regime split (PR 17): a single-integer-key build side
    # whose value span fits under this many direct-address slots takes the
    # scatter-table probe (one gather launch, at most one match per probe
    # row); wider/duplicate-key builds take the sort-merge probe ladder.
    join_scatter_cap: int = 1 << 20
    source: str = "default"      # default | cache | calibrated | env

    def token(self) -> Tuple:
        """The part of the caps that changes compiled kernels (jit cache key)."""
        return (self.matmul_cap, self.chunk_cap, self.minmax_bcast_cap,
                self.high_card_regime, self.partition_block,
                self.bitmap_sel_cap, self.fused_enabled, self.fused_lut_cap,
                self.join_scatter_cap)


_ACTIVE: Optional[KernelCaps] = None


def _valid(caps: KernelCaps) -> bool:
    try:
        return (_MATMUL_CAP_RANGE[0] <= int(caps.matmul_cap) <= _MATMUL_CAP_RANGE[1]
                and _CHUNK_CAP_RANGE[0] <= int(caps.chunk_cap) <= _CHUNK_CAP_RANGE[1]
                and _BCAST_CAP_RANGE[0] <= int(caps.minmax_bcast_cap)
                <= _BCAST_CAP_RANGE[1]
                and _BLOCK_RANGE[0] <= int(caps.partition_block) <= _BLOCK_RANGE[1]
                and int(caps.partition_block) % 64 == 0
                and 0.0 < float(caps.bitmap_sel_cap) <= 1.0
                and isinstance(caps.fused_enabled, bool)
                and _FUSED_LUT_CAP_RANGE[0] <= int(caps.fused_lut_cap)
                <= _FUSED_LUT_CAP_RANGE[1]
                and (1 << 10) <= int(caps.join_scatter_cap) <= (1 << 26)
                and caps.high_card_regime in HIGH_CARD_REGIMES)
    except (TypeError, ValueError):
        return False


def platform_key() -> str:
    """Cache key: caps measured on one platform must not leak onto another."""
    import jax
    return f"{jax.default_backend()}:{jax.devices()[0].device_kind}"


def cache_path() -> str:
    return os.path.expanduser(os.environ.get(CACHE_ENV, _DEFAULT_CACHE))


def load_cached_caps(path: Optional[str] = None,
                     key: Optional[str] = None) -> Optional[KernelCaps]:
    """Caps persisted by a previous calibration run, or None (missing file,
    unreadable JSON, unknown platform, out-of-range values — all fall back)."""
    path = path or cache_path()
    key = key or platform_key()
    try:
        with open(path) as f:
            blob = json.load(f)
        entry = blob[key]
        caps = KernelCaps(
            matmul_cap=int(entry["matmul_cap"]),
            chunk_cap=int(entry["chunk_cap"]),
            minmax_bcast_cap=int(entry["minmax_bcast_cap"]),
            high_card_regime=str(entry["high_card_regime"]),
            partition_block=int(entry["partition_block"]),
            # absent in caches written before the bitmap filter regime existed
            bitmap_sel_cap=float(entry.get("bitmap_sel_cap",
                                           KernelCaps.bitmap_sel_cap)),
            # absent in caches written before the fused execution regime
            fused_enabled=bool(entry.get("fused_enabled",
                                         KernelCaps.fused_enabled)),
            fused_lut_cap=int(entry.get("fused_lut_cap",
                                        KernelCaps.fused_lut_cap)),
            # absent in caches written before the device hash-join regime
            join_scatter_cap=int(entry.get("join_scatter_cap",
                                           KernelCaps.join_scatter_cap)),
            source="cache")
    except Exception:
        return None
    return caps if _valid(caps) else None


def save_cached_caps(caps: KernelCaps, path: Optional[str] = None,
                     key: Optional[str] = None) -> None:
    path = path or cache_path()
    key = key or platform_key()
    blob: Dict[str, dict] = {}
    try:
        with open(path) as f:
            loaded = json.load(f)
        if isinstance(loaded, dict):
            blob = loaded
    # graftcheck: ignore[exception-hygiene] -- a missing/corrupt cache file
    # just means a fresh blob; the save below rewrites it
    except Exception:
        pass
    entry = asdict(caps)
    entry.pop("source", None)
    blob[key] = entry
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(blob, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def _env_overrides(caps: KernelCaps) -> KernelCaps:
    def _int(name):
        v = os.environ.get(name)
        return int(v) if v else None

    changed = {}
    for field_name, env in (("matmul_cap", "PINOT_TPU_MATMUL_CAP"),
                            ("chunk_cap", "PINOT_TPU_CHUNK_CAP"),
                            ("minmax_bcast_cap", "PINOT_TPU_MINMAX_BCAST_CAP"),
                            ("partition_block", "PINOT_TPU_PARTITION_BLOCK")):
        v = _int(env)
        if v is not None:
            changed[field_name] = v
    regime = os.environ.get("PINOT_TPU_GROUPBY_REGIME")
    if regime:
        changed["high_card_regime"] = regime
    sel = os.environ.get("PINOT_TPU_BITMAP_SEL_CAP")
    if sel:
        changed["bitmap_sel_cap"] = float(sel)
    fused = os.environ.get("PINOT_TPU_FUSED")
    if fused:
        changed["fused_enabled"] = fused not in ("0", "false", "no")
    lut_cap = _int("PINOT_TPU_FUSED_LUT_CAP")
    if lut_cap is not None:
        changed["fused_lut_cap"] = lut_cap
    if not changed:
        return caps
    out = replace(caps, source="env", **changed)
    if not _valid(out):
        raise ValueError(f"invalid kernel-caps env override: {changed}")
    return out


def get_caps() -> KernelCaps:
    """The process-wide caps, resolved lazily on first kernel build."""
    global _ACTIVE
    if _ACTIVE is None:
        caps = load_cached_caps() or KernelCaps()
        if os.environ.get("PINOT_TPU_CALIBRATE") == "1":
            # asked for explicitly: a failed calibration raises
            caps = calibrate()
            save_cached_caps(caps)
        _ACTIVE = _env_overrides(caps)
    return _ACTIVE


def set_caps(caps: Optional[KernelCaps]) -> KernelCaps:
    """Install caps explicitly (None re-resolves lazily). Flushes the compiled
    kernel caches: a cap change changes dispatch, and `KernelSpec.signature()`
    only protects NEW lookups, not memory held by stale entries."""
    global _ACTIVE
    if caps is not None and not _valid(caps):
        raise ValueError(f"invalid kernel caps: {caps}")
    _ACTIVE = caps
    from . import kernels
    kernels._KERNEL_CACHE.clear()
    try:
        from ..parallel import combine
        combine._SHARD_KERNEL_CACHE.clear()
    # graftcheck: ignore[exception-hygiene] -- the parallel package is an
    # optional import here; no cache to flush means nothing stale to keep
    except Exception:
        pass
    return get_caps() if caps is None else caps


# -- micro-benchmark --------------------------------------------------------

def _bench_once(fn, args) -> float:
    """Best-of-2 wall time with a warmup run (compile + first dispatch)."""
    import jax
    # graftcheck: ignore[jit-fetch-site] -- a micro-benchmark MUST sync to
    # measure wall time; calibration runs offline, never on the query path
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        # graftcheck: ignore[jit-fetch-site] -- timed sync is the measurement
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _regime_runners(nseg: int, block: int):
    """jit'd (key, val) -> outputs per regime, for one padded key count."""
    import jax
    import jax.numpy as jnp

    from . import kernels

    def matmul(key, val):
        return kernels._onehot_sums(key, nseg, [jnp.ones_like(val), val])

    def chunk(key, val):
        return kernels._grouped_chunk64(key, nseg, [jnp.ones_like(val)], [val])

    def partitioned(key, val):
        return kernels._grouped_partitioned(key, nseg, [val], block)

    def sorted_(key, val):
        return kernels._grouped_sorted(key, nseg, [val], block)

    def scatter(key, val):
        return (jax.ops.segment_sum(jnp.ones_like(val), key, num_segments=nseg),
                jax.ops.segment_sum(val, key, num_segments=nseg))

    return {"matmul": jax.jit(matmul), "chunk": jax.jit(chunk),
            "partitioned": jax.jit(partitioned), "sorted": jax.jit(sorted_),
            "scatter": jax.jit(scatter)}


def _pad_keys(k: int) -> int:
    """Mirror build_device_geometry's padding so measurements hit the same
    compiled shapes queries will."""
    if k <= 4096:
        return 1 << max(0, (k - 1)).bit_length()
    return -(-k // 4096) * 4096


def calibrate(rows: Optional[int] = None,
              key_grid: Optional[Sequence[int]] = None,
              block: int = 4096) -> KernelCaps:
    """Micro-bench the four group-by regimes and return measured crossovers.

    `rows` defaults to PINOT_TPU_CALIBRATE_ROWS (or 2^22); `key_grid` to
    PINOT_TPU_CALIBRATE_KEYS (comma list) or a ladder spanning every regime
    boundary. Timings use count+sum over a uniform key column — the bench's
    very_high_card shape.
    """
    import jax.numpy as jnp
    import numpy as np

    if rows is None:
        rows = int(os.environ.get("PINOT_TPU_CALIBRATE_ROWS", 1 << 22))
    if key_grid is None:
        env = os.environ.get("PINOT_TPU_CALIBRATE_KEYS")
        key_grid = ([int(x) for x in env.split(",") if x.strip()] if env
                    else [256, 512, 1024, 2048, 8192, 32768, 131072, 262144])
    key_grid = sorted({_pad_keys(k) for k in key_grid})

    rng = np.random.default_rng(0)
    times: Dict[int, Dict[str, float]] = {}
    for nseg in key_grid:
        # graftcheck: ignore[memory-untracked-staging] -- calibration
        # micro-bench inputs: freed when the sweep iteration ends, never
        # part of serving residency
        key = jnp.asarray(rng.integers(0, nseg, rows).astype(np.int32))
        val = jnp.asarray(rng.uniform(-1000, 1000, rows).astype(np.float32))  # graftcheck: ignore[memory-untracked-staging] -- calibration bench data, see above
        runners = _regime_runners(nseg, block)
        t: Dict[str, float] = {}
        for name, fn in runners.items():
            if name == "matmul" and nseg > _MATMUL_CAP_RANGE[1]:
                continue  # a dense [2, N]@[N, 256k] trace is pointless work
            try:
                t[name] = _bench_once(fn, (key, val))
            # graftcheck: ignore[exception-hygiene] -- a kernel candidate
            # that cannot run on this backend simply leaves the race; its
            # absence from `t` is the observable record
            except Exception:
                continue
        times[nseg] = t

    def best_high_card(t: Dict[str, float]) -> Tuple[str, float]:
        cands = [(t[r], r) for r in HIGH_CARD_REGIMES if r in t]
        c, r = min(cands) if cands else (float("inf"), "partitioned")
        return r, c

    # crossover caps: the largest measured size where the cheaper regime still
    # wins; the cap then extends halfway (geometrically) to the next grid point
    defaults = KernelCaps()
    matmul_cap, chunk_cap = 0, 0
    for nseg in key_grid:
        t = times[nseg]
        _, hc = best_high_card(t)
        if "matmul" in t and t["matmul"] <= min(t.get("chunk", float("inf")), hc):
            matmul_cap = nseg
        if "chunk" in t and t["chunk"] <= hc:
            chunk_cap = max(chunk_cap, nseg)
    regime, _ = best_high_card(times[key_grid[-1]])

    # fused-vs-staged probe: masked sum with an in-kernel dict decode (LUT
    # gather) vs the same sum over a pre-decoded column. Fusion also saves a
    # dispatch and the decoded column's residency, so the gather form gets
    # 2x slack before the ladder falls back to staged (a platform whose
    # device gathers are pathologically slow is the case this probe exists
    # to catch). It times a 4,096-entry table, i.e. the gather that tables
    # over `kernels.SELECT_DECODE_CAP` keep, and has never run on the v5e:
    # there that gather is a pass of its own at ~100M rows/s (PR 26's
    # traces; 208 ms at 16Mi rows against 0.7 ms for the decoded column, my
    # chip run, PR 27), so this probe would read "staged" for every fused
    # plan, the select-decoded small tables included. Re-aiming it is
    # ROADMAP D5's.
    fused_enabled = defaults.fused_enabled
    try:
        import jax
        card = 4096
        ids_np = rng.integers(0, card, rows).astype(np.int32)
        lut_np = rng.uniform(-1e3, 1e3, card).astype(np.float32)
        # graftcheck: ignore[memory-untracked-staging] -- calibration probe
        # inputs: freed after the probe, never part of serving residency
        ids = jnp.asarray(ids_np)
        lut = jnp.asarray(lut_np)  # graftcheck: ignore[memory-untracked-staging] -- calibration probe data, see above
        fmask = jnp.asarray((rng.random(rows) < 0.5).astype(np.float32))  # graftcheck: ignore[memory-untracked-staging] -- calibration probe data, see above
        decoded = jnp.asarray(lut_np[ids_np])  # graftcheck: ignore[memory-untracked-staging] -- calibration probe data, see above
        t_fused = _bench_once(jax.jit(lambda i, t, m: (t[i] * m).sum()),
                              (ids, lut, fmask))
        t_staged = _bench_once(jax.jit(lambda v, m: (v * m).sum()),
                               (decoded, fmask))
        fused_enabled = bool(t_fused <= t_staged * 2.0)
    # graftcheck: ignore[exception-hygiene] -- probe is best-effort; the
    # default (fused on, CPU/TPU-measured) still dispatches correctly
    except Exception:
        pass

    caps = KernelCaps(
        matmul_cap=int(np.clip(matmul_cap or defaults.matmul_cap,
                               *_MATMUL_CAP_RANGE)),
        chunk_cap=int(np.clip(-(-max(chunk_cap, 4096) // 4096) * 4096,
                              *_CHUNK_CAP_RANGE)),
        minmax_bcast_cap=defaults.minmax_bcast_cap,
        high_card_regime=regime,
        partition_block=block,
        fused_enabled=fused_enabled,
        source="calibrated")
    return caps if _valid(caps) else defaults
