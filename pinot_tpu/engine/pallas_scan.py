"""Pallas TPU scan kernel: the fused filter+aggregate hot loop, hand-tiled.

The default engine path (`engine/kernels.py`) expresses the per-segment scan
as one jit program and lets XLA fuse it; this module is the SAME masked
multi-sum scan written as an explicit Pallas kernel — VMEM-resident row
blocks walked by a 1-D grid, per-block partials in lane-aligned (8, 128)
tiles, cross-block reduce outside.

NOT ON THE SERVED PATH: nothing in the engine imports this module; XLA's
fusion of `engine/kernels.py` is what serves queries. It is kept as the
foundation for hand-scheduled scans. No timing of it on the directly attached
chip exists yet; `python -m pinot_tpu.engine.pallas_scan` times both variants
on the current device.

Correctness is pinned by `tests/test_pallas_scan.py` in interpret mode (CPU);
`tests/test_chip_compile.py` compiles both kernels for a described v5e.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 15   # VMEM row-block (32k rows x ~5 cols x 4B ≈ 640KB)


def masked_sums_pallas(mask_cols: Sequence[jnp.ndarray],
                       thresholds,
                       sum_rows: Sequence[jnp.ndarray],
                       block_rows: int = BLOCK_ROWS,
                       interpret: bool = False) -> jnp.ndarray:
    """sum_j(sum_rows[j] * mask) for mask = AND of range predicates.

    `mask_cols` = [od, disc, qty]-style i32 columns; `thresholds` = for each
    column a (lo, hi) inclusive band (use INT32_MIN/MAX for one-sided);
    `sum_rows` = float32 rows to sum under the mask. All columns must share
    one length that is a multiple of `block_rows` (the caller pads — the
    engine's datablocks already are). Returns float32[len(sum_rows) + 1]:
    the sums followed by the mask count."""
    from jax.experimental import pallas as pl

    n = int(mask_cols[0].shape[0])
    if n % block_rows:
        raise ValueError(f"rows {n} not a multiple of block {block_rows}")
    grid = n // block_rows
    n_mask = len(mask_cols)
    n_sums = len(sum_rows)
    bands = np.asarray(thresholds, dtype=np.int32).reshape(n_mask, 2)

    def kernel(*refs):
        ins = refs[:-1]
        o_ref = refs[-1]
        m = None
        for c in range(n_mask):
            col = ins[c][...]
            leaf = (col >= bands[c, 0]) & (col <= bands[c, 1])
            m = leaf if m is None else (m & leaf)
        fm = m.astype(jnp.float32)
        partials: List[jnp.ndarray] = []
        for j in range(n_sums):
            partials.append((ins[n_mask + j][...] * fm).sum())
        partials.append(fm.sum())
        # lane-aligned (8, 128) partial tile; scalar scatter is not lowerable
        # on TPU, so the tile is built with iota masks
        row = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
        tile = jnp.zeros((8, 128), dtype=jnp.float32)
        for j, s in enumerate(partials):
            tile = tile + jnp.where((row == 0) & (col == j), s, 0.0)
        o_ref[...] = tile

    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows,), lambda i: (i,))
                  for _ in range(n_mask + n_sums)],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * 8, 128), jnp.float32),
        interpret=interpret,
    )(*mask_cols, *sum_rows)
    return out.reshape(grid, 8, 128).sum(axis=0)[0, :n_sums + 1]


def masked_sums_pallas_fused(id_cols: Sequence[jnp.ndarray],
                             id_bands,
                             for_rows: Sequence[Tuple[float, jnp.ndarray]],
                             block_rows: int = BLOCK_ROWS,
                             interpret: bool = False) -> jnp.ndarray:
    """`masked_sums_pallas` operating directly on COMPRESSED resident forms.

    The filter runs on dictionary ids (`id_cols`, i32) with each predicate
    pre-translated to an inclusive id band — ordered dictionaries make value
    ranges id ranges (engine/predicate.py), so no decode precedes the mask.
    Each sum operand arrives frame-of-reference encoded as `(base, deltas)`:
    the kernel computes `base + delta` in-register AFTER the VMEM load, so
    the HBM stream is the narrow delta column and a decoded float column is
    never materialized. Bases ride the trace as compile-time constants (the
    engine keys its jit cache on the spec signature, scalars on iscal — here
    the harness recompiles per base set, fine for bench shapes).

    Caller contract: id padding must fall OUTSIDE every band (the engine
    pads with `cardinality`, which no band contains), so padding rows zero
    out of the mask and the decoded-base padding values never count.
    Returns float32[len(for_rows) + 1]: the sums followed by the mask count.

    Narrow delta dtypes (uint8/uint16) lower on current TPU Pallas via an
    in-kernel upcast; `interpret=True` runs the same program on CPU for the
    correctness suite."""
    from jax.experimental import pallas as pl

    n = int(id_cols[0].shape[0])
    if n % block_rows:
        raise ValueError(f"rows {n} not a multiple of block {block_rows}")
    grid = n // block_rows
    n_mask = len(id_cols)
    n_sums = len(for_rows)
    bands = np.asarray(id_bands, dtype=np.int32).reshape(n_mask, 2)
    bases = [float(b) for b, _ in for_rows]
    deltas = [d for _, d in for_rows]

    def kernel(*refs):
        ins = refs[:-1]
        o_ref = refs[-1]
        m = None
        for c in range(n_mask):
            ids = ins[c][...]
            leaf = (ids >= bands[c, 0]) & (ids <= bands[c, 1])
            m = leaf if m is None else (m & leaf)
        fm = m.astype(jnp.float32)
        partials: List[jnp.ndarray] = []
        for j in range(n_sums):
            # in-register FOR decode: the only float-width copy of this
            # column ever built is this VMEM block
            fv = ins[n_mask + j][...].astype(jnp.float32) + bases[j]
            partials.append((fv * fm).sum())
        partials.append(fm.sum())
        row = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
        tile = jnp.zeros((8, 128), dtype=jnp.float32)
        for j, s in enumerate(partials):
            tile = tile + jnp.where((row == 0) & (col == j), s, 0.0)
        o_ref[...] = tile

    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows,), lambda i: (i,))
                  for _ in range(n_mask + n_sums)],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * 8, 128), jnp.float32),
        interpret=interpret,
    )(*id_cols, *deltas)
    return out.reshape(grid, 8, 128).sum(axis=0)[0, :n_sums + 1]


def masked_sums_xla(mask_cols, thresholds, sum_rows) -> jnp.ndarray:
    """The XLA-fused reference implementation of the same contract."""
    bands = np.asarray(thresholds, dtype=np.int32).reshape(len(mask_cols), 2)
    m = None
    for c, col in enumerate(mask_cols):
        leaf = (col >= int(bands[c, 0])) & (col <= int(bands[c, 1]))
        m = leaf if m is None else (m & leaf)
    fm = m.astype(jnp.float32)
    return jnp.stack([(r * fm).sum() for r in sum_rows] + [fm.sum()])


def _bench() -> None:   # pragma: no cover - manual harness
    import time
    n = 1 << 23
    rng = np.random.default_rng(0)
    # graftcheck: ignore[memory-untracked-staging] -- manual bench harness:
    # synthetic inputs live only for this run, never enter serving residency
    od = jnp.asarray(rng.integers(19920101, 19990101, n), dtype=jnp.int32)
    disc = jnp.asarray(rng.integers(0, 11, n), dtype=jnp.int32)  # graftcheck: ignore[memory-untracked-staging] -- bench data, see above
    qty = jnp.asarray(rng.integers(1, 51, n), dtype=jnp.int32)  # graftcheck: ignore[memory-untracked-staging] -- bench data, see above
    price = jnp.asarray(rng.uniform(1, 10000, n), dtype=jnp.float32)  # graftcheck: ignore[memory-untracked-staging] -- bench data, see above
    rev = jnp.asarray(rng.uniform(1, 60000, n), dtype=jnp.float32)  # graftcheck: ignore[memory-untracked-staging] -- bench data, see above
    cols = (od, disc, qty)
    bands = [(19930101, 19931231), (1, 3), (-(1 << 31), 24)]
    rows = (price, rev)
    fx = lambda *a: masked_sums_xla(a[:3], bands, a[3:])   # noqa: E731
    fp = lambda *a: masked_sums_pallas(a[:3], bands, a[3:])  # noqa: E731
    # graftcheck: ignore[jit-fetch-site] -- standalone self-test compares
    # host-side results; not on the serving path
    a = jax.device_get(jax.jit(fx)(*cols, *rows))
    # graftcheck: ignore[jit-fetch-site] -- standalone self-test (see above)
    b = jax.device_get(jax.jit(fp)(*cols, *rows))
    print("match:", np.allclose(a, b, rtol=1e-3))
    for name, f in (("xla", fx), ("pallas", fp)):
        # each iteration is DATA-DEPENDENT on the previous result: a chain of
        # identical pure calls would be CSE'd by XLA into one computation and
        # a divide-by-iters would misreport per-scan cost ~10x
        def chain(od, disc, qty, price, rev, f=f):
            acc = jnp.float32(0)
            for _ in range(10):
                out = f(od + (acc * 0).astype(jnp.int32), disc, qty,
                        price, rev)
                acc = acc + out.sum()
            return acc
        g = jax.jit(chain)
        # graftcheck: ignore[jit-fetch-site] -- warmup sync of the benchmark
        jax.device_get(g(*cols, *rows))
        t0 = time.perf_counter()
        # graftcheck: ignore[jit-fetch-site] -- timed sync is the measurement
        jax.device_get(g(*cols, *rows))
        dt = (time.perf_counter() - t0) / 10
        print(f"{name}: {dt*1000:.2f} ms/scan ({n/dt/1e9:.1f}B rows/s, "
              f"incl. amortized round trip)")


if __name__ == "__main__":   # pragma: no cover
    _bench()
