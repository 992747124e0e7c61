"""Test harness config: force a virtual 8-device CPU mesh before jax initializes.

Real multi-chip hardware is unavailable in CI; sharding/collective paths are validated on
XLA's host-platform virtual devices (the analog of the reference's single-JVM cluster tests,
`pinot-integration-test-base/.../ClusterTest.java:88` — no real cluster needed anywhere).
"""

import os
import sys



def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 `-m 'not slow'` run")


# Force the CPU backend with 8 virtual devices (jax backend init is lazy, so
# setting the environment here, before any test imports run jax ops, is enough).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from pinot_tpu.schema import (DataType, Schema, date_time, dimension, metric)  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def ssb_schema():
    """A Star-Schema-Benchmark-flavored lineorder schema used across tests."""
    return Schema("lineorder", [
        dimension("lo_orderkey", DataType.LONG),
        dimension("lo_custkey", DataType.INT),
        dimension("lo_region", DataType.STRING),
        dimension("lo_category", DataType.STRING),
        dimension("lo_brand", DataType.STRING),
        date_time("lo_orderdate", DataType.INT),  # yyyymmdd int like SSB
        metric("lo_quantity", DataType.INT),
        metric("lo_extendedprice", DataType.DOUBLE),
        metric("lo_discount", DataType.INT),
        metric("lo_revenue", DataType.DOUBLE),
    ])


REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
CATEGORIES = [f"MFGR#{i}" for i in range(1, 6)]
BRANDS = [f"MFGR#{i}{j}" for i in range(1, 6) for j in range(1, 9)]


def make_ssb_columns(rng, n):
    """Generate random SSB-like lineorder data as a column dict."""
    return {
        "lo_orderkey": rng.integers(1, 10_000_000, n, dtype=np.int64),
        "lo_custkey": rng.integers(1, 30_000, n, dtype=np.int32),
        "lo_region": [REGIONS[i] for i in rng.integers(0, len(REGIONS), n)],
        "lo_category": [CATEGORIES[i] for i in rng.integers(0, len(CATEGORIES), n)],
        "lo_brand": [BRANDS[i] for i in rng.integers(0, len(BRANDS), n)],
        "lo_orderdate": (19920101 + rng.integers(0, 7, n) * 10000
                         + rng.integers(1, 13, n) * 100 + rng.integers(1, 29, n)).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n, dtype=np.int32),
        "lo_extendedprice": np.round(rng.uniform(1.0, 10_000.0, n), 2),
        "lo_discount": rng.integers(0, 11, n, dtype=np.int32),
        "lo_revenue": np.round(rng.uniform(1.0, 60_000.0, n), 2),
    }


@pytest.fixture(scope="session")
def ssb_segment_dir(tmp_path_factory, rng, ssb_schema):
    """One built SSB segment on disk, shared across the test session."""
    from pinot_tpu.segment import SegmentBuilder, SegmentGeneratorConfig
    cols = make_ssb_columns(rng, 4096)
    builder = SegmentBuilder(ssb_schema, SegmentGeneratorConfig(
        inverted_index_columns=["lo_region", "lo_category"],
        range_index_columns=["lo_discount"],
        bloom_filter_columns=["lo_brand"],
    ))
    out = tmp_path_factory.mktemp("segments")
    return builder.build(cols, str(out), "lineorder_0"), cols


def wait_until(fn, timeout: float = 20.0, interval: float = 0.2,
               swallow: tuple = (Exception,)) -> bool:
    """Poll until fn() is truthy (catalog-mirror convergence etc.); exceptions
    in `swallow` count as not-yet (transient 500s during convergence)."""
    import time as _t
    deadline = _t.time() + timeout
    while _t.time() < deadline:
        try:
            if fn():
                return True
        except swallow:
            pass
        _t.sleep(interval)
    return False
