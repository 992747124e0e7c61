"""The table of constants that chooses the kernel (engine/caps.py): what
`set_caps`, the tests' seam, refuses, and that a change of caps reaches the
jit cache key."""

from dataclasses import replace

import pytest

from pinot_tpu.engine import caps


@pytest.fixture
def restore_caps():
    prev = caps.get_caps()
    yield
    caps.set_caps(prev)


def test_invalid_set_caps_rejected(restore_caps):
    with pytest.raises(ValueError):
        caps.set_caps(caps.KernelCaps(partition_block=100))  # not %64
    assert caps.get_caps() == caps.KernelCaps()


def test_caps_change_kernel_signature(restore_caps):
    from pinot_tpu.engine.kernels import KernelSpec
    from pinot_tpu.query.predicate import FilterProgram

    spec = KernelSpec(FilterProgram(), ("k",), 8192, (), {}, 1024)
    sig_a = spec.signature()
    caps.set_caps(replace(caps.get_caps(), chunk_cap=4096))
    sig_b = spec.signature()
    assert sig_a != sig_b  # caps token folds into the jit cache key


def test_set_caps_flushes_the_kernel_caches(restore_caps):
    """Programs built under other caps are not kept: `set_caps` empties the
    single-device and the mesh kernel caches."""
    from pinot_tpu.engine import kernels
    from pinot_tpu.parallel import combine
    kernels._KERNEL_CACHE[("stale",)] = object()
    combine._SHARD_KERNEL_CACHE[("stale",)] = object()
    caps.set_caps(replace(caps.get_caps(), matmul_cap=256))
    assert not kernels._KERNEL_CACHE and not combine._SHARD_KERNEL_CACHE
