"""Kernel-regime calibration: cache persistence, validation, resolution order.

The caps that drive the group-by dispatch ladder (engine/calibrate.py) resolve
from defaults -> persisted cache -> optional micro-bench -> env overrides.
A corrupt or out-of-range cache must fall back WHOLESALE to defaults: a bogus
chunk_cap would silently mis-dispatch every group-by in the process.
"""

import json

import pytest

from pinot_tpu.engine import calibrate as cal


@pytest.fixture
def restore_caps():
    prev = cal.get_caps()
    yield
    cal.set_caps(prev)


def _caps(**kw):
    base = dict(matmul_cap=256, chunk_cap=65536, minmax_bcast_cap=512,
                high_card_regime="sorted", partition_block=512,
                source="calibrated")
    base.update(kw)
    return cal.KernelCaps(**base)


def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "caps.json")
    caps = _caps()
    cal.save_cached_caps(caps, path=path, key="cpu:test")
    loaded = cal.load_cached_caps(path=path, key="cpu:test")
    assert loaded is not None
    assert loaded.source == "cache"
    assert loaded.token() == caps.token()
    # a second platform's entry coexists in the same file
    cal.save_cached_caps(_caps(chunk_cap=8192), path=path, key="tpu:v5e")
    assert cal.load_cached_caps(path=path, key="cpu:test").chunk_cap == 65536
    assert cal.load_cached_caps(path=path, key="tpu:v5e").chunk_cap == 8192


def test_cache_unknown_platform_falls_back(tmp_path):
    path = str(tmp_path / "caps.json")
    cal.save_cached_caps(_caps(), path=path, key="cpu:test")
    assert cal.load_cached_caps(path=path, key="tpu:v99") is None


def test_bogus_cache_falls_back(tmp_path):
    missing = str(tmp_path / "nope.json")
    assert cal.load_cached_caps(path=missing, key="cpu:test") is None

    garbage = tmp_path / "garbage.json"
    garbage.write_text("{this is not json")
    assert cal.load_cached_caps(path=str(garbage), key="cpu:test") is None

    wrong_shape = tmp_path / "shape.json"
    wrong_shape.write_text(json.dumps({"cpu:test": {"matmul_cap": "huge"}}))
    assert cal.load_cached_caps(path=str(wrong_shape), key="cpu:test") is None


def test_out_of_range_cache_falls_back(tmp_path):
    path = tmp_path / "range.json"
    path.write_text(json.dumps({"cpu:test": {
        "matmul_cap": 7,  # below the validator floor
        "chunk_cap": 65536, "minmax_bcast_cap": 512,
        "high_card_regime": "sorted", "partition_block": 512}}))
    assert cal.load_cached_caps(path=str(path), key="cpu:test") is None

    path.write_text(json.dumps({"cpu:test": {
        "matmul_cap": 256, "chunk_cap": 65536, "minmax_bcast_cap": 512,
        "high_card_regime": "warp_speed",  # unknown regime
        "partition_block": 512}}))
    assert cal.load_cached_caps(path=str(path), key="cpu:test") is None

    path.write_text(json.dumps({"cpu:test": {
        "matmul_cap": 256, "chunk_cap": 65536, "minmax_bcast_cap": 512,
        "high_card_regime": "sorted",
        "partition_block": 1000}}))  # not a multiple of 64
    assert cal.load_cached_caps(path=str(path), key="cpu:test") is None


def test_get_caps_reads_persisted_cache(tmp_path, monkeypatch, restore_caps):
    path = str(tmp_path / "caps.json")
    caps = _caps(chunk_cap=32768)
    cal.save_cached_caps(caps, path=path)  # current platform key
    monkeypatch.setenv(cal.CACHE_ENV, path)
    cal.set_caps(None)  # force lazy re-resolution through the cache
    got = cal.get_caps()
    assert got.token() == caps.token()
    assert got.source == "cache"


def test_get_caps_bogus_cache_uses_defaults(tmp_path, monkeypatch,
                                            restore_caps):
    garbage = tmp_path / "garbage.json"
    garbage.write_text("][")
    monkeypatch.setenv(cal.CACHE_ENV, str(garbage))
    cal.set_caps(None)
    got = cal.get_caps()
    assert got.token() == cal.KernelCaps().token()
    assert got.source == "default"


def test_requested_calibration_failure_raises(monkeypatch, restore_caps):
    """PINOT_TPU_CALIBRATE=1 asks for a calibration: a failed one is an error,
    not a silent return to the defaults."""
    def boom():
        raise RuntimeError("calibration failed")
    monkeypatch.setenv("PINOT_TPU_CALIBRATE", "1")
    monkeypatch.setattr(cal, "calibrate", boom)
    with pytest.raises(RuntimeError, match="calibration failed"):
        cal.set_caps(None)  # re-resolves through get_caps()


def test_env_override_wins_over_cache(tmp_path, monkeypatch, restore_caps):
    path = str(tmp_path / "caps.json")
    cal.save_cached_caps(_caps(), path=path)
    monkeypatch.setenv(cal.CACHE_ENV, path)
    monkeypatch.setenv("PINOT_TPU_GROUPBY_REGIME", "partitioned")
    monkeypatch.setenv("PINOT_TPU_CHUNK_CAP", "8192")
    cal.set_caps(None)
    got = cal.get_caps()
    assert got.source == "env"
    assert got.high_card_regime == "partitioned"
    assert got.chunk_cap == 8192
    assert got.matmul_cap == 256  # untouched fields keep the cache values


def test_invalid_set_caps_rejected(restore_caps):
    with pytest.raises(ValueError):
        cal.set_caps(cal.KernelCaps(partition_block=100))  # not %64
    with pytest.raises(ValueError):
        cal.set_caps(cal.KernelCaps(high_card_regime="nope"))


def test_caps_change_kernel_signature(restore_caps):
    from pinot_tpu.engine.kernels import KernelSpec
    from pinot_tpu.query.predicate import FilterProgram

    spec = KernelSpec(FilterProgram(), ("k",), 8192, (), {}, 1024)
    sig_a = spec.signature()
    cal.set_caps(_caps(high_card_regime="partitioned"))
    sig_b = spec.signature()
    assert sig_a != sig_b  # caps token folds into the jit cache key
