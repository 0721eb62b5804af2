"""ops/cost_model: one peaks table keyed by device_kind."""

import types

import pytest

from ceph_tpu.ops import cost_model


def _fake_device(monkeypatch, platform: str, kind: str) -> None:
    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


def test_cpu_platform_has_no_peaks_and_no_roofline():
    assert cost_model.peaks() is None
    assert cost_model.roofline_gbps(1e9, 1e9, 1e9) is None


def test_v5e_peaks_are_the_published_ones(monkeypatch):
    _fake_device(monkeypatch, "tpu", "TPU v5 lite")
    assert cost_model.peaks() == (819.0, 197.0)
    # bytes-bound: 819e9 bytes accessed take >= 1 s
    assert cost_model.roofline_gbps(None, 819e9, 1e9) == \
        pytest.approx(1.0)
    # flops-bound: 197e12 flops take >= 1 s
    assert cost_model.roofline_gbps(197e12, 1.0, 2e9) == \
        pytest.approx(2.0)


def test_unknown_device_kind_is_an_error(monkeypatch):
    _fake_device(monkeypatch, "tpu", "TPU v9 imaginary")
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        cost_model.peaks()
    with pytest.raises(KeyError):
        cost_model.roofline_gbps(1.0, 1.0, 1.0)


def test_peak_overrides_are_gone(monkeypatch):
    _fake_device(monkeypatch, "tpu", "TPU v5 lite")
    monkeypatch.setenv("CEPH_TPU_PEAK_HBM_GBPS", "1")
    monkeypatch.setenv("CEPH_TPU_PEAK_TFLOPS", "1")
    assert cost_model.peaks() == (819.0, 197.0)


def test_measure_noise_floor_reads_the_same_table(monkeypatch):
    from ceph_tpu.bench import measure
    assert measure.min_physical_slope(1 << 30) == 0.0
    _fake_device(monkeypatch, "tpu", "TPU v5 lite")
    assert measure.min_physical_slope(819_000_000) == \
        pytest.approx(1e-3)
