"""The chip's published peaks and ridge (utils/flops.py).

What reads them depends on three properties: the peak table resolves TPU
generations (an unknown TPU is an error, never a default), the ridge is
peak over bandwidth under the same rules, and the benchmark's own table
(benchmark/lib/peaks.py, which decides every roofline share) holds the
same numbers for every device it names.
"""
import pytest

from benchmark.lib import peaks
from nnstreamer_tpu.utils.flops import (
    hbm_bytes_per_s_per_chip,
    peak_flops_per_chip,
    ridge_flops_per_byte,
)


class _FakeDev:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


def test_peak_table_matches_generations():
    assert peak_flops_per_chip(_FakeDev("tpu", "TPU v5 lite")) == 197e12
    assert peak_flops_per_chip(_FakeDev("tpu", "TPU v5p")) == 459e12
    assert peak_flops_per_chip(_FakeDev("tpu", "TPU v4")) == 275e12
    assert peak_flops_per_chip(_FakeDev("tpu", "TPU v6 lite")) == 918e12
    # CPU has no published peak: accounting must say "unknown", not guess
    assert peak_flops_per_chip(_FakeDev("cpu", "cpu")) is None


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    # a TPU that is not in the table raises: no peak is made up from the
    # environment, and none is defaulted
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    with pytest.raises(ValueError, match="unknown-kind"):
        peak_flops_per_chip(_FakeDev("tpu", "unknown-kind"))
    # other accelerators have no table at all: unknown, not an error
    assert peak_flops_per_chip(_FakeDev("gpu", "some gpu")) is None


def test_the_ridge_is_peak_over_bandwidth():
    # the HBM table is keyed as the peak table is, under the same rules
    v5e = _FakeDev("tpu", "TPU v5 lite")
    assert hbm_bytes_per_s_per_chip(v5e) == 819e9
    assert ridge_flops_per_byte(v5e) == pytest.approx(240.5, abs=0.1)
    assert hbm_bytes_per_s_per_chip(_FakeDev("cpu", "cpu")) is None
    assert ridge_flops_per_byte(_FakeDev("cpu", "cpu")) is None
    with pytest.raises(ValueError, match="HBM"):
        hbm_bytes_per_s_per_chip(_FakeDev("tpu", "unknown-kind"))


@pytest.mark.parametrize("reader, quantity", [
    (peak_flops_per_chip, "flops_per_s"),
    (hbm_bytes_per_s_per_chip, "hbm_bytes_per_s"),
])
@pytest.mark.parametrize("device_kind", sorted(peaks.PEAKS))
def test_the_benchmarks_table_holds_the_same_numbers(device_kind, reader,
                                                     quantity):
    # prefill_width compiles a shape from one table and every roofline
    # share is taken against the other: a device either names reads the
    # same from both
    assert reader(_FakeDev("tpu", device_kind)) \
        == peaks.PEAKS[device_kind][quantity]
