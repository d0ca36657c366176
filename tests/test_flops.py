"""FLOPs accounting / MFU substrate (utils/flops.py).

The bench evidence depends on three properties: XLA cost analysis is
close to the analytic matmul count, the peak-FLOPs table resolves TPU
generations (an unknown TPU is an error, never a default), and the record
helper degrades to nulls when the FLOPs or the rate are unknown.
"""
import numpy as np
import pytest

from nnstreamer_tpu.utils.flops import (
    compiled_flops,
    count_params,
    hbm_bytes_per_s_per_chip,
    mfu,
    peak_flops_per_chip,
    perf_record,
    ridge_flops_per_byte,
    transformer_flops,
)


class _FakeDev:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


def test_compiled_flops_close_to_analytic():
    import jax.numpy as jnp

    def f(x, w):
        return x @ w

    got = compiled_flops(f, jnp.ones((8, 256), jnp.float32),
                         jnp.ones((256, 512), jnp.float32))
    analytic = 2 * 8 * 256 * 512
    assert got is not None
    # XLA counts a handful of extra elementwise flops; same order, >= matmul
    assert analytic <= got <= analytic * 1.25


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


def test_mfu_and_record():
    dev = _FakeDev("tpu", "TPU v5 lite")
    # 19.7 TFLOP/s on a 197 TFLOP/s chip = 10% MFU
    assert mfu(19.7e12, n_chips=1, device=dev) == pytest.approx(0.1)
    rec = perf_record(1e9, 1000.0, device=dev)
    assert rec["model_tflops_per_s"] == pytest.approx(1.0)
    assert rec["mfu"] == pytest.approx(1e12 / 197e12, abs=5e-5)  # 4-dp rounded
    # null-safe paths
    assert perf_record(None, 1000.0) == {"model_tflops_per_s": None,
                                         "mfu": None}
    assert mfu(None) is None


def test_transformer_flops_dominated_by_matmul_at_short_ctx():
    n_params, toks = 125_000_000, 1024
    got = transformer_flops(n_params, n_layers=12, d_model=768,
                            seq_len=64, n_tokens=toks)
    assert got >= 2.0 * n_params * toks
    assert got <= 2.6 * n_params * toks  # attn term small at seq 64


def test_count_params():
    tree = {"a": np.zeros((3, 4)), "b": [np.zeros(5), np.zeros((2, 2))]}
    assert count_params(tree) == 12 + 5 + 4
