"""Published peaks of one chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A device
that is not in the table is an error, never a default: a roofline share
against a guessed peak is not a measurement.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    # the same chip as newer runtimes name it
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks on file for device_kind {device_kind!r} "
            f"(have {sorted(PEAKS)}); add a row with its source to "
            "benchmark/lib/peaks.py") from None
