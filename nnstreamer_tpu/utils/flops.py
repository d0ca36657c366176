"""The chip's published peaks and its ridge, keyed on ``device_kind``.

Two per-generation tables from the public spec sheets (dense bf16 FLOP/s
and HBM bytes/s a chip) and their three readers. The ridge (peak over
bandwidth, in FLOPs a byte) decides a compiled shape:
``serving/lm_engine.py`` ``prefill_width`` reads it for the width of a
prefill launch, and the stand-alone chip tools
(``tools/prefill_width_forms.py``, ``tools/moe_grouped_forms.py``) print
shares of it. The benchmark's roofline shares read their own table,
``benchmark/lib/peaks.py``; ``tests/test_flops.py`` holds the two to the
same numbers for every device the benchmark names.

A CPU has no row (the readers return None); a TPU whose ``device_kind``
is not in a table raises, so an unknown chip is an error, never a default.
"""
from __future__ import annotations

from typing import Optional, Tuple

# bf16 dense peak FLOP/s per chip, public spec sheets (cloud.google.com/tpu
# docs; "How to Scale Your Model" table). Ordered: first substring match
# on a lowercased device_kind wins, so more specific names come before
# their prefixes ("v5p" before "v5").
_PEAK_BF16: Tuple[Tuple[str, float], ...] = (
    ("v6e", 918e12), ("v6 lite", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


# HBM bytes/s per chip, keyed as ``_PEAK_BF16`` is (the same spec sheets).
_HBM_BYTES_PER_S: Tuple[Tuple[str, float], ...] = (
    ("v6e", 1640e9), ("v6 lite", 1640e9), ("trillium", 1640e9),
    ("v5p", 2765e9),
    ("v5e", 819e9), ("v5 lite", 819e9), ("v5litepod", 819e9),
    ("v4", 1200e9),
    ("v3", 900e9),
    ("v2", 700e9),
)


def _chip_spec(table, what: str, device=None) -> Optional[float]:
    """``device``'s row of a per-generation table (default device:
    jax.devices()[0]). None on CPU; a TPU whose ``device_kind`` is not in
    the table raises — an unknown chip is an error, not a default."""
    if device is None:
        import jax

        device = jax.devices()[0]
    platform = getattr(device, "platform", "cpu")
    if platform == "cpu":
        return None
    kind = str(getattr(device, "device_kind", "")).lower()
    for key, value in table:
        if key in kind:
            return value
    from .hw_accel import is_tpu_platform

    if is_tpu_platform(platform):
        raise ValueError(
            f"no {what} on file for TPU device_kind {kind!r} "
            f"(known: {[k for k, _ in table]})")
    return None


def peak_flops_per_chip(device=None) -> Optional[float]:
    """Peak dense bf16 FLOP/s for ``device`` (default: jax.devices()[0]).
    None on CPU; a TPU whose ``device_kind`` is not in the table raises —
    an unknown chip is an error, not a default peak."""
    return _chip_spec(_PEAK_BF16, "peak FLOP/s", device)


def hbm_bytes_per_s_per_chip(device=None) -> Optional[float]:
    """HBM bytes/s for ``device``, under ``peak_flops_per_chip``'s rules."""
    return _chip_spec(_HBM_BYTES_PER_S, "HBM bytes/s", device)


def ridge_flops_per_byte(device=None) -> Optional[float]:
    """The chip's ridge: the bf16 FLOPs a byte read from HBM at which a
    kernel stops being bound by its reads (v5e: 240.5). None where the
    chip is unknown to both tables (CPU)."""
    peak = peak_flops_per_chip(device)
    hbm = hbm_bytes_per_s_per_chip(device)
    return peak / hbm if peak and hbm else None
