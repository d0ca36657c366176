"""FLOPs accounting + MFU — the perf-evidence substrate for bench.py and
tools/bench_suite.py.

The reference instruments invoke latency/throughput only
(gst/nnstreamer/tensor_filter/tensor_filter.c:366-510 — 10-invoke sliding
average, µs granularity); on TPU a raw fps number says nothing about how
much of the chip it uses, so every benchmark here also reports
**model FLOP/s and MFU** (model FLOPs / peak chip FLOPs — the
scaling-book utilization metric). Model FLOPs come from XLA's own
compiled-program cost analysis (exact for the executable actually run);
peak comes from a public per-generation spec table keyed on
``device_kind``.

MFU is only reported for devices whose peak is known (TPUs); on CPU the
accounting fields still flow (flops, flops_per_s) so the code path is
CI-validated, with ``mfu: null``.
"""
from __future__ import annotations

import os
from typing import Any, Optional, Tuple

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


def compiled_flops(fn, *example_args, static_argnums=()) -> Optional[float]:
    """FLOPs of one call of ``fn(*example_args)`` per XLA's cost analysis
    of the compiled executable. Returns None when the backend doesn't
    expose cost analysis. Compiles the fn for the example shapes — on a
    warm jit/persistent cache this is ~free, cold it pays one compile."""
    import jax

    try:
        compiled = (jax.jit(fn, static_argnums=static_argnums)
                    .lower(*example_args).compile())
        flops = compiled.cost_analysis().get("flops")
        return float(flops) if flops and flops > 0 else None
    except Exception:  # noqa: BLE001 — accounting must never sink a bench
        return None


def transformer_flops(n_params: int, n_layers: int, d_model: int,
                      seq_len: int, n_tokens: int,
                      kv_cache_len: int = 0) -> float:
    """Analytic decoder-transformer FLOPs for ``n_tokens`` processed
    tokens: the standard 2·N·tokens matmul estimate plus attention-score
    FLOPs (12·L·D·T·ctx per scaling-book appendix; dominant only at long
    context). ``kv_cache_len``: context attended per token in cached
    decode (0 ⇒ full causal ≈ seq_len/2 average)."""
    ctx = kv_cache_len if kv_cache_len > 0 else max(seq_len, 1) / 2.0
    matmul = 2.0 * n_params * n_tokens
    attn = 12.0 * n_layers * d_model * n_tokens * ctx
    return matmul + attn


def mfu(flops_per_second: Optional[float], n_chips: int = 1,
        device=None) -> Optional[float]:
    """Model FLOP utilization in [0, 1]; None when either side is
    unknown."""
    if not flops_per_second:
        return None
    peak = peak_flops_per_chip(device)
    if not peak:
        return None
    return flops_per_second / (peak * max(n_chips, 1))


def count_params(params: Any) -> int:
    """Total scalar count of a pytree of arrays."""
    import jax

    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params)
               if hasattr(x, "size"))


def bench_mesh_policy(n_devices: int, on_cpu: bool, batch: int):
    """Shared bench policy for multi-chip windows (bench.py and
    tools/bench_suite.py must measure the SAME configuration): mesh the
    model stage over every chip unless BENCH_NO_MESH, with
    BENCH_FORCE_MESH enabling the path on the CPU virtual mesh for
    validation. Returns ``(mesh_custom, batch)`` — batch rounded UP to a
    multiple of the dp axis, because an indivisible batch silently falls
    back to unsharded invoke and the reported MFU/devices would claim
    chips that did no work."""
    if n_devices <= 1 or os.environ.get("BENCH_NO_MESH") \
            or (on_cpu and not os.environ.get("BENCH_FORCE_MESH")):
        return "", batch
    if batch % n_devices:
        batch = ((batch + n_devices - 1) // n_devices) * n_devices
    return "mesh:auto", batch


def perf_record(flops_per_item: Optional[float], items_per_second: float,
                n_chips: int = 1, device=None) -> dict:
    """The JSON fields every bench row carries: model_tflops_per_s + mfu
    (null-safe)."""
    if not flops_per_item or items_per_second <= 0:
        return {"model_tflops_per_s": None, "mfu": None}
    fps_flops = flops_per_item * items_per_second
    u = mfu(fps_flops, n_chips=n_chips, device=device)
    return {"model_tflops_per_s": round(fps_flops / 1e12, 4),
            "mfu": round(u, 4) if u is not None else None}
