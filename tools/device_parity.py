"""On-device label parity: jax-on-accelerator vs tflite-on-CPU, one JSON line.

BASELINE.md acceptance row: "label parity: exact vs tflite-CPU subplugin
outputs (v5e-8 vs CPU)". tests/test_label_parity.py proves it CPU-vs-CPU;
this standalone runner lands the SAME check with the jax path on whatever
platform jax selected (the chip, through the chip tool). The flow (export +
pipelines) is one shared harness — nnstreamer_tpu.utils.parity — so this
runner cannot diverge from the acceptance test it mirrors.

Run:  python tools/device_parity.py          (PARITY_FRAMES=64 knob)
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

_T0 = time.monotonic()


def _log(msg: str) -> None:
    print(f"[parity +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def main() -> None:
    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    _log(f"jax platform: {platform}")
    # Parity is a correctness check: pin full-f32 matmul/conv passes. On
    # TPU the default f32 precision runs bf16 MXU passes — measured r5:
    # 2/64 top-1 flips on near-tie frames vs the CPU interpreter. The
    # benchmark's cells keep the default; only parity pays for exactness.
    jax.config.update("jax_default_matmul_precision", "highest")

    from nnstreamer_tpu.utils.parity import (
        export_f32_mobilenet,
        labels_through,
        register_entry_module,
    )

    n_frames = int(os.environ.get("PARITY_FRAMES", "64"))
    _log("building + exporting mobilenet_v2 (float32) to tflite")
    fwd, tfl_path = export_f32_mobilenet("/tmp/nns_parity_mobilenet_v2.tflite")
    jax_model = register_entry_module("nns_parity_entry", fwd)

    rng = np.random.default_rng(20260730)
    frames = [(rng.random((1, 224, 224, 3)) * 2 - 1).astype(np.float32)
              for _ in range(n_frames)]

    _log(f"running jax path on {platform} ({n_frames} frames)")
    jax_labels = labels_through("jax", jax_model, frames, timeout=300)
    _log("running tflite path on CPU")
    tfl_labels = labels_through("tflite", tfl_path, frames, timeout=300)

    mismatches = [i for i, (a, b) in enumerate(zip(jax_labels, tfl_labels))
                  if a != b]
    result = {
        "metric": "label_parity_jax_vs_tflite_cpu",
        "frames": n_frames,
        "jax_platform": platform,
        "jax_frames": len(jax_labels),
        "tflite_frames": len(tfl_labels),
        "mismatches": len(mismatches),
        "parity": ("exact" if not mismatches
                   and len(jax_labels) == len(tfl_labels) == n_frames
                   else "MISMATCH"),
    }
    if mismatches:
        result["first_mismatch_frames"] = mismatches[:5]
    print(json.dumps(result))


if __name__ == "__main__":
    main()
