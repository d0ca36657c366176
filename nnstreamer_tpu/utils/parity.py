"""Shared label-parity harness — BASELINE.md acceptance criterion
("label parity: exact vs tflite-CPU subplugin outputs").

One definition of the parity flow, used by BOTH the CI test
(tests/test_label_parity.py) and the on-device runner
(tools/device_parity.py), so the standalone evidence can never silently
diverge from the acceptance test it mirrors:

  flax MobileNet-v2 (float32) --jax2tf--> .tflite      (same weights)
  frames -> tensor_filter(jax)    -> image_labeling -> labels A
  frames -> tensor_filter(tflite) -> image_labeling -> labels B

float32 compute on both paths so the comparison isolates the runtime,
not the dtype (tflite has no bfloat16 kernels; bf16 label stability is
covered separately by test_bf16_compute_label_stable).

Reference analog: ext/nnstreamer/tensor_filter/tensor_filter_tensorflow_lite.cc
as the flagship backend + tensor_decoder image_labeling goldens
(tests/nnstreamer_decoder_image_labeling/ in the reference tree).
"""
from __future__ import annotations

import sys
import types
from typing import Callable, List, Sequence, Tuple


def export_f32_mobilenet(tflite_path: str) -> Tuple[Callable, str]:
    """Build the float32 flax MobileNet-v2 and export it through
    jax2tf -> TFLite at ``tflite_path``. Returns ``(fwd, tflite_path)``
    where ``fwd`` closes over the SAME weights the .tflite carries."""
    import numpy as np
    import tensorflow as tf

    from nnstreamer_tpu.models.mobilenet_v2 import build_mobilenet_v2

    apply_fn, params = build_mobilenet_v2(compute_dtype="float32")

    def fwd(x):
        return apply_fn(params, x)

    conv = tf.lite.TFLiteConverter.experimental_from_jax(
        [fwd], [[("x", np.zeros((1, 224, 224, 3), np.float32))]])
    with open(tflite_path, "wb") as fh:
        fh.write(conv.convert())
    return fwd, tflite_path


def register_entry_module(name: str, fwd: Callable) -> str:
    """Expose ``fwd`` as an importable ``<name>:entry`` model for the jax
    backend (module entries are its model format). Returns the model
    string. Caller owns cleanup (tests: monkeypatch.setitem)."""
    mod = types.ModuleType(name)
    mod.entry = fwd
    sys.modules[name] = mod
    return f"{name}:entry"


def labels_through(framework: str, model: str, frames: Sequence,
                   timeout: float = 120.0) -> List[int]:
    """Push ``frames`` through the canonical parity pipeline on
    ``framework`` and return the decoded label indices, in order."""
    from nnstreamer_tpu.runtime.parse import parse_launch

    pipe = parse_launch(
        "appsrc name=in caps=other/tensors,format=static,"
        "dimensions=3:224:224:1,types=float32 "
        f"! tensor_filter framework={framework} model={model} "
        "! tensor_decoder mode=image_labeling "
        f"! tensor_sink name=out max-stored={max(64, len(frames))}"
    )
    got: List[int] = []
    pipe.get("out").connect(lambda b: got.append(b.meta["label_index"]))
    pipe.play()
    src = pipe.get("in")
    for f in frames:
        src.push_buffer(f)
    src.end_of_stream()
    pipe.wait(timeout=timeout)
    pipe.stop()
    return got
