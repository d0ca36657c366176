"""Label parity: the jax/TPU execution path must produce the same labels as
the tflite-CPU path for the SAME model weights.

This is the BASELINE.md acceptance criterion ("label parity: exact vs
tflite-CPU subplugin outputs"): the flax MobileNet-v2 is exported through
jax2tf → TFLite, then the identical input stream is run through
  (a) tensor_filter framework=jax    (our native path), and
  (b) tensor_filter framework=tflite (the reference's flagship backend)
with the image_labeling decoder, and the decoded label indices must match
frame for frame.

The flow itself lives in nnstreamer_tpu.utils.parity — shared with
tools/device_parity.py, the standalone runner for the real TPU, so this
test and the on-device evidence are one harness.
"""
import sys

import numpy as np
import pytest

pytest.importorskip("tensorflow")

from nnstreamer_tpu.utils.parity import (
    export_f32_mobilenet,
    labels_through,
    register_entry_module,
)


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    path = tmp_path_factory.mktemp("parity") / "mobilenet_v2.tflite"
    return export_f32_mobilenet(str(path))


@pytest.mark.slow
def test_label_parity_jax_vs_tflite(exported, _entry_module):
    _, tflite_path = exported
    rng = np.random.default_rng(7)
    frames = [rng.random((1, 224, 224, 3), np.float32) * 2 - 1 for _ in range(8)]

    jax_labels = labels_through("jax", _entry_module, frames)
    tflite_labels = labels_through("tflite", tflite_path, frames)
    assert len(jax_labels) == len(tflite_labels) == 8
    assert jax_labels == tflite_labels


@pytest.fixture
def _entry_module(exported):
    """Expose the fixture's forward fn as an importable module:attr entry
    for the jax backend (module entries are its model format)."""
    fwd, _ = exported
    model = register_entry_module("tests_parity_entry", fwd)
    yield model
    sys.modules.pop("tests_parity_entry", None)
