"""Run a real .tflite model on XLA through a full pipeline.

The reference runs .tflite through the tflite interpreter
(tensor_filter framework=tensorflow2-lite); here the same file compiles
to an XLA program (models/tflite_import.py) — same caps, same uint8
output, label parity.

    python examples/classify_tflite_on_xla.py [model.tflite]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from nnstreamer_tpu.runtime.parse import parse_launch  # noqa: E402

DEFAULT = "/root/reference/tests/test_models/models/mobilenet_v2_1.0_224_quant.tflite"


def main() -> None:
    model = sys.argv[1] if len(sys.argv) > 1 else DEFAULT
    if not os.path.exists(model):
        raise SystemExit(
            f"model not found: {model}\n"
            "usage: python examples/classify_tflite_on_xla.py <model.tflite>\n"
            "(the no-argument default expects the reference checkout at "
            "/root/reference)")
    pipe = parse_launch(
        "tensor_src num-buffers=4 dimensions=3:224:224:1 types=uint8 pattern=random "
        f"! tensor_filter framework=jax model={model} "
        "! tensor_decoder mode=image_labeling "
        "! tensor_sink name=out")
    labels = []
    pipe.get("out").connect(lambda b: labels.append(b.meta.get("label")))
    pipe.run(timeout=120)
    print(f"{os.path.basename(model)} on XLA → top-1 class ids: {labels}")


if __name__ == "__main__":
    main()
