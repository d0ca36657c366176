"""Device-resident classification: no full-width host transfers at all.

The TPU-first streaming pattern (r5): frames are generated ON the
accelerator (``tensor_src device=true`` — stands in for any
device-resident ingest), the fused-u8 MobileNet consumes them where they
live, and the decoder reduces the whole batch on device
(``frames-in=N`` → one jitted argmax + ONE compact pull), emitting N
per-frame label buffers. The only device→host traffic is one int32 per
frame.

Contrast with the reference's shape (gsttensor_decoder.c maps every
output byte to host before decoding; videotestsrc feeds full frames
through host memory): on a bandwidth-limited link the reference pattern
is transfer-bound, this one is compute-bound.

    JAX_PLATFORMS=cpu python examples/device_resident_classify.py

(CPU run for the demo; the same line is what the TPU bench runs.)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from nnstreamer_tpu.runtime.parse import parse_launch  # noqa: E402

BATCH = int(os.environ.get("BATCH", "8"))
BUFFERS = int(os.environ.get("BUFFERS", "3"))


def main() -> None:
    labels = "/tmp/nns_example_labels.txt"
    with open(labels, "w") as fh:
        fh.write("\n".join(f"class{i}" for i in range(1001)))
    pipe = parse_launch(
        f"tensor_src device=true pattern=random num-buffers={BUFFERS} "
        f"dimensions=3:224:224:{BATCH} types=uint8 "
        "! tensor_filter framework=jax "
        "model=nnstreamer_tpu.models.mobilenet_v2:filter_model_u8 "
        "sync-invoke=false "
        "! queue max-size-buffers=4 "
        f"! tensor_decoder mode=image_labeling option1={labels} "
        f"frames-in={BATCH} "
        "! tensor_sink name=out max-stored=4")
    got = []
    pipe.get("out").connect(got.append)
    pipe.run(timeout=600)
    print(f"{len(got)} frames labeled "
          f"({BUFFERS} device batches x {BATCH}):")
    print(" ", [b.meta["label"] for b in got[: 2 * BATCH]])
    assert len(got) == BUFFERS * BATCH


if __name__ == "__main__":
    main()
