"""Tensor-parallel LM serving INSIDE a pipeline.

The generative stack (models/decoding.py) behind the product surface: one
launch line serves batched greedy generation with the params sharded
megatron-style over ``tp``, the KV cache per ``cache_pspecs``, and the
batch over ``dp`` — ``custom=mesh:2x4`` is the only topology annotation.

    JAX_PLATFORMS=cpu python examples/serve_lm_pipeline.py

(CPU run uses an 8-device virtual mesh; on a TPU slice the same line
shards over real chips via ICI. The reference has no generative path —
SURVEY.md §5.7 — this is beyond-parity capability.)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

# before the first backend init; only the CPU backend reads it (the
# 8-device virtual mesh of a JAX_PLATFORMS=cpu run)
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402

from nnstreamer_tpu.runtime.parse import parse_launch  # noqa: E402


def main() -> None:
    B, P = 4, 6
    pipe = parse_launch(
        "appsrc name=in caps=other/tensors,format=static,"
        f"dimensions={P}:{B},types=int32 "
        "! tensor_filter framework=jax "
        "model=nnstreamer_tpu.models.lm_serving:tiny custom=mesh:2x4 "
        "name=lm "
        "! tensor_sink name=out max-stored=8")

    outs = []
    pipe.get("out").connect(lambda b: outs.append(b.tensors[0]))
    pipe.play()

    rng = np.random.default_rng(0)
    src = pipe.get("in")
    for _ in range(2):
        src.push_buffer(rng.integers(0, 64, (B, P)).astype(np.int32))
    src.end_of_stream()
    pipe.wait(timeout=120)

    mesh = pipe.get("lm").backend_mesh
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    for i, t in enumerate(outs):
        arr = np.asarray(t)
        print(f"batch {i}: prompt {arr[0, :P].tolist()} -> "
              f"generated {arr[0, P:].tolist()} "
              f"(sharded over {len(t.sharding.device_set)} chips)")
    pipe.stop()

    # the STREAMING form: tensor_generate emits one buffer per decoded
    # token (same entry, same greedy math — token-exact with the above)
    spipe = parse_launch(
        "appsrc name=in caps=other/tensors,format=static,"
        f"dimensions={P}:{B},types=int32 "
        "! tensor_generate model=nnstreamer_tpu.models.lm_serving:tiny "
        "steps=8 mesh=2x4 "
        "! tensor_sink name=out max-stored=16")
    spipe.get("out").connect(
        lambda b: print(f"  token {b.meta['gen_step']}: "
                        f"{np.asarray(b.tensors[0])[:, 0].tolist()}"
                        + ("  <last>" if b.meta["gen_last"] else "")))
    spipe.play()
    print("streaming generation (one line per token as it decodes):")
    spipe.get("in").push_buffer(
        np.random.default_rng(0).integers(0, 64, (B, P)).astype(np.int32))
    spipe.get("in").end_of_stream()
    spipe.wait(timeout=120)
    spipe.stop()


if __name__ == "__main__":
    main()
