"""Autoregressive text generation with a sharded KV cache.

Runs the transformer LM's inference path (models/decoding.py): prefill
fills the per-layer K/V cache, then a jitted ``lax.scan`` decodes one
token per step against it — batch sharded over ``dp``, attention heads
over ``tp``, the same layout the training step uses.

    JAX_PLATFORMS=cpu python examples/generate_lm.py

(CPU run uses an 8-device virtual mesh; on a TPU slice the same code
shards over real chips.)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

# before the first backend init; only the CPU backend reads it (the
# 8-device virtual mesh of a JAX_PLATFORMS=cpu run)
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from nnstreamer_tpu.models.decoding import make_generate  # noqa: E402
from nnstreamer_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    init_params,
    param_pspecs,
)
from nnstreamer_tpu.parallel.mesh import make_mesh  # noqa: E402


def main():
    cfg = TransformerConfig(vocab=64, dim=64, heads=4, layers=2, max_seq=48)
    devices = jax.devices()
    n = len(devices)
    mesh = make_mesh(devices, {"dp": max(n // 2, 1), "tp": 2 if n > 1 else 1})
    print(f"mesh: {dict(mesh.shape)} on {devices[0].platform}")

    params = init_params(cfg, seed=0)
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_pspecs(cfg),
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, shardings)

    batch = dict(mesh.shape)["dp"] * 2
    prompt = np.tile(np.arange(6, dtype=np.int32), (batch, 1)) % cfg.vocab
    prompt_dev = jax.device_put(
        prompt, NamedSharding(mesh, P("dp", None)))

    generate = make_generate(cfg, mesh=mesh, temperature=0.8)
    out = np.asarray(generate(params, prompt_dev, 16,
                              rng=jax.random.PRNGKey(42)))
    print(f"prompt {prompt.shape} -> generated {out.shape}")
    for row in out[:2]:
        print("  ", " ".join(str(t) for t in row))


if __name__ == "__main__":
    main()
