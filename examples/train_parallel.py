"""Sharded training on a virtual 8-device mesh: all five parallelism
families in one script (what dryrun_multichip gates, spelled out).

    python examples/train_parallel.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# before the first backend init; only the CPU backend reads it (the
# 8-device virtual mesh of a JAX_PLATFORMS=cpu run)
jax.config.update("jax_num_cpu_devices", 8)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nnstreamer_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    init_params,
    make_train_step,
)
from nnstreamer_tpu.parallel import make_mesh  # noqa: E402
from nnstreamer_tpu.parallel.pipeline import (  # noqa: E402
    make_pipeline,
    stack_stage_params,
)


def main() -> None:
    rng = np.random.default_rng(0)

    # dp/tp/sp (+ ep riding tp): transformer LM with MoE FFN
    mesh = make_mesh(jax.devices(), {"dp": 2, "tp": 2, "sp": 2})
    cfg = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, max_seq=17,
                            attn_impl="ring", moe_experts=4)
    step, shard_params, data_sharding = make_train_step(cfg, mesh, lr=3e-2)
    params = shard_params(init_params(cfg))
    toks = jax.device_put(
        rng.integers(0, 64, (4, 17)).astype(np.int32), data_sharding)
    for i in range(5):
        params, loss = step(params, toks)
        print(f"dp2×tp2×sp2 ring+moe step {i}: loss {float(loss):.4f}")

    # pp: GPipe microbatch pipeline over 4 stages
    mesh_pp = make_mesh(jax.devices(), {"pp": 4, "dp": 2})
    stages = [{"w": jax.random.normal(jax.random.PRNGKey(i), (16, 16)) * 0.3}
              for i in range(4)]
    stacked = stack_stage_params(stages)
    run = make_pipeline(lambda p, x: jnp.tanh(x @ p["w"]), 4, mesh_pp)
    xs = jax.random.normal(jax.random.PRNGKey(9), (4, 2, 16))

    def loss_fn(p):
        return jnp.mean(run(p, xs) ** 2)

    grad_step = jax.jit(jax.value_and_grad(loss_fn))
    for i in range(5):
        loss, grads = grad_step(stacked)
        stacked = jax.tree_util.tree_map(lambda p, g: p - 0.3 * g, stacked, grads)
        print(f"pp4×dp2 gpipe step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
