"""Compile the timed programs at the cells' real sizes for a described v5e
chip, with no chip attached, and print what the compiler says of their
memory. Costs no chip time; run it here before a chip call:

    JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_compile.py

``_step`` and ``_prefill_chunk`` of the paged engine at the ``opt_1.3b``
configuration's sizes and engine geometry. A compile that passes is not a
chip run.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _report(name, compiled):
    m = compiled.memory_analysis()
    row = {"program": name,
           "argument_bytes": m.argument_size_in_bytes,
           "output_bytes": m.output_size_in_bytes,
           "alias_bytes": m.alias_size_in_bytes,
           "temp_bytes": m.temp_size_in_bytes,
           "code_bytes": m.generated_code_size_in_bytes}
    row["peak_estimate_bytes"] = (row["argument_bytes"] + row["output_bytes"]
                                  - row["alias_bytes"] + row["temp_bytes"])
    print(json.dumps(row), flush=True)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import harness
    from benchmark.references import rms_gpt_lm
    from nnstreamer_tpu.models.transformer import TransformerConfig
    from nnstreamer_tpu.serving.lm_engine import PagedLMEngine

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    bench = harness.load_benchmark()
    _, config = harness.find_cell(bench, "opt1b3_chat")
    sz = rms_gpt_lm.sizes(config)
    tcfg = TransformerConfig(vocab=sz.vocab, dim=sz.hidden, heads=sz.heads,
                             layers=sz.layers, mlp_mult=sz.ffn // sz.hidden,
                             max_seq=sz.positions)
    geo = config["engine"]
    # the engine's programs close over the sizes only: build it over a
    # two-page pool and a stub parameter tree, lower with the real shapes
    engine = PagedLMEngine(tcfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
                           slots=geo["slots"], page_size=geo["page_size"],
                           pages=2, chunk=geo["chunk"])
    params = jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda k: rms_gpt_lm.program_params(
            k, sz, jnp.bfloat16), jax.random.key(0)))
    S, NB = geo["slots"], sz.positions // geo["page_size"]
    pool = shape((sz.layers, geo["pages"] + 1, sz.heads, geo["page_size"],
                  sz.hidden // sz.heads), jnp.bfloat16)
    step = engine._step.func.lower(
        params, shape((S, 1), jnp.int32), shape((S,), jnp.int32),
        shape((S,), jnp.bool_), shape((S, NB), jnp.int32), pool, pool)
    _report("_step", step.compile())
    chunk = engine._prefill_chunk.func.lower(
        params, shape((geo["chunk"],), jnp.int32), shape((), jnp.int32),
        shape((), jnp.int32), shape((NB,), jnp.int32), pool, pool)
    _report("_prefill_chunk", chunk.compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
