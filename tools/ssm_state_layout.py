"""What the compiler makes of the state beside the pages (PR 33), with no
chip attached: ``_step`` and ``_prefill_chunk`` of the paged engine at the
``jamba2_3b`` configuration's sizes and engine geometry, compiled for a
described v5e, in the forms a TPU runs (the step's attention kernel, the
launch's scan kernel). Prints one JSON line a program:

* ``memory_analysis()``: arguments, outputs, aliased and temporary bytes
  (the state arrays and the pools are donated: aliased bytes must cover
  them, and no temporary may be a state array's size);
* ``state_sized_copies``: ``copy`` or ``transpose`` instructions of the
  optimized module whose result has a whole state array's shape: a
  relayout of the state a call (expected 0);
* ``kernels``: mentions of the Mosaic kernels in it, by name.

    JAX_PLATFORMS=cpu python3 tools/ssm_state_layout.py [--conv-rows]
                                                        [--plain-step]

``--conv-rows`` stores the conv's inputs ``(3, 5120)`` a slot instead of
flat, to show what that layout costs; ``--plain-step`` compiles the step's
update in its plain form instead of the kernel. A compile that passes is not a chip
run: nothing here is a time.
"""
from __future__ import annotations

import json
import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import harness
    from nnstreamer_tpu.models.jamba import JambaConfig, JambaFamily
    from nnstreamer_tpu.ops import paged_attention, selective_scan
    from nnstreamer_tpu.serving import lm_engine

    # the forms a TPU runs: chosen by the backend, which is the CPU here
    paged_attention.paged_line_attention = \
        paged_attention.kernel_line_attention
    selective_scan.chunk_scan = selective_scan.tpu_chunk_scan
    if "--plain-step" not in argv:
        selective_scan.slots_update = selective_scan.tpu_slots_update

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(s, dt):
        return jax.ShapeDtypeStruct(tuple(s), dt, sharding=chip)

    with open(os.path.join(harness.ROOT, "benchmark", "configs",
                           "jamba2_3b.json")) as fh:
        config = json.load(fh)
    reference = harness.reference_for(config)
    sz = reference.sizes(config)
    mcfg = JambaConfig.from_published(config)
    if "--conv-rows" in argv:
        rows = (mcfg.mamba_d_conv - 1, mcfg.d_inner)

        class Rows(JambaFamily):  # the layout the flat one is held against
            def __init__(self, cfg):
                super().__init__(cfg)
                self.state_lines = ((rows, None), self.state_lines[1])

            def mix_step(self, blk, x, states, layer, live):
                flat = states[0].reshape(*states[0].shape[:2], -1)
                y, (conv, h) = super().mix_step(
                    blk, x, (flat, states[1]), layer, live)
                return y, (conv.reshape(states[0].shape), h)

            def mix_chunk(self, blk, x, n_valid, state):
                y, (conv, h) = super().mix_chunk(
                    blk, x, n_valid, (state[0].reshape(-1), state[1]))
                return y, (conv.reshape(rows), h)

        lm_engine_family = lambda cfg: Rows(cfg)  # noqa: E731
        import nnstreamer_tpu.models.families as families
        families.family_of = lm_engine_family
    geo = dict(config["engine"])
    width = 256  # what prefill_width derives on a v5e in bfloat16
    pages = geo.pop("pages")
    geo["chunk"] = width
    # the programs close over the sizes only: build the engine over a
    # two-page pool and one slot's state, lower with the real shapes
    stub = {"embed": jnp.zeros((1, 1), jnp.bfloat16)}
    engine = lm_engine.PagedLMEngine(mcfg, stub, pages=2, **{
        **geo, "slots": 1})
    S, NB = geo["slots"], sz.positions // geo["page_size"]
    params = jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda k: reference.program_params(
            k, sz, jnp.bfloat16), jax.random.key(0)))
    attn_layers = sum(sz.attention)
    pools = [shape((attn_layers * (pages + 1), geo["page_size"], w),
                   jnp.bfloat16) for w in engine.line_widths]
    states = [shape((engine.state_layers, S, *s.shape[2:]), s.dtype)
              for s in engine._states]
    i32 = jnp.int32
    # ``slots`` is closed over by nothing but the shapes
    lowered = {
        "_step": engine._step.func.lower(
            params, shape((S, 1), i32), shape((S,), i32),
            shape((S,), jnp.bool_), shape((S, NB), i32), *pools, *states),
        "_prefill_chunk": engine._prefill_chunk.func.lower(
            params, shape((width,), i32), shape((), i32), shape((), i32),
            shape((NB,), i32), *pools, shape((), i32), *states),
    }
    state_shapes = {"[" + ",".join(map(str, s.shape)) + "]" for s in states}
    whole = re.compile(r"= \(?\w+(\[[\d,]*\])[^ ]* (copy|transpose)\(")
    for name, low in lowered.items():
        compiled = low.compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        copies = sum(1 for line in text.splitlines()
                     for found in [whole.search(line)]
                     if found and found.group(1) in state_shapes)
        print(json.dumps({
            "program": name,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "state_bytes": sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
                               for s in states),
            "pool_bytes": sum(2 * math.prod(p.shape) for p in pools),
            "state_sized_copies": copies,
            "kernels": {k: text.count(f"/{k}/pallas_call")
                        for k in ("selective_scan_step",
                                  "selective_scan_chunk",
                                  "paged_line_attention")},
        }), flush=True)
        out = os.environ.get("SSM_LAYOUT_HLO_DIR")
        if out:
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, f"{name}.hlo.txt"), "w") as fh:
                fh.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
