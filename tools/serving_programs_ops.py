"""Do the accepted families' programs still compile to what they compiled to?

For every configuration ``BENCHMARK.json`` lists whose driver kind this
script knows (``lm_serving``, ``lm_serving_moe_mla``,
``lm_serving_moe_window``, ``lm_serving_ssm``, ``lm_serving_looped``,
``lm_serving_moe_mtp``, ``lm_serving_moe_cca``): ``_step`` (``_round`` where
the family drafts, since PR 47) and ``_prefill_chunk`` of the paged engine
at the configuration's sizes and engine geometry, a launch of 256 rows, in the forms a TPU runs
(the step's attention kernel, the experts' kernel; a state layer's products
in their plain form), compiled for a described v5e with no chip attached.
Writes one
file a program, ``<outdir>/<config>.<program>.ops``, one line an
instruction of the optimized module in order: opcode, result type and
shape (names, numbers, layouts, metadata and the kernels' serialized
bodies left out: they hold source lines), and prints instructions, argument,
aliased and temporary bytes and the bytes of generated code a program (a
program's load at every start follows the last: two programs of one
instruction count can differ by a sixth in it, PERF.md section 6, PR 44).
The parameters have the shapes the engine runs with: the family's stored
layout (``family.stored``, since PR 45; a tree without it is lowered with
the shapes as they come). ``weight_copies`` and ``weight_copy_bytes`` count
the ``copy`` instructions whose result has a weight matrix's shape, either
way round, and does not lie in memory space 1 (no ``S(1)`` in its layout):
a matrix written to HBM and read again on every call, which storing it
otherwise would save. A copy into memory space 1 is the compiler's
prefetch of the matrix into fast memory, its one read, and is not counted.
``state_bytes`` is what the slots keep beside their pages (a state layer's
state, an attention layer's kept rows: ``engine._states`` for every slot),
which both programs are donated and hand back; 0 for a family that keeps
none.
A fourth argument ``text`` also
writes the optimized module whole, ``<config>.<program>.hlo`` (to read,
not to compare: it holds source lines).

A PR that touches ``serving/lm_engine.py`` or a family runs it on an
unpacked parent and on its own tree and compares the files:

    git archive <parent> | tar -x -C /root/scratch/parent
    JAX_PLATFORMS=cpu python3 tools/serving_programs_ops.py /root/scratch/parent /root/scratch/ops_parent
    JAX_PLATFORMS=cpu python3 tools/serving_programs_ops.py . /root/scratch/ops_change
    diff -r /root/scratch/ops_parent /root/scratch/ops_change

About two minutes a tree. A compile that passes is not a chip run.
Further arguments name the configurations to compile (all of them with
none); ``main(..., rehearse=True)`` compiles at each file's ``rehearsal``
sizes and a launch of its own width (what the tests do).
"""
from __future__ import annotations

import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

_INSTRUCTION = re.compile(
    r"(?:ROOT )?%?[\w.\-]+ = (\(?[a-z0-9]+\[[0-9,]*\][^ ]*(?:, [^ ]+\))?) "
    r"([\w\-]+)\(")


_HLO_TYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def _model(config: dict):
    kind = config["kind"]
    if kind == "lm_serving":
        from nnstreamer_tpu.models.transformer import TransformerConfig

        return TransformerConfig(
            vocab=config["vocab_size"], dim=config["hidden_size"],
            heads=config["num_attention_heads"],
            layers=config["num_hidden_layers"],
            mlp_mult=config["ffn_dim"] // config["hidden_size"],
            max_seq=config["max_position_embeddings"])
    if kind == "lm_serving_moe_mla":
        from nnstreamer_tpu.models.deepseek_v3 import DeepseekV3Config

        return DeepseekV3Config.from_published(config)
    if kind == "lm_serving_moe_window":
        from nnstreamer_tpu.models.mellum import MellumConfig

        return MellumConfig.from_published(config)
    if kind == "lm_serving_ssm":
        from nnstreamer_tpu.models.jamba import JambaConfig

        return JambaConfig.from_published(config)
    if kind == "lm_serving_looped":
        from nnstreamer_tpu.models.ouro import OuroConfig

        return OuroConfig.from_published(config)
    if kind == "lm_serving_moe_mtp":
        from benchmark.lib import harness
        from nnstreamer_tpu.models.exaone_moe import ExaoneMoeConfig

        return ExaoneMoeConfig.from_published(
            harness.reference_for(config).model_config(config))
    if kind == "lm_serving_moe_cca":
        from nnstreamer_tpu.models.zaya import ZayaConfig

        return ZayaConfig.from_published(config)
    return None


def main(root: str, out: str, text: bool = False, only=(),
         rehearse: bool = False) -> list:
    """Compile and write; returns the lines it printed, as dicts."""
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    os.makedirs(out, exist_ok=True)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import harness
    from nnstreamer_tpu.ops import moe_grouped, paged_attention
    from nnstreamer_tpu.serving import lm_engine

    if not os.path.abspath(lm_engine.__file__).startswith(root):
        raise SystemExit(f"imported {lm_engine.__file__}, not {root}'s")
    # the forms a TPU runs: chosen by the backend, which is the CPU here
    paged_attention.paged_line_attention = \
        paged_attention.kernel_line_attention
    moe_grouped.grouped_experts = moe_grouped.tpu_grouped_experts
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(s, dt):
        return jax.ShapeDtypeStruct(tuple(s), dt, sharding=chip)

    i32, printed = jnp.int32, []
    for entry in harness.load_benchmark()["configs"]:
        if only and entry["name"] not in only:
            continue
        with open(os.path.join(root, entry["file"])) as fh:
            config = json.load(fh)
        width = 256
        if rehearse:
            config = {**config, **config["rehearsal"]}
            width = config["engine"]["chunk"]
        mcfg = _model(config)
        if mcfg is None:
            continue
        reference = harness.reference_for(config)
        sz = reference.sizes(config)
        geo = dict(config["engine"], chunk=width)
        pages = geo.pop("pages")
        by_kind = pages if isinstance(pages, dict) else None
        # the programs close over the sizes only: a two-page pool, one slot
        probe = lm_engine.PagedLMEngine(
            mcfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
            **{**geo, "slots": 1,
               "pages": dict.fromkeys(by_kind, 2) if by_kind else 2})
        by_kind = by_kind or {probe.kinds[0]: pages}
        # as the engine keeps them (a parent of PR 45 has no such method)
        stored = getattr(probe.family, "stored", lambda tree: tree)
        params = jax.tree_util.tree_map(
            lambda a: shape(a.shape, a.dtype),
            jax.eval_shape(lambda k: stored(reference.program_params(
                k, sz, jnp.bfloat16)), jax.random.key(0)))
        # a weight matrix's result type as the module writes it, either
        # way round → its bytes
        matrices = {
            f"{_HLO_TYPE[a.dtype.name]}[{','.join(map(str, dims))}]":
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(params)
            if a.ndim == 2 for dims in (a.shape, a.shape[::-1])}
        S, NB, K = geo["slots"], probe.blocks_per_slot, len(probe.kinds)
        pools = [shape((probe.kind_layers[k] * (by_kind[k] + 1),
                        geo["page_size"], w), jnp.bfloat16)
                 for k in probe.kinds for w in probe.line_widths]
        # what a slot keeps in a state layer and, where the family says
        # so, in an attention layer, for every slot
        states = [shape((s.shape[0], S, *s.shape[2:]), s.dtype)
                  for s in probe._states]
        state_bytes = sum(s.size * s.dtype.itemsize for s in states)
        programs = {
            "_step": (shape((S, 1), i32), shape((S,), i32),
                      shape((S,), jnp.bool_), *[shape((S, NB), i32)] * K,
                      *pools, *states),
            "_prefill_chunk": (shape((width,), i32), shape((), i32),
                               shape((), i32), *[shape((NB,), i32)] * K,
                               *pools, *([shape((), i32)] if states else []),
                               *states)}
        if getattr(probe, "drafts", 0):
            # a drafting family's decode program is the round, and its
            # launch takes the token after its last row
            programs = {
                "_round": (shape((S, 3), i32), shape((S,), jnp.bool_),
                           *[shape((S, NB), i32)] * K, *pools,
                           shape((S, 3), i32)),
                "_prefill_chunk": (*programs["_prefill_chunk"],
                                   shape((), i32))}
        for name, args in programs.items():
            compiled = getattr(probe, name).func.lower(
                params, *args).compile()
            if text:
                with open(os.path.join(
                        out, f"{entry['name']}.{name}.hlo"), "w") as fh:
                    fh.write(compiled.as_text())
            lines, copied = [], []
            for line in compiled.as_text().splitlines():
                found = _INSTRUCTION.match(line.strip())
                if found:
                    result = re.sub(r"[{][^}]*[}]", "", found.group(1))
                    lines.append(f"{found.group(2)} {result}")
                    if (found.group(2) == "copy" and result in matrices
                            and "S(1)" not in found.group(1)):
                        copied.append(matrices[result])
            with open(os.path.join(out, f"{entry['name']}.{name}.ops"),
                      "w") as fh:
                fh.write("\n".join(lines) + "\n")
            m = compiled.memory_analysis()
            printed.append({
                "config": entry["name"], "program": name,
                "instructions": len(lines),
                "argument_bytes": m.argument_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "code_bytes": m.generated_code_size_in_bytes,
                "weight_copies": len(copied),
                "weight_copy_bytes": sum(copied),
                "state_bytes": state_bytes})
            print(json.dumps(printed[-1]), flush=True)
    return printed


if __name__ == "__main__":
    if len(sys.argv) < 3:
        raise SystemExit(__doc__)
    more = sys.argv[3:]
    main(sys.argv[1], sys.argv[2], "text" in more,
         only=[name for name in more if name != "text"])
