"""How far a prefill launch's attention follows what its slot holds: the
positions its attention layers read (``ctx_read``: the blocks the launch's
walk visits times the positions of a block, every layer's added up, by the
walk's own rule) over what a gathered copy of everything a slot may hold
would have (``ctx_padded``: the serving limit a full layer, the held blocks
a window layer), in %. Both are written by the engine on each
``engine.chunk.prepare`` span from its host mirrors; summed over the chunks
of the passes inside the traced part of the window
(``lib/program_spans.py`` has the rule). A program whose launches gather the
padded context counts neither and leaves nothing to read, as does a traced
part without a chunk."""
from benchmark.lib.program_spans import traced_passes


def read(facts):
    chunks = [s.attrs for _, under in traced_passes(facts) or ()
              for s in under if s.name == "engine.chunk.prepare"
              and s.attrs.get("ctx_padded")]
    if not chunks:
        return None
    return 100.0 * sum(a.get("ctx_read", 0) for a in chunks) \
        / sum(a["ctx_padded"] for a in chunks)
