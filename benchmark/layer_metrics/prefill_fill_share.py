"""How full the prefill launches were: the prompt tokens they ingested
(``n_valid``) over the rows they were compiled for (``width``), in %. Both
are written by the engine on each ``engine.chunk.prepare`` span; summed over
the chunks of the passes inside the traced part of the window
(``lib/program_spans.py`` has the rule). A wide launch reads the weights once
for many tokens when prompts fill it and pays its whole width for a short
prompt's few: this says which. A program whose chunks carry no width, or a
traced part without a chunk, leaves nothing to read."""
from benchmark.lib.program_spans import traced_passes


def read(facts):
    chunks = [s.attrs for _, under in traced_passes(facts) or ()
              for s in under if s.name == "engine.chunk.prepare"
              and s.attrs.get("width")]
    if not chunks:
        return None
    return 100.0 * sum(a.get("n_valid", 0) for a in chunks) \
        / sum(a["width"] for a in chunks)
