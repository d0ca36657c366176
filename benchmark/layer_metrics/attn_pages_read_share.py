"""How far a decode step's attention follows what is visible: the pages the
live slots held (what the step's kernel reads, ``pages_read``) over the
pages of every slot's whole block table (what attention over padded
positions read, ``pages_padded``), in %. Both are written by the engine on
each ``engine.step.prepare`` span from its host mirrors; summed over the
steps of the passes inside the traced part of the window
(``lib/program_spans.py`` has the rule). A program whose steps do not count
pages leaves nothing to read."""
from benchmark.lib.program_spans import traced_passes


def read(facts):
    steps = [s.attrs for _, under in traced_passes(facts) or ()
             for s in under if s.name == "engine.step.prepare"
             and s.attrs.get("pages_padded")]
    if not steps:
        return None
    return 100.0 * sum(a.get("pages_read", 0) for a in steps) \
        / sum(a["pages_padded"] for a in steps)
