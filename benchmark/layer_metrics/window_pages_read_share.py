"""Pages a window layer's step reads over the pages a full layer's reads,
in %: ``pages_read_window`` over ``pages_read_full``, both written by the
engine on each ``engine.step.prepare`` span from its host mirrors, summed
over the steps of the passes inside the traced part of the window
(``lib/program_spans.py`` has the rule). What giving pages back behind the
window saves a step's attention. A program whose steps do not count pages
by kind leaves nothing to read."""
from benchmark.lib.program_spans import traced_passes


def read(facts):
    steps = [s.attrs for _, under in traced_passes(facts) or ()
             for s in under if s.name == "engine.step.prepare"
             and s.attrs.get("pages_read_full")]
    if not steps:
        return None
    return 100.0 * sum(a.get("pages_read_window", 0) for a in steps) \
        / sum(a["pages_read_full"] for a in steps)
