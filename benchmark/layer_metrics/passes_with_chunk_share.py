"""Of the scheduler passes that ran a decode step, the share that also ran
a prefill chunk: every live sequence's token gap is a chunk longer in such
a pass. Counted from the ``steps`` and ``chunks`` the scheduler writes on
each ``serving.pass`` span where it counts ``passes_with_step`` and
``passes_with_both``, over the passes inside the traced part of the window
(``lib/program_spans.py`` has the rule)."""
from benchmark.lib.program_spans import traced_passes


def read(facts):
    passes = traced_passes(facts)
    stepped = [p for p, _ in passes or () if p.attrs.get("steps")]
    if not stepped:
        return None
    return 100.0 * sum(1 for p in stepped if p.attrs.get("chunks")) \
        / len(stepped)
