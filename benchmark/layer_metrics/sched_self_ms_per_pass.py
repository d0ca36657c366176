"""The scheduler's own host time in a pass, mean over the passes inside the
traced part of the window: the duration of each ``serving.pass`` span less
its ``engine.chunk.*`` and ``engine.step.*`` descendants
(``lib/program_spans.py`` has the rule; ``engine.release`` stays in it)."""
from benchmark.lib.program_spans import self_seconds, traced_passes


def read(facts):
    passes = traced_passes(facts)
    if not passes:
        return None
    return 1e3 * sum(self_seconds(p, under) for p, under in passes) \
        / len(passes)
