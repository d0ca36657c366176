"""Host time the engine spends before a decode step is on its way: mean of
``engine.step.prepare`` (the page bookkeeping over the live slots) plus
``engine.step.dispatch`` (the jitted call, with the block table as an
argument, until it returns) per step, over the passes inside the traced
part of the window (``lib/program_spans.py`` has the rule)."""
from benchmark.lib.program_spans import mean_ms_per_call


def read(facts):
    return mean_ms_per_call(facts, "step", "prepare", "dispatch")
