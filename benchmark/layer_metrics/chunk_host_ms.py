"""Host time the engine spends before a prefill chunk is on its way: mean
of ``engine.chunk.prepare`` (page bookkeeping, padding) plus
``engine.chunk.dispatch`` (three uploads and the jitted call until it
returns) per chunk, over the passes inside the traced part of the window
(``lib/program_spans.py`` has the rule)."""
from benchmark.lib.program_spans import mean_ms_per_call


def read(facts):
    return mean_ms_per_call(facts, "chunk", "prepare", "dispatch")
