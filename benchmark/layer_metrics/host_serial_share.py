"""Share of the traced part of the window that the host spent in series
with the device, by the program's own account: the scheduler's own time in
every pass plus the engine's ``prepare`` and ``dispatch`` spans (a pass's
duration less its ``pull`` spans, in which the host waits for the device),
over the traced seconds (``lib/program_spans.py`` has the rule for which
passes count). Filed under the device because it is read against
``serving_device_idle``: idle the host does not account for is not the
serving loop's."""
from benchmark.lib.program_spans import seconds_under, traced_passes

PULLS = ("engine.chunk.pull", "engine.step.pull")


def read(facts):
    passes = traced_passes(facts)
    if not passes:
        return None
    bounds = facts["trace_bounds"]
    busy = sum(p.dur_s - seconds_under(under, *PULLS) for p, under in passes)
    return 100.0 * busy / (bounds[1] - bounds[0])
