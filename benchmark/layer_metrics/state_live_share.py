"""How much of the state a decode step reads and writes belongs to a live
sequence: ``state_slots_live`` over ``state_slots``, both written by the
engine on each ``engine.step.prepare`` span, summed over the steps of the
passes inside the traced part of the window, in %. A program that keeps no
state beside its pages leaves nothing to read."""
from benchmark.lib.readers_ssm import span_attrs


def read(facts):
    steps = span_attrs(facts, "engine.step.prepare", "state_slots")
    total = sum(a["state_slots"] for a in steps)
    if not total:
        return None
    return 100.0 * sum(a["state_slots_live"] for a in steps) / total
