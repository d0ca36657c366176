"""Drafts the stack agreed with over drafts proposed, in %: the engine's own
account of the rounds inside the traced part of the window (``accepted`` and
``proposed`` on each ``engine.step.prepare`` span). What trained weights
would raise and seeded weights leave near 0: recorded, judged by nobody."""
from benchmark.lib.readers_moe_mtp import traced_rounds


def read(facts):
    rounds = traced_rounds(facts)
    proposed = sum(r.get("proposed", 0) for r in rounds or ())
    if not proposed:
        return None
    return 100.0 * sum(r.get("accepted", 0) for r in rounds) / proposed
