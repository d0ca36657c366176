"""Tokens a live slot gets a round: ``emitted`` over ``proposed`` (one draft
a live slot a round) on the traced rounds' ``engine.step.prepare`` spans. 1
at acceptance 0, 2 where every draft holds: what ``tpot_p50_ms`` divides a
round's length by."""
from benchmark.lib.readers_moe_mtp import traced_rounds


def read(facts):
    rounds = traced_rounds(facts)
    slots = sum(r.get("proposed", 0) for r in rounds or ())
    if not slots:
        return None
    return sum(r.get("emitted", 0) for r in rounds) / slots
