"""Experts that received at least one token over the expert slots (held
experts times expert layers), summed over the window's decode steps: the
program's own counters (``PagedLMEngine.layer_counts``). The share of the
expert weights a step has to read."""


def read(facts):
    steps, slots = facts.get("moe_steps"), facts.get("moe_expert_slots")
    if not steps or not slots:
        return None
    touched = sum(c["moe_experts_touched"] for _, c in steps)
    return 100.0 * touched / (slots * len(steps))
