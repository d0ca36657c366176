"""How uneven the routing is: the largest number of tokens any one expert
received over the mean per expert slot, each summed over the expert layers
and the window's decode steps (the program's own counters). 1 is even."""


def read(facts):
    steps, slots = facts.get("moe_steps"), facts.get("moe_expert_slots")
    if not steps or not slots:
        return None
    assigned = sum(c["moe_assignments"] for _, c in steps)
    if not assigned:
        return None
    layers = slots / facts["config"]["experts_held"][1]
    largest = sum(c["moe_max_load"] for _, c in steps) / layers
    return largest / (assigned / slots)
