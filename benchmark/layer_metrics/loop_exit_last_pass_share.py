"""Of the tokens the window's decode steps made, the share whose logits
came from the last pass over the stack: the engine's ``exit_pass_<t>``
counts of the highest ``t`` over the sum of all of them (``facts[
"exit_passes"]``: the steps' counts at the window's end less its opening),
in %. At an ``early_exit_threshold`` of 1 the exit rule sends every token
to the last pass, so anything under 100 says a token's logits left early. A
program that counts no exits leaves nothing to read."""


def read(facts):
    counts = facts.get("exit_passes") or {}
    total = sum(counts.values())
    if not total:
        return None
    last = max(counts, key=lambda name: int(name.rsplit("_", 1)[1]))
    return 100.0 * counts[last] / total
