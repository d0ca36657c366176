"""Share of its roofline that a whole decode step reaches: every layer's
weights outside the experts once, the reached experts, the visible lines and
the new ones, every slot's state read and written, the tied head
(``lib/opcount_moe_cca.step``), averaged over the traced decode steps, over
the device time of one ``_step``."""
from benchmark.lib.opcount_moe_cca import step
from benchmark.lib.readers_moe_cca import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: step(
        cfg, active, context, c["moe_experts_touched"], c["moe_assignments"],
        cfg["engine"]["slots"]))
