"""Share of its roofline that the whole decode step reaches: every weight
outside the routed experts once, the experts the step reached, the head,
the visible cache lines (``lib/opcount_moe_mla.step``), averaged over the
traced decode steps, over ``decode_step_dev_ms``."""
from benchmark.lib.opcount_moe_mla import step
from benchmark.lib.readers_moe_mla import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: step(
        cfg, active, context, c["moe_experts_touched"],
        c["moe_assignments"]))
