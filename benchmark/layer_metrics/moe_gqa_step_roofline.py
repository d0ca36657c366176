"""Share of its roofline that a whole decode step reaches: expert layers,
attention of both kinds, the head and the embedding rows
(``lib/opcount_moe_gqa_window.step``), averaged over the traced decode
steps, over the device time of one ``_step``."""
from benchmark.lib.opcount_moe_gqa_window import step
from benchmark.lib.readers_moe_mla import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: step(
        cfg, active, context, c["ctx_window"], c["moe_experts_touched"],
        c["moe_assignments"]))
