"""Share of its roofline that the experts' kernel reaches at one assignment
a row: the least time for the three matrices of the experts the step reached
(the program's counters) and the rows in and out
(``lib/opcount_moe_cca.moe_top1``), averaged over the traced decode steps,
over the device time of the ``grouped_experts`` kernel's calls in one
``_step``. Bound by HBM bytes at a decode batch."""
from benchmark.lib.opcount_moe_cca import moe_top1
from benchmark.lib.readers_moe_cca import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: moe_top1(
        cfg, active, c["moe_experts_touched"], c["moe_assignments"]),
        "moe.experts.kernel")
