"""Share of its roofline that the expert layers of a decode step reach: the
least time for the experts the step reached (the program's counters: each
reached expert's three matrices once, plus every expert layer's router and
shared experts; ``lib/opcount_moe_mla.moe_decode``), averaged over the
traced decode steps, over the device time under ``moe.*`` in one ``_step``.
Bound by HBM bytes at a decode batch."""
from benchmark.lib.opcount_moe_mla import moe_decode
from benchmark.lib.readers_moe_mla import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: moe_decode(
        cfg, active, c["moe_experts_touched"], c["moe_assignments"]), "moe.")
