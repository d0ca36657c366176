"""Share of its roofline that the expert layers of a decode step reach: the
least time for the experts the step reached (the program's counters: each
reached expert's three matrices once, plus every layer's router;
``lib/opcount_moe_gqa_window.moe_decode``), averaged over the traced decode
steps, over the device time under ``moe.*`` in one ``_step``. Bound by HBM
bytes at a decode batch."""
from benchmark.lib.opcount_moe_gqa_window import moe_decode
from benchmark.lib.readers_moe_mla import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: moe_decode(
        cfg, active, c["moe_experts_touched"], c["moe_assignments"]), "moe.")
