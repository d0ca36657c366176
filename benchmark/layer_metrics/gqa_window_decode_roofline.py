"""Share of its roofline that a decode step's attention reaches, both layer
kinds together: the least time for the projections' weights once, the lines
the live sequences see by layer kind (the whole context in a full layer,
``min(context, sliding_window)`` in a window layer; the driver's count per
step) and the lines written (``lib/opcount_moe_gqa_window.gqa_decode``),
averaged over the traced decode steps, over the device time under ``attn.*``
in one ``_step``."""
from benchmark.lib.opcount_moe_gqa_window import gqa_decode
from benchmark.lib.readers_moe_mla import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: gqa_decode(
        cfg, active, context, c["ctx_window"]), "attn.")
