"""Share of its roofline that the step's line-attention kernel reaches over
the latent lines: the least time for the lines the live sequences see, once
a layer, with the queries in and the results out
(``lib/opcount_moe_cca.cca_decode``), averaged over the traced decode steps,
over the device time of the ``paged_line_attention`` kernel's calls in one
``_step``. Bound by HBM bytes."""
from benchmark.lib.opcount_moe_cca import cca_decode
from benchmark.lib.readers_moe_cca import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: cca_decode(
        cfg, active, context), "attn.full.kernel")
