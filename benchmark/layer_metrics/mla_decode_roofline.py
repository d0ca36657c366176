"""Share of its roofline that latent attention reaches in a decode step: the
visible lines of the live sequences read once (latent + rotary values a
token a layer), the new lines written, the projections' weights once
(``lib/opcount_moe_mla.mla_decode``), averaged over the traced decode steps,
over the device time under ``mla`` in one ``_step``."""
from benchmark.lib.opcount_moe_mla import mla_decode
from benchmark.lib.readers_moe_mla import roofline


def read(facts):
    cfg = facts["config"]
    return roofline(facts, lambda active, context, c: mla_decode(
        cfg, active, context), "mla")
