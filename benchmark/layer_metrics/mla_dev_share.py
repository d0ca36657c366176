"""Share of the device time of the two serving programs spent in latent
attention: the operations traced under ``mla`` (projections, the line's
write, the gather of the slots' lines, both contractions, the output
projection) over ``_step`` + ``_prefill_chunk`` in the traced window."""
from benchmark.lib.readers_moe_mla import share_under


def read(facts):
    return share_under(facts, "mla")
