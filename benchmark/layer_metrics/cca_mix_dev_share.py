"""Share of a decode step's device time under ``cca.mix``: the two
convolutions over the packed latent row, the q-k mean, the value shift, the
norms and rotation, and the read and write of the state a slot keeps in
every attention layer: what the state costs a step, in %."""
from benchmark.lib.readers_moe_cca import decode_share_under


def read(facts):
    return decode_share_under(facts, "attn.full.cca.mix")
