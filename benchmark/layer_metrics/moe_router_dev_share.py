"""Share of a decode step's device time under ``moe.router``: the
projection to the router's width, the depth average (the carry from the
layer before), the norm, the three-layer MLP in float32 at ``highest``, the
softmax and the choice, in %."""
from benchmark.lib.readers_moe_cca import decode_share_under


def read(facts):
    return decode_share_under(facts, "moe.router")
