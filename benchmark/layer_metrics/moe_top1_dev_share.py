"""Share of a decode step's device time under ``moe.experts``: the top-1
assignments' bookkeeping and the experts' kernel, which streams each reached
expert's 25 MB for the two rows it got, in %."""
from benchmark.lib.readers_moe_cca import decode_share_under


def read(facts):
    return decode_share_under(facts, "moe.experts")
