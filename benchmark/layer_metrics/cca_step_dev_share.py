"""Share of a decode step's device time spent in the CCA attention part: the
operations of ``programs.decode`` traced under ``attn.full`` (down-
projections ``cca.in``, the convolutions, q-k mean, value shift, norms and
the state's read and write ``cca.mix``, the lines' write, the paged kernel,
``cca.out``, the part's merge) over the program's whole device time in the
traced window, in %."""
from benchmark.lib.readers_moe_cca import decode_share_under


def read(facts):
    return decode_share_under(facts, "attn.full")
