"""Share of a round's device time spent in the stack's attention, both layer
kinds: the operations of ``programs.decode`` traced under ``attn.window`` and
``attn.full`` (projections, q/k norms, rotary, the two rows' lines written,
the paged kernel with two queries a slot, output projection; the MTP
block's attention is under ``mtp.``) over the program's whole device time in
the traced window, in %."""
from benchmark.lib.readers_moe_mtp import decode_share_under


def read(facts):
    return decode_share_under(facts, "attn.")
