"""Share of a round's device time spent in the stack's expert layers: the
operations of ``programs.decode`` traced under ``moe.route``, ``moe.experts``
(this chip's 16 experts) and ``moe.shared`` (the MTP block's expert layer
is under ``mtp.``) over the program's whole device time in the traced
window, in %."""
from benchmark.lib.readers_moe_mtp import decode_share_under


def read(facts):
    return decode_share_under(facts, "moe.")
