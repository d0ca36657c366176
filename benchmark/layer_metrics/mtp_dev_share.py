"""Share of a round's device time spent in the MTP layer: the operations of
``programs.decode`` traced under ``mtp.embed``, ``mtp.block`` (its attention
and its expert layer with it) and ``mtp.head`` over the program's whole
device time in the traced window, in %."""
from benchmark.lib.readers_moe_mtp import decode_share_under


def read(facts):
    return decode_share_under(facts, "mtp.")
