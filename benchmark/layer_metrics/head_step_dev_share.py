"""Share of a decode step's device time under ``head``: the final norm and
the tied head, 262,272 rows of 2048 read for the batch's rows, and the best
token of each, in %."""
from benchmark.lib.readers_moe_cca import decode_share_under


def read(facts):
    return decode_share_under(facts, "head")
