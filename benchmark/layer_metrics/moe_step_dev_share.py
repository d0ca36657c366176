"""Share of a decode step's device time spent in the expert layers: the
operations of ``programs.decode`` traced under ``moe.*`` (router, sort and
grouped products) over the program's whole device time in the traced window,
in %. A program without the scopes leaves nothing to read."""
from benchmark.lib.readers_moe_mla import scope_seconds


def read(facts):
    got = scope_seconds(facts, "moe.", keys=("decode",))
    if not got or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
