"""Share of a decode step's device time spent in the window layers'
attention: the operations of ``programs.decode`` traced under
``attn.window`` (projections, rotary, the lines' write, the paged kernel
from the window's first page on, output projection) over the program's
whole device time in the traced window, in %."""
from benchmark.lib.readers_moe_mla import scope_seconds


def read(facts):
    got = scope_seconds(facts, "attn.window", keys=("decode",))
    if not got or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
