"""Share of a decode step's device time spent in the full layers'
attention: the operations of ``programs.decode`` traced under ``attn.full``
(projections, YaRN rotary, the lines' write, the paged kernel over every
page a slot holds, output projection) over the program's whole device time
in the traced window, in %."""
from benchmark.lib.readers_moe_mla import scope_seconds


def read(facts):
    got = scope_seconds(facts, "attn.full", keys=("decode",))
    if not got or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
