"""Share of a decode step's device time spent in the blocks' dense gated
MLPs: the operations of ``programs.decode`` traced under ``mlp`` over the
program's whole device time in the traced window, in %."""
from benchmark.lib.readers_ssm import scope_share


def read(facts):
    return scope_share(facts, ("mlp",), "decode")
