"""Share of a decode step's device time spent in the state-space layers'
mixers: the operations of ``programs.decode`` traced under ``ssm.*`` (input
projection, conv, the step size and maps, the states' update, output
projection) over the program's whole device time in the traced window, in
%. A program without the scopes leaves nothing to read."""
from benchmark.lib.readers_ssm import scope_share


def read(facts):
    return scope_share(facts, ("ssm.",), "decode")
