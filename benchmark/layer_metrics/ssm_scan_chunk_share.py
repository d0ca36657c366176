"""Share of a prefill launch's device time spent in the state-space layers'
recurrence and conv: the operations of ``programs.prefill`` traced under
``ssm.scan`` and ``ssm.conv`` over the program's whole device time in the
traced window, in %. A window whose traced part holds no launch leaves
nothing to read."""
from benchmark.lib.readers_ssm import scope_share


def read(facts):
    return scope_share(facts, ("ssm.scan", "ssm.conv"), "prefill")
