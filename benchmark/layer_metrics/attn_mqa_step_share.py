"""Share of a decode step's device time spent in the attention layers
(multi-query: the narrowest line any cell has): the operations of
``programs.decode`` traced under ``attn.full`` over the program's whole
device time in the traced window, in %."""
from benchmark.lib.readers_ssm import scope_share


def read(facts):
    return scope_share(facts, ("attn.full",), "decode")
