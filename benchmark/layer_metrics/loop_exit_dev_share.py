"""Share of a decode step's device time spent closing the passes: the
operations of ``programs.decode`` traced under ``loop.exit`` (the final
norm that ends every pass, the gate, the exit rule's running sums, the rows
that leave) over the program's whole device time in the traced window, in
%. A program without the scope leaves nothing to read."""
from benchmark.lib.readers_ssm import scope_share


def read(facts):
    return scope_share(facts, ("loop.exit",), "decode")
