"""Device time of one decode step: mean duration of the executions of the
program ``programs.decode`` in the traced window."""
from benchmark.lib.readers import per_execution_ms


def read(facts):
    return per_execution_ms(facts, "decode")
