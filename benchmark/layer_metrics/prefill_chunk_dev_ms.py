"""Device time of one prefill chunk: mean duration of the executions of the
program ``programs.prefill`` in the traced window."""
from benchmark.lib.readers import per_execution_ms


def read(facts):
    return per_execution_ms(facts, "prefill")
