"""Seconds of set-up jax spent tracing and lowering the program's functions,
paid at every start whatever the compile cache holds, no second counted
twice (``lib/startup.py``, from the program's own account)."""
from benchmark.lib.startup import split


def read(facts):
    return (split(facts) or {}).get("trace_lower_s")
