"""Seconds of set-up spent in backend compiles that no hit in the persistent
compile cache preceded (``lib/startup.py``, from the program's own
account): the whole of a cold start's excess, and warm the small programs
jax never caches."""
from benchmark.lib.startup import split


def read(facts):
    return (split(facts) or {}).get("fresh_compile_s")
