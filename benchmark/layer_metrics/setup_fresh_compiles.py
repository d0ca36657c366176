"""Backend compiles of set-up that no hit in the persistent compile cache
preceded, counted (``lib/startup.py``, from the program's own account)."""
from benchmark.lib.startup import split


def read(facts):
    return (split(facts) or {}).get("fresh_compiles")
