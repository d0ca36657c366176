"""Seconds of set-up spent in backend compiles that a hit in the persistent
compile cache preceded: the loads (``lib/startup.py``, from the program's
own account). With ``setup_fresh_compile_s`` it is ``setup_compile_s``."""
from benchmark.lib.startup import split


def read(facts):
    return (split(facts) or {}).get("cache_load_s")
