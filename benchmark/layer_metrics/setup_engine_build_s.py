"""Seconds of set-up the program spent building what it serves from: its
``setup.params`` and ``setup.engine`` spans, less jax's seconds charged to
them (``lib/startup.py``, from the program's own account)."""
from benchmark.lib.startup import split


def read(facts):
    return (split(facts) or {}).get("engine_build_s")
