"""Median time a request waited in the scheduler's queue for a slot, from
the program's ``Request.metrics["queue_wait_s"]``."""
from benchmark.lib.stats import median


def read(facts):
    waits = facts.get("queue_wait_ms")
    return median(waits) if waits else None
