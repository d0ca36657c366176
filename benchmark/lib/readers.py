"""What several per-layer readers share: each reader under
``layer_metrics/`` stays a few lines over these."""
from __future__ import annotations


def per_execution_ms(facts, key):
    """Mean device time of one execution of the program that the
    configuration's ``programs`` table names under ``key``: the sum of its
    ``XLA Modules`` events in the traced window over their number."""
    trace = facts.get("trace")
    name = facts["config"].get("programs", {}).get(key)
    if not trace or name not in trace["programs"]:
        return None
    prog = trace["programs"][name]
    return prog["total_s"] / prog["count"] * 1e3 if prog["count"] else None


def device_idle_share(facts):
    """1 - (union of device operation intervals) / traced window, in %."""
    trace = facts.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
