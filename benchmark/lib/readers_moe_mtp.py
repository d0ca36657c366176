"""What the readers of the round share (``mtp_*``, ``verify_attn_dev_share``,
``moe_share_*``, ``gqa_verify_roofline``, ``moe_mtp_step_roofline``): the
rounds inside the traced part of the window, each with what the engine
wrote on its ``engine.step.prepare`` span (``rounds``, ``rows``,
``proposed``, ``accepted``, ``emitted``, ``pages_fetched_full | window``)
and what the expert layers counted in it. A program that runs no round (the
parent of the PR that added it) leaves nothing to read and every reader
returns ``None``."""
from __future__ import annotations

from benchmark.lib.opcount import least_seconds
from benchmark.lib.program_spans import traced_passes
from benchmark.lib.readers_moe_mla import scope_seconds


def traced_rounds(facts):
    """The attributes of every round's ``engine.step.prepare`` span inside
    the traced part of the window, or ``None``."""
    rounds = [s.attrs for _, under in traced_passes(facts) or ()
              for s in under if s.name == "engine.step.prepare"
              and s.attrs.get("rounds")]
    return rounds or None


def traced_counts(facts):
    """What the driver noted beside every round inside the traced part of
    the window (the expert layers' counters, the rows), or ``None``."""
    bounds, moe = facts.get("trace_bounds"), facts.get("moe_steps")
    if not bounds or bounds[1] is None or not moe:
        return None
    got = [c for t, c in moe if bounds[0] <= t <= bounds[1] and "rows" in c]
    return got or None


def decode_share_under(facts, prefix):
    """% of the decode program's device time under the scopes that start
    with ``prefix``."""
    got = scope_seconds(facts, prefix, keys=("decode",))
    if not got or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]


def roofline(facts, cost, *prefixes):
    """% of its roofline: the least time of ``cost`` (one round's mean)
    over the device time of one round under the given scopes (the whole
    program with none)."""
    peaks, trace = facts.get("peaks"), facts.get("trace")
    name = facts["config"].get("programs", {}).get("decode")
    if not peaks or not trace or name not in trace["programs"]:
        return None
    prog = trace["programs"][name]
    seconds = prog["total_s"]
    if prefixes:
        got = [scope_seconds(facts, p, keys=("decode",)) for p in prefixes]
        if not all(got):
            return None
        seconds = sum(g[0] for g in got)
    if not prog["count"] or seconds <= 0:
        return None
    return 100.0 * least_seconds(cost, peaks)[0] / (seconds / prog["count"])


def mean(rows, key):
    return sum(r.get(key, 0) for r in rows) / len(rows)
