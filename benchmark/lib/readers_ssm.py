"""What the readers of the state-space layers share (``ssm_*``,
``state_live_share``, the three rooflines): the window's decode steps and
prefill launches inside the traced part, and a roofline share from a cost
function. A program without the scopes, the counters or the spans (the
parent of the PR that added them) gives ``None`` everywhere."""
from __future__ import annotations

from benchmark.lib import program_spans
from benchmark.lib.opcount import least_seconds
from benchmark.lib.readers_moe_mla import scope_seconds


def traced_steps(facts):
    """``[(active, context tokens)]`` for the decode steps inside the traced
    part of the window, or ``None``."""
    bounds, steps = facts.get("trace_bounds"), facts.get("decode_steps")
    if not bounds or bounds[1] is None or not steps:
        return None
    out = [(active, context) for t, active, context, _ in steps
           if bounds[0] <= t <= bounds[1]]
    return out or None


def span_attrs(facts, name: str, key: str):
    """The attributes of the spans called ``name`` that carry ``key``, under
    the passes inside the traced part of the window."""
    return [s.attrs for _, under in program_spans.traced_passes(facts) or ()
            for s in under if s.name == name and key in s.attrs]


def scope_share(facts, prefixes, key: str):
    """% of one program's device time under the scopes that start with one
    of ``prefixes``."""
    under = total = 0.0
    for prefix in prefixes:
        got = scope_seconds(facts, prefix, keys=(key,))
        if not got or got[1] <= 0:
            return None
        under, total = under + got[0], got[1]
    return 100.0 * under / total


def step_roofline(facts, cost_of, prefix=None):
    """% of its roofline: the mean least time of ``cost_of(active,
    context)`` over the traced decode steps, over the device time of one
    decode step (under ``prefix``, or whole when ``prefix`` is None)."""
    steps, peaks = traced_steps(facts), facts.get("peaks")
    if not steps or not peaks:
        return None
    if prefix is None:
        trace = facts.get("trace")
        name = facts["config"].get("programs", {}).get("decode")
        if not trace or name not in trace["programs"]:
            return None
        prog = trace["programs"][name]
        seconds, calls = prog["total_s"], prog["count"]
    else:
        got = scope_seconds(facts, prefix, keys=("decode",))
        if not got:
            return None
        seconds, _, calls = got
    if not calls or seconds <= 0:
        return None
    least = [least_seconds(cost_of(*s), peaks)[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (seconds / calls)
