"""What the readers of the expert and latent-attention layers share
(``moe_dev_share``, ``mla_dev_share``, the three rooflines): device time by
``jax.named_scope`` region, and the window's decode steps paired with what
the expert layers counted in them. A program without the scopes or the
counters (the parent of the PR that added them) gives ``None`` everywhere."""
from __future__ import annotations

from benchmark.lib.opcount import least_seconds

PROGRAMS = ("decode", "prefill")


def scope_seconds(facts, prefix, keys=PROGRAMS):
    """``(seconds under the scopes that start with prefix, seconds of the
    programs, executions of the first program)`` over the traced window for
    the programs the configuration names under ``keys``; ``None`` without
    a trace or the scope map."""
    trace, scopes = facts.get("trace"), facts.get("op_scopes")
    names = [facts["config"].get("programs", {}).get(k) for k in keys]
    if not trace or not scopes or any(
            n not in trace["programs"] or n not in scopes for n in names):
        return None
    under = total = 0.0
    for name in names:
        prog = trace["programs"][name]
        total += prog["total_s"]
        under += sum(s for op, s in prog["ops"].items()
                     if (scopes[name].get(op) or "").startswith(prefix))
    return under, total, trace["programs"][names[0]]["count"]


def share_under(facts, prefix):
    """% of the device time of both programs spent under a scope."""
    got = scope_seconds(facts, prefix)
    if not got or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]


def traced_steps(facts):
    """``[(active, context tokens, counts)]`` for the decode steps inside
    the traced part of the window, or ``None``."""
    bounds = facts.get("trace_bounds")
    steps, moe = facts.get("decode_steps"), facts.get("moe_steps")
    if not bounds or bounds[1] is None or not steps or not moe:
        return None
    counts = {t: c for t, c in moe}
    out = [(active, context, counts[t]) for t, active, context, _ in steps
           if bounds[0] <= t <= bounds[1] and t in counts]
    return out or None


def roofline(facts, cost_of, prefix=None):
    """% of its roofline: the mean least time of ``cost_of(active, context,
    counts)`` over the traced decode steps, over the device time of one
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
