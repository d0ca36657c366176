"""The program's own spans, for the per-layer readers that read them.

The serving plane records a span tree per scheduler pass whenever it runs
(``nnstreamer_tpu.obs.context.span``: ``serving.pass`` with ``sched.admit``,
``engine.chunk.prepare|dispatch|pull``, ``engine.step.prepare|dispatch|pull``
and ``sched.route`` under it, on ``time.monotonic``) and a ``request`` tree
per retired request, in a bounded ring in memory. The driver drops the
scheduler before the readers run, so a reader takes the ring itself.

One rule for every reader of passes: a pass counts when it lies wholly
inside ``facts["trace_bounds"]``, the traced part of the window, so that
the numbers stand beside the device trace's; its descendants count with
it. A program without these spans leaves the ring empty and every reader
returns ``None``.
"""
from __future__ import annotations

from collections import defaultdict

ENGINE_CALLS = ("engine.chunk.", "engine.step.")


def _ring():
    from nnstreamer_tpu.obs import context

    spans = context.finished_spans()
    children = defaultdict(list)
    for s in spans:
        parent = getattr(s, "parent", None)
        if parent is not None:
            children[id(parent)].append(s)
    return spans, children


def traced_passes(facts):
    """``[(pass, [its descendants])]`` for the ``serving.pass`` spans inside
    the traced part of the window, or ``None`` with nothing to read."""
    bounds = facts.get("trace_bounds")
    if not bounds or bounds[1] is None:
        return None
    spans, children = _ring()

    def under(span):
        for child in children.get(id(span), ()):
            yield child
            yield from under(child)

    passes = [(s, list(under(s))) for s in spans
              if s.name == "serving.pass" and bounds[0] <= s.start_s
              and s.start_s + s.dur_s <= bounds[1]]
    return passes or None


def seconds_under(descendants, *prefixes) -> float:
    """Summed duration of the spans whose name starts with a prefix."""
    return sum(s.dur_s for s in descendants if s.name.startswith(prefixes))


def self_seconds(span, descendants) -> float:
    """A pass's duration less its engine calls: the scheduler's own code
    (``engine.release`` stays in it: the scheduler decides when to retire)."""
    return span.dur_s - seconds_under(descendants, *ENGINE_CALLS)


def mean_ms_per_call(facts, call: str, *phases):
    """Mean over the traced passes' calls of ``engine.<call>`` of the summed
    duration of the given phases, in ms; a call is counted by its
    ``dispatch`` span."""
    passes = traced_passes(facts)
    if not passes:
        return None
    spans = [s for _, under in passes for s in under]
    calls = sum(1 for s in spans if s.name == f"engine.{call}.dispatch")
    if not calls:
        return None
    names = tuple(f"engine.{call}.{phase}" for phase in phases)
    return 1e3 * sum(s.dur_s for s in spans if s.name in names) / calls


def finished_requests():
    """``[{span name: span}]`` for every ``request`` tree in the ring: the
    root under ``"request"`` and its phases under their names."""
    spans, children = _ring()
    return [{"request": s, **{c.name: c for c in children.get(id(s), ())}}
            for s in spans if s.name == "request"]
