"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers. Needs nothing but jax's own ``ProfileData``.

What a TPU trace holds (looked at by hand on a v5e, PR 23): a plane
``/device:TPU:<n>`` per chip with a line ``XLA Modules`` (one event per
program execution, named ``jit_<function>(<fingerprint>)``) and a line
``XLA Ops`` (one event per HLO operation that ran, named by its HLO text,
``%copy.9 = bf16[24,1025,32,16,64]{...} copy(...)``), and a plane
``/host:CPU`` with a line per host thread where ``TraceAnnotation`` spans
appear by name. Device and host events share one time base to within about
a millisecond (the device's events read that much early), so a gap is
attributed to a host span only above that scale.

Definitions, as the contract has them:

* busy: the union of the intervals in which an operation ran on the device
  (``XLA Ops``), clipped to the window, averaged over the chips;
* window: the span of the host annotation named ``bench:window`` if the
  trace has one, else first start to last end of all device operations and
  ``bench:`` spans;
* a program's time: the sum of the durations of its ``XLA Modules`` events
  whose midpoint lies in the window; an operation belongs to the module
  event that contains its start;
* the breakdown's device operations: per program, the time of each kind of
  operation (name without its number, result type and shape), its numbered
  instances summed, the ten largest;
* an idle gap: a maximal interval of the window with no device operation,
  charged to the ``bench:`` host spans that overlap it (innermost first),
  the rest to ``unattributed``.
"""
from __future__ import annotations

import bisect
import functools
import itertools
import re
from collections import defaultdict

WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
_OP = re.compile(r"^%?([\w.\-]+) = (?:\()?([a-z0-9]+\[[0-9,]*\])")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


@functools.lru_cache(maxsize=None)
def op_label(hlo_text: str, numbered: bool = True) -> str:
    """``%copy.9 = bf16[24,1025]{1,0:T(8,128)} copy(...)`` →
    ``copy.9_bf16_24_1025_`` (operation name, result type and shape);
    without ``numbered`` the name loses its number, ``copy_bf16_24_1025_``:
    the kind that the instances of one operation share. Kept by text: a
    trace holds millions of events of a few thousand operations."""
    m = _OP.match(hlo_text)
    if not m:
        return re.sub(r"[^\w.\-]+", "_", hlo_text)[:64]
    name = m.group(1) if numbered else re.sub(r"\.\d+$", "", m.group(1))
    return f"{name}_{re.sub(r'[^0-9a-z]+', '_', m.group(2))}"


def program_name(module_event: str) -> str:
    """``jit__step(1234)`` → ``_step``."""
    return _MODULE.match(module_event).group(1)


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce_trace(path: str) -> dict:
    """The numbers above from one ``.xplane.pb``. Times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, spans = [], []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:"):
            chips.append((_events(lines["XLA Modules"])
                          if "XLA Modules" in lines else [],
                          _events(lines["XLA Ops"])
                          if "XLA Ops" in lines else []))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith(SPAN_PREFIX)]
    if not chips or not any(ops for _, ops in chips):
        raise ValueError(f"{path}: no device operation in the trace")
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if window:
        w0, w1 = window[0][1], window[0][2]
    else:
        marks = [t for _, ops in chips for _, a, b in ops for t in (a, b)]
        marks += [t for _, a, b in spans for t in (a, b)]
        w0, w1 = min(marks), max(marks)
    spans = _Spans(s for s in spans if s[0] != WINDOW_SPAN)

    programs = defaultdict(lambda: {"count": 0, "total_s": 0.0,
                                    "ops": defaultdict(float),
                                    "kinds": defaultdict(float),
                                    "instances": defaultdict(set)})
    busy_ns, gaps = 0.0, defaultdict(float)
    for modules, ops in chips:
        modules = sorted((a, b, program_name(n)) for n, a, b in modules
                         if w0 <= (a + b) / 2 <= w1)
        starts = [m[0] for m in modules]
        for a, b, name in modules:
            programs[name]["count"] += 1
            programs[name]["total_s"] += (b - a) * 1e-9
        for name, a, b in ops:
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < modules[i][1]:
                prog, kind = programs[modules[i][2]], op_label(name, False)
                prog["ops"][op_label(name)] += (b - a) * 1e-9
                prog["kinds"][kind] += (b - a) * 1e-9
                prog["instances"][kind].add(op_label(name))
        busy = _union((max(a, w0), min(b, w1)) for _, a, b in ops
                      if b > w0 and a < w1)
        busy_ns += sum(b - a for a, b in busy)
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                _charge(gaps, g0, g1, spans.near(g0, g1))
    n = len(chips)
    top_ops = sorted(
        ((f"{p}:{kind}" + (f"(x{len(d['instances'][kind])})"
                           if len(d["instances"][kind]) > 1 else ""), s / n)
         for p, d in programs.items() for kind, s in d["kinds"].items()),
        key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "chips": n,
        "programs": {p: {"count": d["count"], "total_s": d["total_s"],
                         "ops": dict(d["ops"])}
                     for p, d in programs.items()},
        "device_ops": [[k, v] for k, v in top_ops[:10]],
        "idle_gaps": [[k, v * 1e-9 / n] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


class _Spans:
    """Host spans sorted by their start, beside the running maximum of
    their ends: those that can overlap an interval are a slice found by
    bisection, so charging a trace's gaps does not grow with gaps x spans
    (a window of 760 steps holds some ten thousand of each)."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [a for _, a, _ in self.spans]
        self.ends = list(itertools.accumulate(
            (b for _, _, b in self.spans), max))

    def near(self, g0, g1) -> list:
        """Every span that overlaps [g0, g1), and maybe some that end
        before it: none before the slice ends after ``g0``, none after it
        starts before ``g1``."""
        return self.spans[bisect.bisect_right(self.ends, g0):
                          bisect.bisect_left(self.starts, g1)]


def _charge(gaps, g0, g1, spans):
    """Charge the idle interval [g0, g1) to the host spans that overlap
    it: where spans nest, the shortest one covering a moment takes it.
    ``spans`` need hold only those that can overlap it (``_Spans.near``)."""
    cuts = sorted({g0, g1, *(t for _, a, b in spans for t in (a, b)
                             if g0 < t < g1)})
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(e - s, name) for name, s, e in spans if s <= mid < e]
        name = min(cover)[1][len(SPAN_PREFIX):] if cover else "unattributed"
        gaps[name] += b - a
