"""Device idle time by the host span that covers it, for any span prefix.

    python3 benchmark/tools/idle_by_span.py <trace.xplane.pb> --prefix nns:

The run's own reduction (``lib/xplane.reduce_trace``) charges idle gaps to
the benchmark proxy's ``bench:`` spans only. This tool reads a trace kept
with ``BENCH_KEEP_TRACE=<dir>`` and charges the same gaps, by the same rule
(``xplane._charge``: the shortest span of the prefix covering a moment takes
it, the rest is ``unattributed``), to the spans of the prefix given: with
``nns:`` to the program's own (``nnstreamer_tpu.obs.context.span``), which
split the proxy's ``step`` and ``prefill_tick`` boxes into prepare, dispatch
and pull. With ``bench:`` it reproduces the run's ``idle_gaps``. It also
prints each span's count and mean duration, so that one kept trace holds
both families on one clock, and how much of the window the prefix's spans
cover: in all, and between the first and the last of them (the profiler
keeps a span only if it began and ended inside the session, so the pass
that the session's start or end cut is missing at either edge). Outside the
run's path: nothing imports it.

Window and busy time are ``reduce_trace``'s: the ``bench:window`` span where
the trace has one (else first start to last end of the device's operations
and the prefix's spans), and the union of the ``XLA Ops`` intervals.
"""
from __future__ import annotations

import argparse
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import xplane  # noqa: E402


def idle_by_span(path: str, prefix: str) -> dict:
    """``{"window_s", "idle_s", "idle": {span: s}, "spans": {span: (count,
    mean ms)}, "covered", "covered_inside"}`` from one ``.xplane.pb``; span
    names without the prefix, idle seconds averaged over the chips, the
    two coverages as shares of the window and of the stretch from the
    first span's start to the last one's end."""
    from jax.profiler import ProfileData

    chips, spans, window = [], [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            chips.append([ev for ln in plane.lines if ln.name == "XLA Ops"
                          for ev in xplane._events(ln)])
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for ev in xplane._events(ln):
                    if ev[0] == xplane.WINDOW_SPAN:
                        window = window or ev
                    elif ev[0].startswith(prefix):
                        spans.append(ev)
    if not any(chips):
        raise ValueError(f"{path}: no device operation in the trace")
    if window:
        w0, w1 = window[1], window[2]
    else:
        marks = [t for ops in chips for _, a, b in ops for t in (a, b)]
        marks += [t for _, a, b in spans for t in (a, b)]
        w0, w1 = min(marks), max(marks)

    # _charge cuts the prefix it knows off a name: hand it ours under that
    renamed = xplane._Spans((xplane.SPAN_PREFIX + n[len(prefix):], a, b)
                            for n, a, b in spans)
    gaps, idle_ns = defaultdict(float), 0.0
    for ops in chips:
        busy = xplane._union((max(a, w0), min(b, w1)) for _, a, b in ops
                             if b > w0 and a < w1)
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                idle_ns += g1 - g0
                xplane._charge(gaps, g0, g1, renamed.near(g0, g1))
    n = len(chips)
    by_name = defaultdict(list)
    for name, a, b in spans:
        if b > w0 and a < w1:
            by_name[name[len(prefix):]].append(b - a)
    cover = xplane._union((max(a, w0), min(b, w1)) for _, a, b in spans
                          if b > w0 and a < w1)
    covered_ns = sum(b - a for a, b in cover)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "covered": covered_ns / (w1 - w0),
        "covered_inside": (covered_ns / (cover[-1][1] - cover[0][0])
                           if cover else 0.0),
        "idle_s": idle_ns * 1e-9 / n,
        "idle": {k: v * 1e-9 / n for k, v in
                 sorted(gaps.items(), key=lambda kv: -kv[1])},
        "spans": {k: (len(v), sum(v) / len(v) * 1e-6)
                  for k, v in sorted(by_name.items())},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb kept with BENCH_KEEP_TRACE")
    ap.add_argument("--prefix", default="nns:",
                    help="host spans to charge (nns: the program's, bench: "
                         "the benchmark proxy's)")
    args = ap.parse_args(argv)
    out = idle_by_span(args.trace, args.prefix)
    print(f"window {out['window_s']:.3f} s, device idle {out['idle_s']:.4f} s"
          f" ({100 * out['idle_s'] / out['window_s']:.2f}%), "
          f"prefix {args.prefix}")
    print(f"spans cover {100 * out['covered']:.3f}% of the window, "
          f"{100 * out['covered_inside']:.3f}% from the first to the last")
    print(f"{'span':<28}{'count':>7}{'mean ms':>10}{'idle s':>10}{'of idle':>9}")
    names = list(out["idle"]) + [k for k in out["spans"]
                                 if k not in out["idle"]]
    for name in names:
        count, mean_ms = out["spans"].get(name, (0, 0.0))
        idle = out["idle"].get(name, 0.0)
        share = 100 * idle / out["idle_s"] if out["idle_s"] else 0.0
        print(f"{name:<28}{count:>7}{mean_ms:>10.3f}{idle:>10.4f}"
              f"{share:>8.1f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
