"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run is one new process: it builds the cell's configuration with weights
and inputs from ``--seed``, warms the shapes this cell's traffic uses (set-up),
measures for ``--seconds``, checks what the timed path produced against the
plain reference once the window has closed, and prints one JSON object as the
last line of its standard output. With ``--trace 0`` the line carries the
cell's end-to-end metrics; with ``--trace 1`` part of the window runs under
jax's profiler and the line carries the per-layer metrics and a breakdown.

It runs only on a TPU with at least the chips the cell asks for: anywhere
else it prints no result and exits 2. ``--rehearse`` is for the benchmark's
own tests: the same code path on the CPU at the tiny sizes each file gives
under ``rehearsal``, every time-valued metric printed as null.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default="",
                    help="comma-separated lower precisions (int8,fp8): also "
                         "print what the reference reads when computed in "
                         "them; the result is not changed")
    args = ap.parse_args(argv)

    from benchmark.lib import harness, traffic
    from benchmark.lib.compile_clock import CompileClock
    from benchmark.lib.peaks import peaks_for

    bench = harness.load_benchmark()
    cell, config = harness.find_cell(bench, args.workload)
    mix = traffic.load(cell["traffic"])
    if args.rehearse:
        config = {**config, **config.get("rehearsal", {})}
        mix = {**mix, **mix.get("rehearsal", {})}

    import jax

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"benchmark: {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"jax selected {len(devices)} x {dev.platform} "
              f"({dev.device_kind}). Nothing was run.", file=sys.stderr)
        return 2
    peaks = None if args.rehearse else peaks_for(dev.device_kind)

    from nnstreamer_tpu.utils.hw_accel import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    clock = CompileClock()
    tracer = None
    if args.trace:
        spec = mix["trace"]
        tracer = harness.Tracer(
            min(spec["start_s"], max(args.seconds - spec["seconds"], 0.0)),
            min(spec["seconds"], args.seconds))
    ctx = {"seed": args.seed, "seconds": args.seconds, "config": config,
           "mix": mix, "clock": clock, "tracer": tracer, "t_start": T_START,
           "rehearse": args.rehearse, "cell": cell, "bench": bench,
           "control": tuple(q for q in args.control.split(",") if q)}
    outcome = harness.driver_for(config).run(ctx)
    trace = tracer.reduce(need_device=not args.rehearse) if tracer else None

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome["memory_peak_bytes"]}
    print(json.dumps({"checks": [
        {"name": n, "value": v, "limit": lim}
        for n, v, lim in outcome["checks"]]}))
    print(f"benchmark: compile cache {cache_dir}; {clock.read()}",
          file=sys.stderr)
    if trace is not None:
        print("benchmark: traced programs " + json.dumps(
            {p: [d["count"], d["total_s"]]
             for p, d in trace["programs"].items()}), file=sys.stderr)
    line = harness.result_line(bench, cell, outcome, trace, bool(args.trace),
                               device, peaks, args.rehearse)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
