"""Per-element overhead microbenchmark.

The reference's headline quantitative claim (papers linked from its
README) is low per-element overhead vs raw framework invocation; this
measures ours, in two regimes:

* **host chains** (``tensor_debug``): the pure Python pad-hop cost of one
  element (pad push → chain → transform → push);
* **device chains** (``tensor_transform``): the pad-hop PLUS one
  ``jax.jit`` dispatch per element — the cost the device-segment fusion
  compiler (``nnstreamer_tpu/runtime/fusion.py``) deletes by collapsing a
  linear device run into ONE dispatch. Measured fused vs ``fuse=False``;
  the marginal per-element cost of an 8-element fused device chain must
  stay >= 3x lower than unfused (the r06 acceptance bar; ``--smoke``
  gates a softer 2x in CI to absorb shared-runner jitter).

It also gates the observability plane's cost contract
(docs/observability.md): with tracers and request tracing DISABLED the
hot paths pay one module-global check and nothing else — measured as a
host chain after an enable→disable cycle vs the same chain never
enabled, asserted within 2% (best-of-N to absorb shared-runner jitter).
Enabled-mode overhead (chrometrace + span tracing on) is REPORTED in
the JSON, not gated — turning tracing on is a deliberate trade. The
continuous profiler (obs/profile.py) gets the same leg with the same
<= 2% gate on its stopped fast path (queue/fusion/request hooks back to
one module-global check after ``profile.stop()``); profiler-enabled
overhead is reported alongside. The placement compiler
(runtime/placement.py) gets a steady-state leg too: a fused device
chain dispatching through an applied PlacementPlan must stay within 2%
of the same chain with placement off (planning runs at play(), never
per buffer). The memory accounting plane (obs/memory.py) gets the same
leg family: with accounting stopped the fused-dispatch/filter hooks are
one module-global check, gated <= 2%; enabled mode (one AOT lowering
per trace generation + static-estimate records) is reported alongside.
The data-plane quality taps (obs/quality.py) get the same leg on the
fused device chain: taps off = one module-global check, gated <= 2%;
taps on (sampled device-side health reductions) reported alongside.
The NNS_LEAKCHECK paired-resource ledger (analysis/sanitizer.py) gets
the same leg on the host chain: disabled = one module-global check per
note_* call site (and NOTHING on the per-buffer path, by construction),
gated <= 2%; enabled-mode ledger cost reported alongside. The
NNS_XFERCHECK transfer sanitizer (analysis/sanitizer.py third half)
gets the same leg on the fused DEVICE chain — its guard scope wraps the
fused dispatch itself: disabled = one module-global check at each choke
point, gated <= 2%; enabled mode (transfer-guard scopes + byte ledger)
reported alongside.

The serving plane's program spans (``obs.context.span``) are the one
instrument that is ALWAYS on, so their leg gates a cost, not a fast path:
one ``with span(...)`` with no profiler session must stay within 5 us
(ISSUE 24's budget: at most 12 a scheduler pass of 64 ms or more, under
0.1% of it); what it costs while a ``jax.profiler`` session records the
annotation is reported alongside.

Usage:
  python tools/microbench_overhead.py [n_frames]      # full report
  python tools/microbench_overhead.py --json OUT.json # + machine-readable
  python tools/microbench_overhead.py --smoke         # fast CI gate
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from nnstreamer_tpu.runtime.parse import parse_launch  # noqa: E402

HOST_ELEM = "tensor_debug output-mode=none"
DEVICE_ELEM = "tensor_transform mode=arithmetic option=add:1"


def measure(n_elems: int, n_bufs: int, elem: str = HOST_ELEM,
            fuse: bool = True, place=None) -> float:
    chain = " ! ".join([elem] * n_elems)
    pipe = parse_launch(
        f"tensor_src num-buffers={n_bufs} dimensions=16 types=float32 "
        f"! {chain} ! tensor_sink name=out max-stored=1", fuse=fuse,
        place=place)
    t0 = time.perf_counter()
    pipe.run(timeout=300)
    return (time.perf_counter() - t0) / n_bufs


def marginal_per_element(n_bufs: int, elem: str, fuse: bool,
                         n_lo: int = 1, n_hi: int = 8) -> dict:
    """us/frame at chain lengths n_lo and n_hi, and the marginal cost of
    one additional element ((t_hi - t_lo) / (n_hi - n_lo))."""
    t_lo = measure(n_lo, n_bufs, elem, fuse)
    t_hi = measure(n_hi, n_bufs, elem, fuse)
    return {
        "n_lo": n_lo, "n_hi": n_hi,
        "us_per_frame_lo": t_lo * 1e6,
        "us_per_frame_hi": t_hi * 1e6,
        "marginal_us_per_element": (t_hi - t_lo) / (n_hi - n_lo) * 1e6,
    }


def device_chain_report(n_bufs: int) -> dict:
    unfused = marginal_per_element(n_bufs, DEVICE_ELEM, fuse=False)
    fused = marginal_per_element(n_bufs, DEVICE_ELEM, fuse=True)
    # floor the fused marginal at a tenth of a microsecond: the fused hop
    # cost can measure as ~0 (or slightly negative, pure noise) because
    # the whole chain is one dispatch regardless of length
    denom = max(fused["marginal_us_per_element"], 0.1)
    return {
        "unfused": unfused,
        "fused": fused,
        "speedup_marginal": unfused["marginal_us_per_element"] / denom,
    }


def tracing_overhead_report(n_bufs: int, attempts: int = 3) -> dict:
    """Tracing cost in three states of an 8-element HOST chain (pure
    pad-hop path — the one every buffer of every stream pays):

    * ``baseline`` — tracing never enabled in this process;
    * ``enabled``  — chrometrace tracer installed + obs span tracing on;
    * ``disabled`` — after uninstall/disable: must match baseline (the
      one-module-global-check fast-path contract, gated at <= 2%).

    Shared runners drift at second scale, so baseline and disabled are
    measured as ADJACENT pairs (baseline leg, enable→disable cycle,
    disabled leg) and the gate reads the MINIMUM of the per-pair ratios:
    a genuine structural overhead shifts EVERY pair up (the cleanest
    pair still shows it), while a co-tenant spike only inflates some —
    the same a-real-regression-fails-every-attempt stance as the fused
    speedup gate and tests/test_throughput.
    """
    import statistics
    import tempfile

    from nnstreamer_tpu.obs import context as obs_context
    from nnstreamer_tpu.utils import trace as nns_trace

    measure(8, max(200, n_bufs // 4))  # warmup: imports/registries/allocs
    trace_path = os.path.join(tempfile.gettempdir(),
                              "nns_overhead_trace.json")
    baselines, disableds, enabled = [], [], None
    for i in range(attempts):
        baselines.append(measure(8, n_bufs))
        tracer = nns_trace.ChromeTraceTracer(path=trace_path)
        nns_trace.install_tracer(tracer)
        obs_context.enable_tracing()
        try:
            if enabled is None:
                enabled = measure(8, n_bufs)
        finally:
            nns_trace.uninstall_tracers()
            obs_context.disable_tracing()
            obs_context.reset()
        disableds.append(measure(8, n_bufs))
    ratios = [d / b for b, d in zip(baselines, disableds)]
    baseline = min(baselines)
    return {
        "n_frames": n_bufs,
        "attempts": attempts,
        "baseline_us_per_frame": baseline * 1e6,
        "enabled_us_per_frame": enabled * 1e6,
        "disabled_us_per_frame": min(disableds) * 1e6,
        "pair_ratios": [round(r, 4) for r in ratios],
        # the gated number: disabled fast path vs never-enabled baseline
        # (floor of the pairs — see docstring; median reported alongside)
        "disabled_overhead_frac": min(ratios) - 1.0,
        "disabled_overhead_frac_median": statistics.median(ratios) - 1.0,
        # reported, not gated: what turning tracing ON costs
        "enabled_overhead_frac": enabled / baseline - 1.0,
    }


SPAN_BUDGET_US = 5.0


def span_cost_report(n_spans: int = 20000, attempts: int = 5) -> dict:
    """Cost of one program span (``obs.context.span``: an allocation, the
    thread's stack, a ``TraceAnnotation`` and two clock reads, one ring
    append), best of ``attempts`` batches: with no profiler session (gated
    at :data:`SPAN_BUDGET_US`) and while a session records it (reported)."""
    import shutil
    import tempfile

    from nnstreamer_tpu.obs import context as obs_context

    def batch() -> float:
        t0 = time.perf_counter()
        for _ in range(n_spans):
            with obs_context.span("engine.step.prepare", live=3):
                pass
        return (time.perf_counter() - t0) / n_spans * 1e6

    batch()  # first use loads jax.profiler and fills the ring
    idle = min(batch() for _ in range(attempts))
    logdir = tempfile.mkdtemp(prefix="nns_span_cost_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        traced = min(batch() for _ in range(attempts))
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(logdir, ignore_errors=True)
        obs_context.reset()
    return {"n_spans": n_spans, "attempts": attempts,
            "no_session_us_per_span": idle, "budget_us": SPAN_BUDGET_US,
            # reported, not gated: a session is a deliberate trade
            "in_session_us_per_span": traced}


def profiler_overhead_report(n_bufs: int, attempts: int = 3) -> dict:
    """Continuous-profiler cost on an 8-element HOST chain, same
    three-state protocol (and the same min-of-pairs gate discipline) as
    :func:`tracing_overhead_report`:

    * ``baseline`` — profiler never started in this leg's pair;
    * ``enabled``  — ``obs.profile.start()`` (element tracer + queue/
      fused/request hooks + digest inserts) — REPORTED, not gated;
    * ``disabled`` — after ``stop()``: back to the one-module-global
      check, gated at <= 2% vs its paired baseline.
    """
    import statistics

    from nnstreamer_tpu.obs import profile as obs_profile

    measure(8, max(200, n_bufs // 4))  # warmup
    baselines, disableds, enabled = [], [], None
    for _ in range(attempts):
        baselines.append(measure(8, n_bufs))
        obs_profile.start()
        try:
            if enabled is None:
                enabled = measure(8, n_bufs)
        finally:
            obs_profile.stop()
            obs_profile.reset()
        disableds.append(measure(8, n_bufs))
    ratios = [d / b for b, d in zip(baselines, disableds)]
    baseline = min(baselines)
    return {
        "n_frames": n_bufs,
        "attempts": attempts,
        "baseline_us_per_frame": baseline * 1e6,
        "enabled_us_per_frame": enabled * 1e6,
        "disabled_us_per_frame": min(disableds) * 1e6,
        "pair_ratios": [round(r, 4) for r in ratios],
        "disabled_overhead_frac": min(ratios) - 1.0,
        "disabled_overhead_frac_median": statistics.median(ratios) - 1.0,
        "enabled_overhead_frac": enabled / baseline - 1.0,
    }


def memory_overhead_report(n_bufs: int, attempts: int = 3) -> dict:
    """Memory-accounting cost on an 8-element fused DEVICE chain (the
    hooks live on the fused dispatch and the filter invoke), same
    three-state protocol and min-of-pairs gate as the tracing/profiler
    legs:

    * ``baseline`` — accounting never enabled in this leg's pair;
    * ``enabled``  — ``obs.memory.start()`` (one AOT lowering per trace
      generation + static-estimate records) — REPORTED, not gated;
    * ``disabled`` — after ``stop()``: back to the one-module-global
      check, gated at <= 2% vs its paired baseline.
    """
    import statistics

    from nnstreamer_tpu.obs import memory as obs_memory

    measure(8, max(200, n_bufs // 4), DEVICE_ELEM)  # warmup
    baselines, disableds, enabled = [], [], None
    for _ in range(attempts):
        baselines.append(measure(8, n_bufs, DEVICE_ELEM))
        obs_memory.start()
        try:
            if enabled is None:
                enabled = measure(8, n_bufs, DEVICE_ELEM)
        finally:
            obs_memory.stop()
            obs_memory.reset()
        disableds.append(measure(8, n_bufs, DEVICE_ELEM))
    ratios = [d / b for b, d in zip(baselines, disableds)]
    baseline = min(baselines)
    return {
        "n_frames": n_bufs,
        "attempts": attempts,
        "baseline_us_per_frame": baseline * 1e6,
        "enabled_us_per_frame": enabled * 1e6,
        "disabled_us_per_frame": min(disableds) * 1e6,
        "pair_ratios": [round(r, 4) for r in ratios],
        "disabled_overhead_frac": min(ratios) - 1.0,
        "disabled_overhead_frac_median": statistics.median(ratios) - 1.0,
        "enabled_overhead_frac": enabled / baseline - 1.0,
    }


def quality_overhead_report(n_bufs: int, attempts: int = 3) -> dict:
    """Tensor-health-tap cost on an 8-element fused DEVICE chain (the
    taps ride the pad tracer hook AND the fused dispatch), same
    three-state protocol and min-of-pairs gate as the tracing/profiler/
    memory legs:

    * ``baseline`` — taps never enabled in this leg's pair;
    * ``enabled``  — ``obs.quality.start()`` (pad tracer + sampled
      device-side reductions every SAMPLE_EVERY buffers) — REPORTED,
      not gated;
    * ``disabled`` — after ``stop()``: back to the one-module-global
      check, gated at <= 2% vs its paired baseline.
    """
    import statistics

    from nnstreamer_tpu.obs import quality as obs_quality

    measure(8, max(200, n_bufs // 4), DEVICE_ELEM)  # warmup
    baselines, disableds, enabled = [], [], None
    for _ in range(attempts):
        baselines.append(measure(8, n_bufs, DEVICE_ELEM))
        obs_quality.start()
        try:
            if enabled is None:
                enabled = measure(8, n_bufs, DEVICE_ELEM)
        finally:
            obs_quality.stop()
            obs_quality.reset()
        disableds.append(measure(8, n_bufs, DEVICE_ELEM))
    ratios = [d / b for b, d in zip(baselines, disableds)]
    baseline = min(baselines)
    return {
        "n_frames": n_bufs,
        "attempts": attempts,
        "baseline_us_per_frame": baseline * 1e6,
        "enabled_us_per_frame": enabled * 1e6,
        "disabled_us_per_frame": min(disableds) * 1e6,
        "pair_ratios": [round(r, 4) for r in ratios],
        "disabled_overhead_frac": min(ratios) - 1.0,
        "disabled_overhead_frac_median": statistics.median(ratios) - 1.0,
        "enabled_overhead_frac": enabled / baseline - 1.0,
    }


def leakcheck_overhead_report(n_bufs: int, attempts: int = 3) -> dict:
    """NNS_LEAKCHECK ledger cost on an 8-element HOST chain — same
    three-state protocol and min-of-pairs gate as the tracing/profiler
    legs:

    * ``baseline`` — leakcheck never enabled in this leg's pair;
    * ``enabled``  — ``sanitizer.enable_leakcheck()`` (every
      note_acquire/note_release lands in the ledger) — REPORTED,
      not gated;
    * ``disabled`` — after ``disable_leakcheck()``: back to the
      one-module-global check, gated at <= 2% vs its paired baseline.

    The pad-hop path carries NO leakcheck hooks by construction (the
    ledger instruments control-plane pairs — calibration, spans,
    reservations — never per-buffer code), so this leg asserts exactly
    that: enabling the ledger must not perturb the steady-state buffer
    path, and the disabled fast path costs nothing where it matters
    most. Per-pair note_* cost is control-plane-rate and not measured
    here.
    """
    import statistics

    from nnstreamer_tpu.analysis import sanitizer as nns_sanitizer

    measure(8, max(200, n_bufs // 4))  # warmup
    baselines, disableds, enabled = [], [], None
    for _ in range(attempts):
        baselines.append(measure(8, n_bufs))
        nns_sanitizer.enable_leakcheck()
        try:
            if enabled is None:
                enabled = measure(8, n_bufs)
        finally:
            nns_sanitizer.disable_leakcheck()
            nns_sanitizer.reset_leakcheck()
        disableds.append(measure(8, n_bufs))
    ratios = [d / b for b, d in zip(baselines, disableds)]
    baseline = min(baselines)
    return {
        "n_frames": n_bufs,
        "attempts": attempts,
        "baseline_us_per_frame": baseline * 1e6,
        "enabled_us_per_frame": enabled * 1e6,
        "disabled_us_per_frame": min(disableds) * 1e6,
        "pair_ratios": [round(r, 4) for r in ratios],
        "disabled_overhead_frac": min(ratios) - 1.0,
        "disabled_overhead_frac_median": statistics.median(ratios) - 1.0,
        "enabled_overhead_frac": enabled / baseline - 1.0,
    }


def xfercheck_overhead_report(n_bufs: int, attempts: int = 3) -> dict:
    """NNS_XFERCHECK transfer-sanitizer cost on an 8-element fused
    DEVICE chain — the hooks live exactly where this leg measures: the
    fused dispatch runs under the transfer-guard scope and the choke
    points check the module global per buffer. Same three-state protocol
    and min-of-pairs gate as the tracing/profiler/leakcheck legs:

    * ``baseline`` — xfercheck never enabled in this leg's pair;
    * ``enabled``  — ``sanitizer.enable_xfercheck()`` (guard scopes
      armed + byte ledger recording) — REPORTED, not gated;
    * ``disabled`` — after ``disable_xfercheck()``: back to the
      one-module-global check, gated at <= 2% vs its paired baseline.
    """
    import statistics

    from nnstreamer_tpu.analysis import sanitizer as nns_sanitizer

    measure(8, max(200, n_bufs // 4), DEVICE_ELEM)  # warmup
    baselines, disableds, enabled = [], [], None
    for _ in range(attempts):
        baselines.append(measure(8, n_bufs, DEVICE_ELEM))
        nns_sanitizer.enable_xfercheck()
        try:
            if enabled is None:
                enabled = measure(8, n_bufs, DEVICE_ELEM)
        finally:
            nns_sanitizer.disable_xfercheck()
            nns_sanitizer.reset_xfercheck()
        disableds.append(measure(8, n_bufs, DEVICE_ELEM))
    ratios = [d / b for b, d in zip(baselines, disableds)]
    baseline = min(baselines)
    return {
        "n_frames": n_bufs,
        "attempts": attempts,
        "baseline_us_per_frame": baseline * 1e6,
        "enabled_us_per_frame": enabled * 1e6,
        "disabled_us_per_frame": min(disableds) * 1e6,
        "pair_ratios": [round(r, 4) for r in ratios],
        "disabled_overhead_frac": min(ratios) - 1.0,
        "disabled_overhead_frac_median": statistics.median(ratios) - 1.0,
        "enabled_overhead_frac": enabled / baseline - 1.0,
    }


def wirefuzz_overhead_report(n_bufs: int, attempts: int = 3) -> dict:
    """NNS_WIREFUZZ scorekeeper cost on the wire codec round trip — the
    one hot path it hooks (``_note_wire_bytes`` fires per encode and per
    decode in transport/frame.py). Same three-state protocol and
    min-of-pairs gate as the leakcheck/xfercheck legs:

    * ``baseline`` — wirefuzz never enabled in this leg's pair;
    * ``enabled``  — ``sanitizer.enable_wirefuzz()`` (frame ledger
      recording per codec call) — REPORTED, not gated;
    * ``disabled`` — after ``disable_wirefuzz()``: back to the
      one-module-global check, gated at <= 2% vs its paired baseline.
    """
    import statistics

    import numpy as np

    from nnstreamer_tpu import transport
    from nnstreamer_tpu.analysis import sanitizer as nns_sanitizer
    from nnstreamer_tpu.core import Buffer

    buf = Buffer([np.zeros((16,), np.float32)], meta={"tag": "bench"})

    def roundtrip(n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            transport.decode_frame(bytes(transport.encode_frame_bytes(buf)))
        return (time.perf_counter() - t0) / n

    roundtrip(max(200, n_bufs // 4))  # warmup
    baselines, disableds, enabled = [], [], None
    for _ in range(attempts):
        baselines.append(roundtrip(n_bufs))
        nns_sanitizer.enable_wirefuzz()
        try:
            if enabled is None:
                enabled = roundtrip(n_bufs)
        finally:
            nns_sanitizer.disable_wirefuzz()
        disableds.append(roundtrip(n_bufs))
    ratios = [d / b for b, d in zip(baselines, disableds)]
    baseline = min(baselines)
    return {
        "n_frames": n_bufs,
        "attempts": attempts,
        "baseline_us_per_frame": baseline * 1e6,
        "enabled_us_per_frame": enabled * 1e6,
        "disabled_us_per_frame": min(disableds) * 1e6,
        "pair_ratios": [round(r, 4) for r in ratios],
        "disabled_overhead_frac": min(ratios) - 1.0,
        "disabled_overhead_frac_median": statistics.median(ratios) - 1.0,
        "enabled_overhead_frac": enabled / baseline - 1.0,
    }


def placement_overhead_report(n_bufs: int, attempts: int = 3) -> dict:
    """Placement cost on an 8-element fused DEVICE chain: per-buffer
    steady state with a plan applied vs ``place`` off, same min-of-pairs
    discipline as the tracing/profiler legs (gate <= 2%).

    The plan pins the chain's one fused segment explicitly (an applied
    :class:`PlacementPlan` — no store, no calibration window), so the
    leg isolates exactly what every placed buffer pays: the composed
    jit lowered with ``in_shardings`` instead of default placement. The
    planning itself runs once at play() — off the hot path by
    construction — and calibration cost is a bounded one-time window,
    reported in docs/placement.md rather than gated here.
    """
    import statistics

    from nnstreamer_tpu.runtime.placement import Planner

    probe = parse_launch(
        "tensor_src num-buffers=1 dimensions=16 types=float32 ! "
        + " ! ".join([DEVICE_ELEM] * 8) + " ! tensor_sink max-stored=1")
    import jax

    plan = Planner(devices=[jax.devices()[0]]).plan(probe)
    measure(8, max(200, n_bufs // 4), DEVICE_ELEM)  # warmup
    baselines, placeds = [], []
    for _ in range(attempts):
        baselines.append(measure(8, n_bufs, DEVICE_ELEM))
        placeds.append(measure(8, n_bufs, DEVICE_ELEM, place=plan))
    ratios = [p / b for b, p in zip(baselines, placeds)]
    baseline = min(baselines)
    return {
        "n_frames": n_bufs,
        "attempts": attempts,
        "baseline_us_per_frame": baseline * 1e6,
        "placed_us_per_frame": min(placeds) * 1e6,
        "pair_ratios": [round(r, 4) for r in ratios],
        "placed_overhead_frac": min(ratios) - 1.0,
        "placed_overhead_frac_median": statistics.median(ratios) - 1.0,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("n_frames", nargs="?", type=int, default=4000)
    ap.add_argument("--json", dest="json_path", metavar="PATH",
                    help="write the full report as JSON (BENCH_r06.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast fused-vs-unfused regression gate for CI: "
                    "exit 1 when the 8-element device-chain marginal "
                    "speedup drops below 2x")
    args = ap.parse_args()

    if args.smoke:
        # tracing-overhead gate FIRST: it needs a process where tracing
        # was never enabled for its baseline leg
        tracing = tracing_overhead_report(n_bufs=2000, attempts=4)
        profiling = profiler_overhead_report(n_bufs=2000, attempts=4)
        # best-of-two: wall-clock ratios on shared CI runners flake under
        # co-tenant load spikes (same mitigation as tests/test_throughput);
        # a genuine regression fails BOTH measurements
        best = None
        for attempt in range(2):
            dev = device_chain_report(n_bufs=1500)
            if best is None or dev["speedup_marginal"] > best["speedup_marginal"]:
                best = dev
            if best["speedup_marginal"] >= 2.0:
                break
        placement = placement_overhead_report(n_bufs=1500, attempts=4)
        memory = memory_overhead_report(n_bufs=1500, attempts=4)
        quality = quality_overhead_report(n_bufs=1500, attempts=4)
        leakcheck = leakcheck_overhead_report(n_bufs=2000, attempts=4)
        xfercheck = xfercheck_overhead_report(n_bufs=1500, attempts=4)
        wirefuzz = wirefuzz_overhead_report(n_bufs=2000, attempts=4)
        spans = span_cost_report()
        best["span_cost"] = spans
        best["tracing_overhead"] = tracing
        best["profiler_overhead"] = profiling
        best["placement_overhead"] = placement
        best["memory_overhead"] = memory
        best["quality_overhead"] = quality
        best["leakcheck_overhead"] = leakcheck
        best["xfercheck_overhead"] = xfercheck
        best["wirefuzz_overhead"] = wirefuzz
        print(json.dumps(best, indent=2))
        ok = best["speedup_marginal"] >= 2.0
        print(f"smoke: fused marginal speedup {best['speedup_marginal']:.1f}x "
              f"({'OK' if ok else 'REGRESSION — below 2x on both attempts'})")
        trc_ok = tracing["disabled_overhead_frac"] <= 0.02
        verdict = ("OK" if trc_ok
                   else "REGRESSION — disabled tracing is not free anymore")
        print(f"smoke: tracing-disabled fast path "
              f"{tracing['disabled_overhead_frac'] * 100:+.2f}% vs baseline "
              f"(gate <= 2%), enabled mode "
              f"{tracing['enabled_overhead_frac'] * 100:+.1f}% ({verdict})")
        prof_ok = profiling["disabled_overhead_frac"] <= 0.02
        verdict = ("OK" if prof_ok
                   else "REGRESSION — stopped profiler is not free anymore")
        print(f"smoke: profiler-disabled fast path "
              f"{profiling['disabled_overhead_frac'] * 100:+.2f}% vs "
              f"baseline (gate <= 2%), enabled mode "
              f"{profiling['enabled_overhead_frac'] * 100:+.1f}% ({verdict})")
        plc_ok = placement["placed_overhead_frac"] <= 0.02
        verdict = ("OK" if plc_ok
                   else "REGRESSION — placed dispatch costs more than "
                        "default placement")
        print(f"smoke: placement steady-state per-buffer "
              f"{placement['placed_overhead_frac'] * 100:+.2f}% vs "
              f"place-off fused chain (gate <= 2%) ({verdict})")
        mem_ok = memory["disabled_overhead_frac"] <= 0.02
        verdict = ("OK" if mem_ok
                   else "REGRESSION — disabled memory accounting is not "
                        "free anymore")
        print(f"smoke: memory-accounting-disabled fast path "
              f"{memory['disabled_overhead_frac'] * 100:+.2f}% vs "
              f"baseline (gate <= 2%), enabled mode "
              f"{memory['enabled_overhead_frac'] * 100:+.1f}% ({verdict})")
        qual_ok = quality["disabled_overhead_frac"] <= 0.02
        verdict = ("OK" if qual_ok
                   else "REGRESSION — disabled quality taps are not "
                        "free anymore")
        print(f"smoke: quality-taps-disabled fast path "
              f"{quality['disabled_overhead_frac'] * 100:+.2f}% vs "
              f"baseline (gate <= 2%), enabled mode "
              f"{quality['enabled_overhead_frac'] * 100:+.1f}% ({verdict})")
        leak_ok = leakcheck["disabled_overhead_frac"] <= 0.02
        verdict = ("OK" if leak_ok
                   else "REGRESSION — disabled leakcheck is not free "
                        "anymore")
        print(f"smoke: leakcheck-disabled fast path "
              f"{leakcheck['disabled_overhead_frac'] * 100:+.2f}% vs "
              f"baseline (gate <= 2%), enabled mode "
              f"{leakcheck['enabled_overhead_frac'] * 100:+.1f}% ({verdict})")
        xc_ok = xfercheck["disabled_overhead_frac"] <= 0.02
        verdict = ("OK" if xc_ok
                   else "REGRESSION — disabled xfercheck is not free "
                        "anymore")
        print(f"smoke: xfercheck-disabled fast path "
              f"{xfercheck['disabled_overhead_frac'] * 100:+.2f}% vs "
              f"baseline (gate <= 2%), enabled mode "
              f"{xfercheck['enabled_overhead_frac'] * 100:+.1f}% ({verdict})")
        wf_ok = wirefuzz["disabled_overhead_frac"] <= 0.02
        verdict = ("OK" if wf_ok
                   else "REGRESSION — disabled wirefuzz is not free "
                        "anymore")
        print(f"smoke: wirefuzz-disabled fast path "
              f"{wirefuzz['disabled_overhead_frac'] * 100:+.2f}% vs "
              f"baseline (gate <= 2%), enabled mode "
              f"{wirefuzz['enabled_overhead_frac'] * 100:+.1f}% ({verdict})")
        span_ok = spans["no_session_us_per_span"] <= SPAN_BUDGET_US
        verdict = ("OK" if span_ok
                   else "REGRESSION — a program span costs more than its "
                        "budget")
        print(f"smoke: program span {spans['no_session_us_per_span']:.2f} us "
              f"with no profiler session (gate <= {SPAN_BUDGET_US:.0f} us), "
              f"{spans['in_session_us_per_span']:.2f} us inside one "
              f"({verdict})")
        sys.exit(0 if ok and trc_ok and prof_ok and plc_ok and mem_ok
                 and qual_ok and leak_ok and xc_ok and wf_ok and span_ok
                 else 1)

    n_bufs = args.n_frames
    report = {"n_frames": n_bufs, "host_chain": [], "device_chain": None,
              "tracing_overhead": None, "profiler_overhead": None,
              "placement_overhead": None, "memory_overhead": None,
              "quality_overhead": None, "leakcheck_overhead": None,
              "xfercheck_overhead": None, "wirefuzz_overhead": None,
              "span_cost": None}
    # before any other measurement: the baseline leg requires a process
    # where tracing has never been enabled
    report["tracing_overhead"] = tracing_overhead_report(
        n_bufs=min(n_bufs, 2000))
    t = report["tracing_overhead"]
    print("— tracing overhead (8-element host chain) —")
    print(f"baseline {t['baseline_us_per_frame']:8.1f} us/frame | "
          f"enabled {t['enabled_us_per_frame']:8.1f} "
          f"({t['enabled_overhead_frac'] * 100:+.1f}%) | "
          f"disabled {t['disabled_us_per_frame']:8.1f} "
          f"({t['disabled_overhead_frac'] * 100:+.2f}%, gate <= 2%)")
    report["profiler_overhead"] = profiler_overhead_report(
        n_bufs=min(n_bufs, 2000))
    t = report["profiler_overhead"]
    print("— continuous-profiler overhead (8-element host chain) —")
    print(f"baseline {t['baseline_us_per_frame']:8.1f} us/frame | "
          f"enabled {t['enabled_us_per_frame']:8.1f} "
          f"({t['enabled_overhead_frac'] * 100:+.1f}%) | "
          f"disabled {t['disabled_us_per_frame']:8.1f} "
          f"({t['disabled_overhead_frac'] * 100:+.2f}%, gate <= 2%)")
    report["placement_overhead"] = placement_overhead_report(
        n_bufs=min(n_bufs, 2000))
    t = report["placement_overhead"]
    print("— placement overhead (8-element fused device chain) —")
    print(f"place off {t['baseline_us_per_frame']:8.1f} us/frame | "
          f"placed {t['placed_us_per_frame']:8.1f} "
          f"({t['placed_overhead_frac'] * 100:+.2f}%, gate <= 2%)")
    report["memory_overhead"] = memory_overhead_report(
        n_bufs=min(n_bufs, 2000))
    t = report["memory_overhead"]
    print("— memory-accounting overhead (8-element fused device chain) —")
    print(f"baseline {t['baseline_us_per_frame']:8.1f} us/frame | "
          f"enabled {t['enabled_us_per_frame']:8.1f} "
          f"({t['enabled_overhead_frac'] * 100:+.1f}%) | "
          f"disabled {t['disabled_us_per_frame']:8.1f} "
          f"({t['disabled_overhead_frac'] * 100:+.2f}%, gate <= 2%)")
    report["quality_overhead"] = quality_overhead_report(
        n_bufs=min(n_bufs, 2000))
    t = report["quality_overhead"]
    print("— quality-tap overhead (8-element fused device chain) —")
    print(f"baseline {t['baseline_us_per_frame']:8.1f} us/frame | "
          f"enabled {t['enabled_us_per_frame']:8.1f} "
          f"({t['enabled_overhead_frac'] * 100:+.1f}%) | "
          f"disabled {t['disabled_us_per_frame']:8.1f} "
          f"({t['disabled_overhead_frac'] * 100:+.2f}%, gate <= 2%)")
    report["leakcheck_overhead"] = leakcheck_overhead_report(
        n_bufs=min(n_bufs, 2000))
    t = report["leakcheck_overhead"]
    print("— leakcheck overhead (8-element host chain) —")
    print(f"baseline {t['baseline_us_per_frame']:8.1f} us/frame | "
          f"enabled {t['enabled_us_per_frame']:8.1f} "
          f"({t['enabled_overhead_frac'] * 100:+.1f}%) | "
          f"disabled {t['disabled_us_per_frame']:8.1f} "
          f"({t['disabled_overhead_frac'] * 100:+.2f}%, gate <= 2%)")
    report["xfercheck_overhead"] = xfercheck_overhead_report(
        n_bufs=min(n_bufs, 2000))
    t = report["xfercheck_overhead"]
    print("— xfercheck overhead (8-element fused device chain) —")
    print(f"baseline {t['baseline_us_per_frame']:8.1f} us/frame | "
          f"enabled {t['enabled_us_per_frame']:8.1f} "
          f"({t['enabled_overhead_frac'] * 100:+.1f}%) | "
          f"disabled {t['disabled_us_per_frame']:8.1f} "
          f"({t['disabled_overhead_frac'] * 100:+.2f}%, gate <= 2%)")
    report["wirefuzz_overhead"] = wirefuzz_overhead_report(
        n_bufs=min(n_bufs, 2000))
    t = report["wirefuzz_overhead"]
    print("— wirefuzz overhead (wire codec round trip) —")
    print(f"baseline {t['baseline_us_per_frame']:8.1f} us/frame | "
          f"enabled {t['enabled_us_per_frame']:8.1f} "
          f"({t['enabled_overhead_frac'] * 100:+.1f}%) | "
          f"disabled {t['disabled_us_per_frame']:8.1f} "
          f"({t['disabled_overhead_frac'] * 100:+.2f}%, gate <= 2%)")
    report["span_cost"] = span_cost_report()
    t = report["span_cost"]
    print("— program span (obs.context.span, always on) —")
    print(f"no profiler session {t['no_session_us_per_span']:6.2f} us/span "
          f"(gate <= {t['budget_us']:.0f} us) | inside a session "
          f"{t['in_session_us_per_span']:6.2f} us/span")
    print("— host chains (tensor_debug): pure pad-hop cost —")
    prev = None
    for n in (1, 2, 4, 8, 16, 32):
        per_buf = measure(n, n_bufs)
        marginal = (per_buf - prev) / (n / 2) if prev is not None else None
        report["host_chain"].append(
            {"n": n, "us_per_frame": per_buf * 1e6,
             "marginal_us_per_element":
                 marginal * 1e6 if marginal is not None else None})
        print(f"chain={n:3d}: {per_buf * 1e6:8.1f} us/frame"
              + (f"   ~{marginal * 1e6:5.2f} us/element marginal"
                 if prev is not None else ""))
        prev = per_buf

    print("— device chains (tensor_transform): hop + jit dispatch —")
    dev = device_chain_report(n_bufs)
    report["device_chain"] = dev
    for mode in ("unfused", "fused"):
        m = dev[mode]
        print(f"{mode:8s}: chain=1 {m['us_per_frame_lo']:8.1f} us/frame, "
              f"chain=8 {m['us_per_frame_hi']:8.1f} us/frame, "
              f"marginal {m['marginal_us_per_element']:6.2f} us/element")
    print(f"fused marginal per-element speedup: "
          f"{dev['speedup_marginal']:.1f}x (target >= 3x)")

    if args.json_path:
        with open(args.json_path, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"wrote {args.json_path}")


if __name__ == "__main__":
    main()
