"""Hot-swap downtime benchmark + AOT cold-start leg + headless smoke.

Measures what the service control plane promises: a model swap on a LIVE
service costs no request errors and no visible gap in delivery. A
steady-rate pipeline streams through a slot-bound ``tensor_filter`` while
the slot hot-swaps between two versions; every buffer's arrival at the
sink is timestamped, and the report compares the p99 inter-arrival gap
in the flip window against the steady-state batch interval.

The ``--cold-start`` leg measures the AOT compile-cache promise
(docs/aot.md, ``AOT_r14.json``): restart-to-READY of a fresh process
against a COLD vs a WARM ``NNS_AOT_CACHE`` (min-of-pairs; warm must be
>= 3x faster — each leg is a real subprocess so every interpreter + jit
cost is paid), distinct-compilation count across all serving buckets
with a shape-poly artifact (== 1 total, vs one Python trace per bucket
on the plain-jit path), and fused-vs-host byte parity for
artifact-LOADED segments.

    python tools/bench_service.py                 # bench, writes JSON
    python tools/bench_service.py --cold-start    # AOT leg -> AOT_r14.json
    python tools/bench_service.py --smoke         # CI: register, health-
                                                  # check, swap, drain
    python tools/bench_service.py --cold-start --smoke   # CI: 1 pair,
                                                  # smaller model, lenient
                                                  # gate (warm < cold)
Exit nonzero when the acceptance property fails (errors during the flip,
or flip-window p99 gap above one batch interval + steady p99; for the
cold-start leg: speedup/coverage/parity gates).

Process layout: the parent initializes jax and the cold-start leg starts
child interpreters that do too, and a TPU chip belongs to one process at
a time. It is a CPU tool: run it with ``JAX_PLATFORMS=cpu`` (as CI does).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _mgr():
    from nnstreamer_tpu.service import RestartPolicy, ServiceManager

    mgr = ServiceManager(jitter_seed=0)
    mgr.models.define("bench", {"1": "builtin://scaler?factor=2",
                                "2": "builtin://scaler?factor=2"},
                      active="1")
    svc = mgr.register(
        "bench-svc",
        "tensor_src num-buffers=-1 framerate={fps} dimensions=64:8 "
        "types=float32 pattern=counter "
        "! tensor_filter framework=jax model=registry://bench "
        "! tensor_sink name=out max-stored=4".format(fps=FPS),
        restart=RestartPolicy(mode="on-failure"), watchdog_s=5.0)
    return mgr, svc


FPS = 200  # steady request rate; batch interval = 1/FPS


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


def bench(n_swaps: int = 5, settle_s: float = 1.0) -> dict:
    mgr, svc = _mgr()
    stamps = []
    errors = []
    svc.start()
    svc.pipeline.get("out").connect(
        lambda buf: stamps.append(time.monotonic()))
    svc.pipeline.add_state_listener(
        lambda kind, src, data: errors.append((kind, src, data))
        if kind == "error" else None)
    time.sleep(settle_s)                      # steady state
    batch_interval = 1.0 / FPS
    swap_spans = []                           # (t_start, t_flip)
    for i in range(n_swaps):
        target = "2" if mgr.models.info("bench")["active"] == "1" else "1"
        t0 = time.monotonic()
        mgr.models.swap("bench", target)
        # the pointer flip is the LAST step of swap(): prepare+warmup ran
        # first with the OLD backend still serving every frame
        swap_spans.append((t0, time.monotonic()))
        time.sleep(settle_s / 2)
    time.sleep(settle_s / 2)
    svc.drain(timeout_s=10)
    mgr.shutdown()

    gaps = [(b - a, a) for a, b in zip(stamps, stamps[1:])]
    flip_pad = 0.1  # delivery window after the flip the new model must own

    def in_any(at, spans):
        return any(s <= at <= e for s, e in spans)

    flip_windows = [(f - batch_interval, f + flip_pad)
                    for _s, f in swap_spans]
    prepare_windows = [(s, f - batch_interval) for s, f in swap_spans]
    in_flip = sorted(g for g, at in gaps if in_any(at, flip_windows))
    in_prep = sorted(g for g, at in gaps if in_any(at, prepare_windows))
    steady = sorted(g for g, at in gaps
                    if not in_any(at, flip_windows)
                    and not in_any(at, prepare_windows))
    p99_flip = _percentile(in_flip, 99)
    p99_steady = _percentile(steady, 99)
    result = {
        "bench": "service_hot_swap_downtime",
        "fps": FPS,
        "batch_interval_ms": batch_interval * 1e3,
        "swaps": n_swaps,
        "buffers": len(stamps),
        "errors_during_run": len(errors),
        # THE acceptance numbers: delivery across the atomic flip — extra
        # p99 gap attributable to the flip must stay under one batch
        # interval, with zero request errors
        "flip_gap_p50_ms": _percentile(in_flip, 50) * 1e3,
        "flip_gap_p99_ms": p99_flip * 1e3,
        "flip_gap_max_ms": (in_flip[-1] if in_flip else 0.0) * 1e3,
        "flip_excess_p99_ms": max(0.0, p99_flip - p99_steady) * 1e3,
        "steady_gap_p99_ms": p99_steady * 1e3,
        # prepare/warmup phase: old model serving throughout; jit tracing
        # of the NEW model contends the GIL on CPU, so delivery jitters
        # but never stops — reported separately, not downtime
        "prepare_gap_max_ms": (in_prep[-1] if in_prep else 0.0) * 1e3,
        "ok": (len(errors) == 0
               and (p99_flip - p99_steady) < batch_interval
               and len(in_flip) > 0),
    }
    return result


def _sanitizer_bypassed() -> bool:
    """The hot-path guarantee of tsan-lite: with the sanitizer disabled
    (the production default), the named-lock factories return RAW
    threading primitives — no wrapper object, no recording, zero
    steady-state overhead. A wrapper type leaking through here would put
    instrumentation in every queue push and filter invoke."""
    import threading

    from nnstreamer_tpu.analysis import sanitizer

    if sanitizer.is_enabled():  # smoke must measure the production path
        return False
    return (
        type(sanitizer.named_lock("probe")) is type(threading.Lock())
        and type(sanitizer.named_rlock("probe")) is type(threading.RLock())
        and type(sanitizer.named_condition("probe")) is threading.Condition
    )


def smoke() -> dict:
    """Headless control-plane smoke: register → start → health-check →
    swap → health-check → drain. Exercises the same path CI needs green."""
    from nnstreamer_tpu.service import ServiceState

    mgr, svc = _mgr()
    svc.start()
    checks = {"ready_after_start": svc.readiness()}
    checks["sanitizer_off_is_fully_bypassed"] = _sanitizer_bypassed()
    snap = svc.status()
    checks["live"] = snap["live"]
    checks["warmup_buffers"] = snap["sink_buffers"] >= 1
    out = mgr.models.swap("bench", "2")
    checks["swap_flipped"] = out["flipped"] == 1
    checks["ready_after_swap"] = svc.readiness()
    svc.drain(timeout_s=10)
    checks["stopped_after_drain"] = svc.state is ServiceState.STOPPED
    mgr.shutdown()
    return {"bench": "service_smoke", "checks": checks,
            "ok": all(checks.values())}


# ---------------------------------------------------------------------------
# AOT cold-start leg (docs/aot.md, AOT_r14.json)
# ---------------------------------------------------------------------------

#: the compile-bound stand-in (threefry weight folding: seconds of XLA
#: compile for a few-KB module); the smoke variant compiles in ~1 s
COLD_MODEL = "builtin://mlp?n=384&layers=32"
COLD_MODEL_SMOKE = "builtin://mlp?n=128&layers=8"
COLD_BUCKETS = (1, 2, 4, 8, 16)


def cold_child(root: str, model: str) -> dict:
    """One restart-to-READY sample, run in a FRESH interpreter (the
    parent re-execs this file with ``--cold-start-child``): build the
    service, time ``start()`` → readiness (caps negotiated + one warmup
    inference at the sink). Whether the fused segment exported (cold) or
    loaded (warm) is reported so the parent can assert the measurement
    measured what it claims."""
    os.environ["NNS_AOT_CACHE"] = root
    from nnstreamer_tpu.service import ServiceManager

    mgr = ServiceManager(jitter_seed=0)
    mgr.models.define("coldm", {"1": model}, active="1")
    svc = mgr.register(
        "cold-svc",
        "tensor_src num-buffers=-1 framerate=100 dimensions=64:8 "
        "types=float32 pattern=counter "
        "! tensor_transform mode=arithmetic option=add:0 "
        "! tensor_filter framework=jax model=registry://coldm "
        "! tensor_sink name=out max-stored=4")
    t0 = time.monotonic()
    svc.start()
    ready_s = time.monotonic() - t0
    ready = svc.readiness()
    segs = svc.pipeline.fused_segments
    stats = segs[0].stats if segs else {}
    mgr.shutdown()
    return {"ready_s": ready_s, "ready": ready,
            "aot_hits": stats.get("aot_hits", 0),
            "aot_exports": stats.get("aot_exports", 0)}


def _spawn_cold_child(root: str, model: str) -> dict:
    import subprocess

    # the XLA binary half of a warm start rides jax's own persistent
    # cache, placed beside the StableHLO artifacts for this measurement
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, "xla"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--cold-start-child",
         "--root", root, "--model", model],
        capture_output=True, text=True, timeout=300, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"cold-start child failed rc={proc.returncode}: "
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bucket_coverage() -> dict:
    """Distinct-compilation count across serving buckets: ONE shape-poly
    artifact serves every bucket off a single Python trace; the plain
    ``jax.jit`` path (pre-AOT behavior under flexible caps) re-traces
    per bucket — the NNL008 recompile storm this leg quantifies."""
    import numpy as np

    import jax
    from nnstreamer_tpu import aot

    traces = []

    def model(x):
        traces.append(1)
        return (x * 2.0 + 1.0,)

    blob, meta, _fresh = aot.export_stage(
        model, (np.ones((2, 8), np.float32),), poly=True)
    loaded = aot.load_artifact(blob)
    for b in COLD_BUCKETS:
        out = loaded.call(np.ones((b, 8), np.float32))
        assert np.asarray(out[0]).shape == (b, 8)
    poly_traces = len(traces)
    traces.clear()
    jitted = jax.jit(model)
    for b in COLD_BUCKETS:
        jitted(np.ones((b, 8), np.float32))
    jit_traces = len(traces)
    return {"buckets": list(COLD_BUCKETS), "poly": meta["poly"],
            "poly_compilations": poly_traces,
            "plain_jit_compilations": jit_traces}


def _artifact_parity(root: str) -> bool:
    """Fused-vs-host byte parity for artifact-LOADED segments: run a
    fused line twice (export, then load) and compare the loaded run's
    bytes against the unfused host reference."""
    import numpy as np

    from nnstreamer_tpu.runtime.parse import parse_launch

    os.environ["NNS_AOT_CACHE"] = root
    line = ("tensor_src num-buffers=6 dimensions=8 types=float32 "
            "pattern=counter ! tensor_transform mode=arithmetic "
            "option=add:1 ! tensor_filter framework=jax "
            "model=builtin://scaler?factor=2 ! tensor_sink name=out "
            "max-stored=16")

    def run(fuse):
        pipe = parse_launch(line, fuse=fuse)
        pipe.run(timeout=60)
        out, vals = pipe.get("out"), []
        while True:
            b = out.pull(timeout=0.2)
            if b is None:
                return pipe, vals
            vals.append(tuple(np.ascontiguousarray(np.asarray(t)).tobytes()
                              for t in b.tensors))

    run(True)                       # export
    loaded_pipe, loaded = run(True)  # artifact-loaded serve
    (seg,) = loaded_pipe.fused_segments
    _host_pipe, host = run(False)
    return seg.stats["aot_hits"] == 1 and loaded == host


def cold_start(pairs: int = 3, smoke_mode: bool = False) -> dict:
    """The AOT cold-start leg. Each pair wipes the cache dir, spawns a
    COLD child (exports), then a WARM child (loads) against the SAME
    dir; min-of-pairs on both sides (co-tenant spikes only ever slow a
    sample down). Full mode gates warm >= 3x faster; smoke gates the
    direction only (one pair, smaller model — CI rigs are noisy)."""
    import shutil
    import tempfile

    model = COLD_MODEL_SMOKE if smoke_mode else COLD_MODEL
    n_pairs = 1 if smoke_mode else pairs
    base = tempfile.mkdtemp(prefix="nns-aot-bench-")
    root = os.path.join(base, "cache")
    cold_runs, warm_runs = [], []
    try:
        for _ in range(n_pairs):
            shutil.rmtree(root, ignore_errors=True)
            cold_runs.append(_spawn_cold_child(root, model))
            warm_runs.append(_spawn_cold_child(root, model))
        coverage = _bucket_coverage()
        parity = _artifact_parity(root)
    finally:
        shutil.rmtree(base, ignore_errors=True)
        os.environ.pop("NNS_AOT_CACHE", None)
    cold_s = min(r["ready_s"] for r in cold_runs)
    warm_s = min(r["ready_s"] for r in warm_runs)
    speedup = cold_s / warm_s if warm_s > 0 else 0.0
    measured_right = (all(r["ready"] and r["aot_exports"] == 1
                          for r in cold_runs)
                      and all(r["ready"] and r["aot_hits"] == 1
                              for r in warm_runs))
    checks = {
        "cold_exported_warm_loaded": measured_right,
        "warm_speedup": (speedup >= 1.0 if smoke_mode
                         else speedup >= 3.0),
        "one_compilation_covers_buckets":
            coverage["poly"] and coverage["poly_compilations"] == 1,
        "plain_jit_compiles_per_bucket":
            coverage["plain_jit_compilations"] == len(COLD_BUCKETS),
        "artifact_parity": parity,
    }
    return {
        "bench": "aot_cold_start",
        "mode": "smoke" if smoke_mode else "full",
        "model": model,
        "pairs": n_pairs,
        "cold_ready_s": cold_s,
        "warm_ready_s": warm_s,
        "cold_ready_all_s": [round(r["ready_s"], 3) for r in cold_runs],
        "warm_ready_all_s": [round(r["ready_s"], 3) for r in warm_runs],
        "warm_speedup": round(speedup, 2),
        "bucket_coverage": coverage,
        "checks": checks,
        "ok": all(checks.values()),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="headless register/health/swap/drain smoke only "
                         "(with --cold-start: 1 pair, lenient gate)")
    ap.add_argument("--cold-start", action="store_true",
                    help="AOT compile-cache cold-start leg (docs/aot.md)")
    ap.add_argument("--cold-start-child", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one READY sample
    ap.add_argument("--root", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--model", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--pairs", type=int, default=3,
                    help="cold/warm subprocess pairs (--cold-start)")
    ap.add_argument("--swaps", type=int, default=5)
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args()
    if args.cold_start_child:
        print(json.dumps(cold_child(args.root, args.model)))
        return 0
    if args.cold_start:
        result = cold_start(pairs=args.pairs, smoke_mode=args.smoke)
    else:
        result = smoke() if args.smoke else bench(n_swaps=args.swaps)
    print(json.dumps(result, indent=2))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=2)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
