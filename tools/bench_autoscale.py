"""Autoscaler benchmark: the closed loop's numbers (ISSUE 12).

Two legs against a capacity-limited fabric (``builtin://sleeper`` — a
fixed number of milliseconds of real service time per request, so one
replica's throughput is deterministic):

* **ramp** — steady low traffic establishes a baseline p99; offered
  load then steps up hard against a 1-replica fabric with a running
  :class:`~nnstreamer_tpu.service.autoscaler.Autoscaler` (max 3
  replicas). Recorded: **time-to-scale-out** (load step → first
  ``scale_out`` event), **ramp p99 vs steady p99** (the transient the
  loop is racing) and **post-scale p99** (what users see once capacity
  lands). Gate: the loop scales out within the bound, post-scale p99
  recovers under the SLO, zero request errors.
* **shed** — the same load against a fabric whose ceiling is 1 replica
  (``max_replicas=1``): the loop cannot grow, so it must ARM the
  overload guard — low-priority requests shed with a typed
  :class:`~nnstreamer_tpu.serving.request.OverloadShedError` (counted),
  priority-0 requests keep completing. Gate: sheds happen, every shed
  is the typed error (never a timeout), zero priority-0 errors.

Report written to AUTOSCALE_r12.json (full mode) — the ISSUE 12
trajectory point.

    python tools/bench_autoscale.py           # full bench, JSON report
    python tools/bench_autoscale.py --smoke   # CI gate, short run
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

CAPS = "other/tensors,format=static,dimensions=4,types=float32"
SLEEP_MS = 40


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


def _fabric(mgr, name: str, replicas: int = 1):
    from nnstreamer_tpu.service.fabric import ServiceFabric

    fab = ServiceFabric(
        mgr, name,
        f"tensor_filter framework=jax model=builtin://sleeper?ms={SLEEP_MS}",
        CAPS, replicas=replicas, quarantine_base_s=0.2, health_poll_s=0.05)
    fab.start()
    import numpy as np

    for i in range(4):  # jit warmup off the clock
        fab.request([np.zeros(4, np.float32)], key=f"warm{i}", timeout=30.0)
    return fab


def _autoscaler(fab, max_replicas: int, name: str):
    from nnstreamer_tpu.service import Autoscaler, AutoscalerConfig

    cfg = AutoscalerConfig(
        min_replicas=1, max_replicas=max_replicas,
        latency_slo_s=0.1, target=0.9,
        short_window_s=2.0, long_window_s=6.0,
        scale_out_burn=3.0, scale_in_burn=0.8, min_samples=6,
        scale_out_cooldown_s=1.5, scale_in_cooldown_s=4.0,
        tick_s=0.25)
    return Autoscaler(fab, cfg, name=name)


class _Load:
    """Closed-loop workers; phase-stamped samples, typed-error buckets."""

    def __init__(self, fab, workers: int, priority_split: bool = False,
                 timeout: float = 12.0):
        self.fab = fab
        self.timeout = timeout
        self.samples: list = []      # (t_done, latency_s, priority)
        self.errors: list = []       # unexpected errors
        self.sheds = 0               # typed OverloadShedError count
        self.other_shed_errors: list = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._open = threading.Event()
        self._threads = [
            threading.Thread(target=self._run,
                             args=(i % 2 if priority_split else 0,),
                             name=f"fabric:bench:{i}", daemon=True)
            for i in range(workers)]

    def _run(self, priority: int) -> None:
        import numpy as np

        from nnstreamer_tpu.serving.request import OverloadShedError

        n = 0
        me = threading.current_thread().name
        while not self._stop.is_set():
            self._open.wait(0.1)
            if not self._open.is_set():
                continue
            n += 1
            t0 = time.monotonic()
            try:
                self.fab.request([np.full(4, 1.0, np.float32)],
                                 key=f"{me}:{n}", timeout=self.timeout,
                                 priority=priority)
                with self._lock:
                    self.samples.append((time.monotonic(),
                                         time.monotonic() - t0, priority))
            except OverloadShedError:
                with self._lock:
                    self.sheds += 1
                self._stop.wait(0.02)  # a real client backs off
            except Exception as e:  # noqa: BLE001
                with self._lock:
                    self.errors.append(
                        f"p{priority} {type(e).__name__}: {e}")

    def start(self):
        for t in self._threads:
            t.start()
        self._open.set()
        return self

    def stop(self):
        self._stop.set()
        self._open.set()
        for t in self._threads:
            t.join(timeout=self.timeout + 3.0)

    def p99_between(self, t0: float, t1: float, priority=None) -> tuple:
        with self._lock:
            vals = sorted(s for (td, s, p) in self.samples
                          if t0 <= td <= t1
                          and (priority is None or p == priority))
        return _percentile(vals, 99), len(vals)


def leg_ramp(mgr, steady_s: float, ramp_s: float) -> dict:
    fab = _fabric(mgr, "bench-scale")
    scaler = _autoscaler(fab, max_replicas=3, name="bench-scale")
    load = _Load(fab, workers=1)
    try:
        scaler.start()
        load.start()
        t0 = time.monotonic()
        time.sleep(steady_s)
        t_step = time.monotonic()
        steady_p99, steady_n = load.p99_between(t0 + 0.5, t_step)
        # the step: 7 more closed-loop workers against 1 replica
        burst = _Load(fab, workers=7)
        burst.start()
        t_scaled = None
        deadline = t_step + max(20.0, ramp_s)
        while time.monotonic() < deadline:
            if scaler.snapshot()["scale_out"] >= 1:
                t_scaled = time.monotonic()
                break
            time.sleep(0.05)
        time.sleep(ramp_s)  # post-scale steady window
        t_end = time.monotonic()
        burst.stop()
        load.stop()
        ramp_p99 = post_p99 = 0.0
        ramp_n = post_n = 0
        if t_scaled is not None:
            for ld in (load, burst):
                p, n = ld.p99_between(t_step, t_scaled)
                ramp_p99, ramp_n = max(ramp_p99, p), ramp_n + n
                p, n = ld.p99_between(t_end - 0.6 * ramp_s, t_end)
                post_p99, post_n = max(post_p99, p), post_n + n
        snap = scaler.snapshot()
        errors = load.errors + burst.errors
        tts = None if t_scaled is None else round(t_scaled - t_step, 3)
        return {
            "steady_p99_s": round(steady_p99, 4), "steady_n": steady_n,
            "ramp_p99_s": round(ramp_p99, 4), "ramp_n": ramp_n,
            "post_scale_p99_s": round(post_p99, 4), "post_n": post_n,
            "time_to_scale_out_s": tts,
            "scale_out_events": snap["scale_out"],
            "replicas_final": fab.replica_count(),
            "errors": errors,
            "ok": (not errors and tts is not None and tts <= 15.0
                   and post_n > 10 and post_p99 <= 0.3),
        }
    finally:
        scaler.stop()
        fab.stop()


def leg_shed(mgr, duration_s: float) -> dict:
    fab = _fabric(mgr, "bench-shed")
    scaler = _autoscaler(fab, max_replicas=1, name="bench-shed")
    load = _Load(fab, workers=8, priority_split=True, timeout=20.0)
    try:
        scaler.start()
        load.start()
        # wait for the guard to arm (short window heats in ~2s)
        armed_at = None
        deadline = time.monotonic() + max(15.0, duration_s)
        while time.monotonic() < deadline:
            if scaler.shed_armed():
                armed_at = time.monotonic()
                break
            time.sleep(0.05)
        time.sleep(duration_s)
        load.stop()
        with load._lock:
            p0_ok = sum(1 for (_t, _s, p) in load.samples if p == 0)
            p1_ok = sum(1 for (_t, _s, p) in load.samples if p == 1)
        snap = fab.pool.snapshot()
        return {
            "armed": armed_at is not None,
            "sheds_typed": load.sheds,
            "pool_shed_overload": snap["shed_overload"],
            "p0_completed": p0_ok, "p1_completed": p1_ok,
            "errors": load.errors,
            "ok": (armed_at is not None and load.sheds >= 5
                   and not load.errors and p0_ok > 0),
        }
    finally:
        scaler.stop()
        fab.stop()


def run(steady_s: float, ramp_s: float, shed_s: float) -> dict:
    from nnstreamer_tpu.service import ServiceManager

    legs = {}
    for name, fn, args in (("ramp", leg_ramp, (steady_s, ramp_s)),
                           ("shed", leg_shed, (shed_s,))):
        mgr = ServiceManager(jitter_seed=0)
        try:
            legs[name] = fn(mgr, *args)
        finally:
            mgr.shutdown()
        print(f"[bench_autoscale] {name}: "
              f"{'ok' if legs[name]['ok'] else 'FAILED'}", file=sys.stderr)
    return {"bench": "autoscale", "sleep_ms": SLEEP_MS, "legs": legs,
            "ok": all(l["ok"] for l in legs.values())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI: short phases, gates only")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args()
    if args.smoke:
        report = run(steady_s=3.0, ramp_s=5.0, shed_s=4.0)
    else:
        report = run(steady_s=6.0, ramp_s=10.0, shed_s=8.0)
    print(json.dumps(report, indent=2, default=str))
    out = args.out or (None if args.smoke else "AUTOSCALE_r12.json")
    if out:
        with open(out, "w") as fh:
            json.dump(report, fh, indent=2, default=str)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
