"""Fabric failover benchmark: the chaos acceptance gate, with numbers.

Measures what the replica fabric promises (ISSUE 6 / docs/fabric.md):
with 3 replicas under sustained query traffic,

* killing one replica yields **zero client-visible request errors** —
  retries/hedges mask the death — and the pool evicts then (after
  revival) readmits it; the report records time-to-evict and
  time-to-readmit plus request latency percentiles before/during/after
  the failover window;
* a rolling ``registry://`` hot swap across ALL replicas completes with
  zero errors while traffic flows.

The failover numbers (time-to-evict, time-to-readmit, retry counts) are
read from the control plane's ``GET /metrics`` Prometheus endpoint —
the same scrape surface an external monitor would poll — so the bench
doubles as an integration gate on the unified metrics plane
(docs/observability.md).

    python tools/bench_fabric.py            # full bench, JSON report
    python tools/bench_fabric.py --smoke    # CI gate, short run
    NNS_TSAN=1 python tools/bench_fabric.py --smoke   # + sanitizer gate

Exit nonzero when any gate fails (request errors, missing eviction/
readmission, failed roll, or sanitizer violations under NNS_TSAN=1).
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


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


class _TimedTraffic:
    """Request loop that timestamps every outcome for phase attribution."""

    def __init__(self, fab, rate_hz: float, workers: int = 2):
        self.fab = fab
        self.period = 1.0 / rate_hz
        self.samples: list = []   # (t_done, latency_s)
        self.errors: list = []    # (t, message)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._run, name=f"fabric:bench:{i}",
                             daemon=True) for i in range(workers)]

    def _run(self) -> None:
        import numpy as np

        i = 0
        me = threading.current_thread().name
        while not self._stop.is_set():
            i += 1
            t0 = time.monotonic()
            try:
                self.fab.request([np.full(4, 1.0, np.float32)],
                                 key=f"{me}:{i}", timeout=8.0)
                with self._lock:
                    self.samples.append((time.monotonic(),
                                         time.monotonic() - t0))
            except Exception as e:  # noqa: BLE001 - errors ARE the metric
                with self._lock:
                    self.errors.append((time.monotonic(),
                                        f"{type(e).__name__}: {e}"))
            self._stop.wait(self.period)

    def __enter__(self):
        for t in self._threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10.0)


# the Prometheus text parsing/polling lives in the shared module the
# fleet scraper uses too (obs/promtext.py) — one parser, every consumer
def _scrape_metric(endpoint: str, name: str, **labels):
    from nnstreamer_tpu.obs import promtext

    return promtext.scrape_metric(endpoint, name, **labels)


def _wait_metric(endpoint: str, name: str, labels: dict, want: float,
                 timeout: float = 15.0):
    from nnstreamer_tpu.obs import promtext

    return promtext.wait_metric(endpoint, name, labels, want,
                                timeout=timeout)


def bench(steady_s: float = 2.0, rate_hz: float = 120.0) -> dict:
    from nnstreamer_tpu.service import (ControlServer, ServiceFabric,
                                        ServiceManager)

    import numpy as np

    mgr = ServiceManager(jitter_seed=0)
    mgr.models.define("bench", {"1": "builtin://scaler?factor=2",
                                "2": "builtin://scaler?factor=3"},
                      active="1")
    fab = ServiceFabric(
        mgr, "bench-fab", "tensor_filter framework=jax model=registry://bench",
        CAPS, replicas=3, quarantine_base_s=0.2, health_poll_s=0.05)
    fab.start()
    # the failover clock reads the /metrics scrape surface, not
    # in-process snapshots — same path an external monitor polls
    ctrl = ControlServer(mgr).start()
    endpoint = ctrl.endpoint
    pool_labels = {"pool": "bench-fab"}
    try:
        for i in range(6):  # warm every replica's jit before measuring
            fab.request([np.zeros(4, np.float32)], key=f"w{i}", timeout=30.0)

        # -- phase 1: kill one replica mid-traffic, then revive ------------
        with _TimedTraffic(fab, rate_hz) as tr:
            time.sleep(steady_s)
            t_kill = time.monotonic()
            fab.kill_replica(1)
            t_evict = _wait_metric(endpoint, "nns_fabric_evictions_total",
                                   pool_labels, 1)
            time.sleep(steady_s / 2)
            fab.revive_replica(1)
            t_revive = time.monotonic()
            t_readmit = _wait_metric(endpoint,
                                     "nns_fabric_readmissions_total",
                                     pool_labels, 1)
            time.sleep(steady_s / 2)

        # -- phase 2: rolling swap across all replicas under traffic ------
        with _TimedTraffic(fab, rate_hz) as tr2:
            time.sleep(steady_s / 2)
            fab.rolling_swap("bench", "2")
            time.sleep(steady_s / 2)
        out = fab.request([np.ones(4, np.float32)], key="vf", timeout=8.0)
        post_factor = float(out.tensors[0].reshape(-1)[0])

        failover_window = (t_kill, t_kill + 1.0)
        steady = sorted(lat for t, lat in tr.samples
                        if not failover_window[0] <= t <= failover_window[1])
        during = sorted(lat for t, lat in tr.samples
                        if failover_window[0] <= t <= failover_window[1])
        retries = _scrape_metric(endpoint, "nns_fabric_retries_total",
                                 **pool_labels)
        result = {
            "bench": "fabric_failover",
            "rate_hz": rate_hz,
            "replicas": 3,
            "metrics_source": endpoint + "/metrics",
            "failover": {
                "requests": len(tr.samples),
                "errors": [m for _t, m in tr.errors],
                "time_to_evict_s": (None if t_evict is None
                                    else round(t_evict - t_kill, 3)),
                "time_to_readmit_s": (None if t_readmit is None
                                      else round(t_readmit - t_revive, 3)),
                "steady_p50_ms": round(_percentile(steady, 50) * 1e3, 2),
                "steady_p99_ms": round(_percentile(steady, 99) * 1e3, 2),
                "failover_window_p99_ms": round(
                    _percentile(during, 99) * 1e3, 2),
                "retries": None if retries is None else int(retries),
            },
            "rolling_swap": {
                "requests": len(tr2.samples),
                "errors": [m for _t, m in tr2.errors],
                "post_swap_factor": post_factor,
            },
        }
        result["ok"] = (
            not tr.errors and not tr2.errors
            and len(tr.samples) > 0 and len(tr2.samples) > 0
            and t_evict is not None and t_readmit is not None
            and post_factor == 3.0)
        tsan = _tsan_verdict()
        if tsan is not None:
            result["tsan_violations"] = tsan
            result["ok"] = result["ok"] and not tsan
        return result
    finally:
        ctrl.stop()
        fab.stop()
        mgr.shutdown()


def _tsan_verdict():
    from nnstreamer_tpu.analysis import sanitizer

    if not sanitizer.is_enabled():
        return None
    return sanitizer.violations()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short CI gate run")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args()
    if os.environ.get("NNS_TSAN") == "1":
        from nnstreamer_tpu.analysis import sanitizer

        sanitizer.enable(hold_warn_s=5.0)
    result = bench(steady_s=1.0 if args.smoke else 3.0,
                   rate_hz=80.0 if args.smoke else 120.0)
    print(json.dumps(result, indent=2, default=str))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=2, default=str)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
