"""Find the knee of an open-loop serving mix once, on the chip: the highest of
a few fixed arrival rates that the system sustains. The mix's traffic file then
holds a share of it as ``rate_per_s``, with the sweep beside it in PERF.md.

    chiprun -- python3 benchmark/tools/sweep.py --workload opt1b3_chat \
        --rates 0.5,0.7,0.9,1.1,1.3 --arrive-s 40 --drain-s 30

One process and one engine: for each rate the mix's list is repeated to
fill ``--arrive-s`` of arrivals, the window stays open ``--drain-s`` longer,
and the system is left to empty before the next rate. A rate is sustained
when at least 95% of its due requests have finished by the window's end and
no more than one request was still waiting for its first token when arrivals
stopped.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--arrive-s", type=float, default=40.0)
    ap.add_argument("--drain-s", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    import jax

    from benchmark.drivers import lm_serving
    from benchmark.lib import harness, stats, traffic
    from nnstreamer_tpu.utils.hw_accel import enable_compilation_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    enable_compilation_cache()
    bench = harness.load_benchmark()
    cell, config = harness.find_cell(bench, args.workload)
    mix = traffic.load(cell["traffic"])
    sched, proxy, tcfg = lm_serving.build(config, args.seed)
    try:
        lm_serving.warm(sched, proxy, config, tcfg.vocab)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            # another seed for every rate: the same token ids again would
            # be served from the prefix registry the rate before filled
            seed = args.seed + 1000 * i
            want = int(args.arrive_s * rate)
            copies = -(-want // len(mix["requests"]))
            swept = {**mix, "rate_per_s": rate, "drain_s": 0.0,
                     "requests": (mix["requests"] * copies)[:want]}
            schedule = traffic.requests(
                swept, seed, args.arrive_s, tcfg.vocab)
            t0, cutoff, records = lm_serving.drive(
                sched, proxy, schedule, args.arrive_s + args.drain_s, None)
            stop = t0 + args.arrive_s
            finished = sum(1 for r in records if r["request"].done()
                           and r["request"].error is None)
            waiting = sum(1 for r in records
                          if not r["token_t"] or r["token_t"][0] > stop)
            ttft, tpot, _ = lm_serving.window_samples(records, t0, cutoff)
            row = {"rate_per_s": rate, "due": len(records),
                   "finished_share": finished / len(records),
                   "waiting_at_stop": waiting,
                   "ttft_p50_ms": stats.median(ttft),
                   "ttft_p90_ms": stats.percentile(ttft, 90),
                   "tpot_p50_ms": stats.median(tpot),
                   "sustained": (finished / len(records) >= 0.95
                                 and waiting <= 1)}
            print(json.dumps(row), flush=True)
            deadline = time.monotonic() + 180
            while (any(not r["request"].done() for r in records)
                   and time.monotonic() < deadline):
                time.sleep(0.2)
    finally:
        sched.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
