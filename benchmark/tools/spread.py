"""Measure a cell's spread as the contract asks, before it goes into
``BENCHMARK.json``: sets of runs of the benchmark's command over the same
seeds, one new process a run, and for every end-to-end metric the spread of
each set (distance between the quartiles over the median,
``statistics.quantiles(n=4)``).

    chiprun --timeout 3000 -- python3 benchmark/tools/spread.py \
        --workload opt1b3_chat --seeds 11,2147483659,3000000019 --sets 2

This process never touches jax (a chip belongs to one process at a time).
Result lines and every run's checks go to ``chiprun_out/spread/<workload>/``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib.stats import spread  # noqa: E402 — no jax behind it


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--extra", default="",
                    help="further arguments for every run, e.g. "
                         "'--control int8,fp8'")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    seconds = args.seconds or bench["run_seconds"]
    out = os.path.join(ROOT, "chiprun_out", "spread", args.workload)
    os.makedirs(out, exist_ok=True)
    sets, all_ok = [], True
    for s in range(args.sets):
        rows = []
        for seed in args.seeds.split(","):
            cmd = [*bench["command"], "--workload", args.workload, "--seed",
                   seed, "--seconds", str(seconds), "--trace", "0",
                   *args.extra.split()]
            run = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                 text=True)
            with open(os.path.join(out, f"set{s}_seed{seed}.txt"), "w") as fh:
                fh.write(run.stdout + "\n--- stderr ---\n" + run.stderr[-4000:])
            lines = run.stdout.strip().splitlines()
            if run.returncode != 0 or not lines:
                print(f"set {s} seed {seed}: exit {run.returncode}",
                      flush=True)
                all_ok = False
                continue
            line = json.loads(lines[-1])
            all_ok = all_ok and line["correct"]
            row = {k: v["value"] for k, v in line["metrics"].items()}
            print(json.dumps({"set": s, "seed": seed, "correct":
                              line["correct"], **row,
                              "checks": json.loads(lines[-2])["checks"]}),
                  flush=True)
            rows.append(row)
        sets.append(rows)
    for name in (sets[0][0] if sets and sets[0] else {}):
        per_set = [[r[name] for r in rows] for rows in sets if len(rows) >= 4]
        print(json.dumps({
            "metric": name,
            "medians": [statistics.median(v) for v in per_set],
            "spreads": [spread(v) for v in per_set]}), flush=True)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
