"""Sharded tensor-query scaling measurement (SURVEY §5.8 north-star #5,
VERDICT r4 #6).

Measures, on loopback TCP, the throughput of ONE query worker vs TWO
workers fed by ``tensor_shard`` (round-robin frame scatter — each worker
serves every other frame), sweeping the per-frame model cost (builtin
matmul of size n).
Writes ``QUERY_SHARDING_r05.json`` with per-size rows:

    {"n": ..., "fps_single": ..., "fps_sharded_x2": ..., "ratio": ...,
     "overhead_frac": ...}

Interpretation on THIS rig: the box has ONE cpu core, so both workers
share it — compute cannot parallelize and the theoretical ceiling of
``ratio`` is 1.0, approached as the model grows and the fixed
shard/unshard + wire overhead amortizes. The row set therefore publishes
the measured crossover curve: ``overhead_frac`` (1 - ratio) shrinking
with n. On parallel hardware (2 cores / 2 hosts — the deployment the
query layer exists for) the expected speedup at size n is
``2 * ratio(n)``: the same overhead curve, with the halved compute
actually running concurrently; ratio > 0.75 is the measured condition
for the reference's ">1.5x with 2 workers" target.

Run:  python tools/bench_query_sharding.py  [sizes...]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

ROUND = os.environ.get("BENCH_ROUND", "r05")


def _run_fps(make_pipe, n_frames: int, deadline_s: float = 120.0):
    """Wall-clock fps: play→last-frame over the WHOLE run (arrival-interval
    timing lies when a re-join stage drains buffered frames in a burst).
    A first short run absorbs jit compile; the second is the measurement."""
    for frames in (8, n_frames):
        pipe = make_pipe(frames)
        sink = pipe.get("out")
        seen = []
        sink.connect(lambda b: seen.append(time.perf_counter()))
        t0 = time.perf_counter()
        pipe.play()
        deadline = time.monotonic() + deadline_s
        while len(seen) < frames and time.monotonic() < deadline:
            time.sleep(0.002)
        t1 = seen[-1] if seen else time.perf_counter()
        pipe.stop()
        if len(seen) < frames:
            raise RuntimeError(f"only {len(seen)}/{frames} frames arrived")
    return n_frames / (t1 - t0)


def bench_single(n: int, frames: int) -> float:
    from nnstreamer_tpu.runtime.parse import parse_launch

    server = parse_launch(
        "tensor_query_serversrc name=ssrc id=40 port=0 "
        f"caps=other/tensors,format=static,dimensions={n}:1,types=float32 "
        f"! tensor_filter framework=jax model=builtin://matmul?n={n} "
        "! tensor_query_serversink id=40")
    server.play()
    t0 = time.monotonic()
    while server.get("ssrc").bound_port == 0 and time.monotonic() - t0 < 5:
        time.sleep(0.01)
    port = server.get("ssrc").bound_port
    try:
        return _run_fps(lambda nf: parse_launch(
            f"tensor_src num-buffers={nf} dimensions={n}:1 "
            "types=float32 pattern=random "
            f"! tensor_query_client host=127.0.0.1 port={port} "
            "! tensor_sink name=out max-stored=1"), frames)
    finally:
        server.stop()


def bench_sharded(n: int, frames: int) -> float:
    from nnstreamer_tpu.runtime.parse import parse_launch

    servers, ports = [], []
    try:
        for i in range(2):
            srv = parse_launch(
                f"tensor_query_serversrc name=ssrc id={50 + i} port=0 "
                f"caps=other/tensors,format=static,dimensions={n}:1,"
                "types=float32 "
                f"! tensor_filter framework=jax model=builtin://matmul?n={n} "
                f"! tensor_query_serversink id={50 + i}")
            srv.play()
            servers.append(srv)
            t0 = time.monotonic()
            while srv.get("ssrc").bound_port == 0 and time.monotonic() - t0 < 5:
                time.sleep(0.01)
            ports.append(srv.get("ssrc").bound_port)
        # tensor_shard is a round-robin frame scatter: each worker gets
        # every other FULL frame (task parallelism), so the client emits
        # the same frame shape the single-worker path does
        return _run_fps(lambda nf: parse_launch(
            f"tensor_src num-buffers={nf} dimensions={n}:1 "
            "types=float32 pattern=random "
            "! tensor_shard name=s "
            f"s.src_0 ! queue ! tensor_query_client host=127.0.0.1 "
            f"port={ports[0]} ! u.sink_0 "
            f"s.src_1 ! queue ! tensor_query_client host=127.0.0.1 "
            f"port={ports[1]} ! u.sink_1 "
            "tensor_unshard name=u ! tensor_sink name=out max-stored=1"),
            frames)
    finally:
        for srv in servers:
            srv.stop()


def main() -> None:
    import jax

    platform = jax.devices()[0].platform

    sizes = [int(a) for a in sys.argv[1:]] or [128, 512, 1024, 2048]
    rows = []
    for n in sizes:
        frames = max(16, min(96, 2_000_000 // max(n, 1)))
        single = bench_single(n, frames)
        sharded = bench_sharded(n, frames)
        ratio = sharded / single if single else 0.0
        rows.append({
            "n": n, "frames": frames,
            "fps_single": round(single, 1),
            "fps_sharded_x2": round(sharded, 1),
            "ratio": round(ratio, 3),
            "overhead_frac": round(max(0.0, 1 - ratio), 3),
            "expected_speedup_on_2_cores": round(2 * ratio, 2),
        })
        print(json.dumps(rows[-1]), flush=True)
    out = {
        "metric": "tensor_query_sharded_scaling",
        "platform": platform,
        "note": ("single-core host: ratio ceiling is 1.0 (workers share "
                 "the core); expected_speedup_on_2_cores = 2*ratio is the "
                 "parallel-hardware projection; >1.5x needs ratio>0.75"),
        "rows": rows,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        f"QUERY_SHARDING_{ROUND}.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps({"rows": len(rows),
                      "best_ratio": max(r["ratio"] for r in rows)}))


if __name__ == "__main__":
    main()
