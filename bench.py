"""Benchmark: MobileNet-v2 224×224 streaming pipeline fps + p50 latency.

The BASELINE.json north star: the reference's image-classification pipeline
(videotestsrc → tensor_converter → tensor_filter → tensor_decoder) at
≥2000 fps aggregate on TPU. This runs the same topology through our
framework on the device jax selected, with tensor_aggregator batching
frames into the MXU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where
vs_baseline = fps / 2000 (the target, BASELINE.md — the reference repo
publishes no numbers of its own). The line names the platform it ran on;
platform selection is jax's (``JAX_PLATFORMS``), and a backend that does
not initialize is an error, not a fallback.

Phases are logged separately on stderr: the measurement deadline starts
only AFTER the model is compiled, pipeline bus errors fail fast with the
real cause, and a partial result is emitted if the deadline hits
mid-measurement.
"""
from __future__ import annotations

import json
import os
import sys
import time
from contextlib import closing

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_FPS = 2000.0  # BASELINE.json target on TPU
BATCH = int(os.environ.get("BENCH_BATCH", "64"))
WARMUP_BATCHES = 3
MEASURE_BATCHES = int(os.environ.get("BENCH_BATCHES", "30"))
# wall budget for the measurement loop itself (post-init, post-compile)
DEADLINE_S = float(os.environ.get("BENCH_DEADLINE", "300"))

_T0 = time.monotonic()


def _log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def _raise_pipeline_error(msg) -> None:
    raise RuntimeError(f"pipeline ERROR from {msg.source}: {msg.data.get('error')}")


def main() -> None:
    global BATCH

    import numpy as np

    import jax

    from nnstreamer_tpu.utils.hw_accel import enable_compilation_cache

    _log(f"persistent XLA compile cache: {enable_compilation_cache()}")
    _log("initializing jax backend in-process")
    devices = jax.devices()
    platform = devices[0].platform
    _log(f"backend up: {len(devices)} x {platform}")
    # multi-chip window: run the filter stage mesh-sharded over every chip
    # (BASELINE's ≥2000 fps target is v5e-8 AGGREGATE; mesh:auto is the
    # in-pipeline dp path). Single chip keeps the default-device fast
    # path.
    from nnstreamer_tpu.utils.flops import bench_mesh_policy

    mesh_custom, BATCH = bench_mesh_policy(
        len(devices), platform == "cpu", BATCH)
    if mesh_custom:
        _log(f"mesh mode: dp over {len(devices)} chips (batch={BATCH})")

    from nnstreamer_tpu.core import MessageType
    from nnstreamer_tpu.runtime.parse import parse_launch
    from nnstreamer_tpu.single import SingleShot

    # Topology: batch RAW uint8 on host (aggregator, numpy) → one H2D copy
    # per batch → normalization + forward fused in a single jitted program
    # (models.mobilenet_v2:filter_model_u8). The queue decouples host
    # batching from device compute so H2D of batch N+1 overlaps the forward
    # of batch N. Normalize-then-batch per frame (the reference topology)
    # would ship 4x the bytes and pay per-frame dispatch round-trips.
    model = "nnstreamer_tpu.models.mobilenet_v2:filter_model_u8"

    # Pre-compile the EXACT executable the pipeline will run: the shared
    # tensor-filter key resolves SingleShot and the pipeline filter to one
    # refcounted backend instance (acquire_backend), so warming it here
    # means the streaming thread hits a warm jit cache. Kept open across
    # the run — the p50 phase below reuses it.
    _log(f"compiling batch graph (batch={BATCH}) ...")
    t_c = time.monotonic()
    with closing(SingleShot("jax", model, share_key="bench",
                            custom=mesh_custom)) as single:
        warm = single.invoke(np.zeros((BATCH, 224, 224, 3), np.uint8))
        warm[0].block_until_ready()
        compile_s = time.monotonic() - t_c
        _log(f"compile done in {compile_s:.1f}s")

        # On an accelerator, the best batch size is not knowable in advance
        # (depends on chip generation + HBM): sweep a few sizes through the
        # same shared backend (its compile cache is per-shape) and run the
        # pipeline at the winner. The driver gives us one shot per round —
        # spend ~1 compile per candidate to not leave throughput on the
        # table. Skipped when BENCH_BATCH pins the size or on CPU.
        if (platform != "cpu" or os.environ.get("BENCH_FORCE_SWEEP")) \
                and "BENCH_BATCH" not in os.environ \
                and not os.environ.get("BENCH_NO_SWEEP"):
            candidates = [int(b) for b in os.environ.get(
                "BENCH_SWEEP", "64,128,256").split(",")]
            if mesh_custom:  # same divisibility rule as the main batch
                kept = [b for b in candidates if b % len(devices) == 0]
                if kept != candidates:
                    _log(f"sweep candidates {sorted(set(candidates) - set(kept))} "
                         f"dropped (not divisible by {len(devices)} chips)")
                candidates = kept
            best_b, best_fps = BATCH, 0.0
            for b in candidates:
                try:
                    xb = np.zeros((b, 224, 224, 3), np.uint8)
                    t0 = time.monotonic()
                    single.invoke(xb)[0].block_until_ready()  # compile
                    _log(f"sweep batch={b}: compiled in {time.monotonic() - t0:.1f}s")
                    t0 = time.monotonic()
                    outs = [single.invoke(xb) for _ in range(8)]
                    outs[-1][0].block_until_ready()
                    fps_b = 8 * b / (time.monotonic() - t0)
                    _log(f"sweep batch={b}: {fps_b:.0f} fps (direct invoke)")
                except Exception as e:  # e.g. HBM OOM at large batch
                    _log(f"sweep batch={b}: failed ({e}); skipping")
                    continue
                if fps_b > best_fps:
                    best_b, best_fps = b, fps_b
            BATCH = best_b
            _log(f"sweep winner: batch={BATCH} ({best_fps:.0f} fps direct)")

        total_frames = (WARMUP_BATCHES + MEASURE_BATCHES) * BATCH
        pipe = parse_launch(
            f"tensor_src num-buffers={total_frames} dimensions=3:224:224:1 "
            "types=uint8 pattern=random "
            f"! tensor_aggregator frames-out={BATCH} frames-dim=0 concat=true "
            "! queue max-size-buffers=4 "
            f"! tensor_filter framework=jax model={model} "
            + (f"custom={mesh_custom} " if mesh_custom else "")
            + "shared-tensor-filter-key=bench name=f sync-invoke=false "
            "! queue max-size-buffers=4 name=outq "
            "! tensor_sink name=out max-stored=1"
        )

        sink = pipe.get("out")
        times = []

        def on_batch(b):
            # force completion at the SINK, not the filter: while we block on
            # batch N here, the filter thread is already dispatching batch N+1,
            # overlapping its host→HBM transfer with batch N's compute
            for t in b.tensors:
                if hasattr(t, "block_until_ready"):
                    t.block_until_ready()
            times.append(time.monotonic())

        sink.connect(on_batch)
        pipe.play()
        deadline = time.monotonic() + DEADLINE_S
        want = WARMUP_BATCHES + MEASURE_BATCHES
        partial = False
        early_eos = False
        last_beat = time.monotonic()
        while len(times) < want:
            now = time.monotonic()
            if now >= deadline:
                partial = True
                _log(f"deadline hit with {len(times)}/{want} batches — emitting partial result")
                break
            # surface real pipeline failures immediately instead of a silent stall
            msg = pipe.bus.pop(timeout=0.05)
            if msg is not None and msg.type is MessageType.ERROR:
                pipe.stop()
                _raise_pipeline_error(msg)
            if msg is not None and msg.type is MessageType.EOS:
                # stream finished with fewer batches than expected (dropped
                # frames); don't idle out the deadline waiting for more
                early_eos = len(times) < want
                break
            if now - last_beat >= 10.0:
                last_beat = now
                _log(f"progress: {len(times)}/{want} batches")
        pipe.stop()
        # drain any ERROR that raced the deadline break — a failed run must
        # not be misreported as a clean partial result
        if len(times) < want:
            while True:
                msg = pipe.bus.pop(timeout=0)
                if msg is None:
                    break
                if msg.type is MessageType.ERROR:
                    _raise_pipeline_error(msg)
        if len(times) <= WARMUP_BATCHES + 1:
            raise RuntimeError(
                f"bench produced only {len(times)} batches "
                f"(want {want}, deadline {DEADLINE_S}s post-compile; "
                "no pipeline ERROR was posted — see heartbeat log above)"
            )

        # batches completed after warmup, timed from the last warmup batch
        n_measured = len(times) - WARMUP_BATCHES
        span = times[-1] - times[WARMUP_BATCHES - 1]
        fps = n_measured * BATCH / span if span > 0 else 0.0
        _log(f"throughput: {n_measured} batches in {span:.2f}s = {fps:.0f} fps")

        # Device-resident pipeline: the same topology with tensor_src
        # device=true — frames are born on the chip (jitted jax.random),
        # so this measures the FRAMEWORK + model throughput with ingest
        # off the critical path.
        fps_dev = None
        if (platform != "cpu" or os.environ.get("BENCH_FORCE_DEVICE_SRC")) \
                and not partial \
                and not os.environ.get("BENCH_NO_DEVICE_SRC"):
            try:
                dev_batches = min(MEASURE_BATCHES, 20) + WARMUP_BATCHES
                pipe_d = parse_launch(
                    f"tensor_src device=true pattern=random "
                    f"num-buffers={dev_batches} "
                    f"dimensions=3:224:224:{BATCH} types=uint8 "
                    f"! tensor_filter framework=jax model={model} "
                    + (f"custom={mesh_custom} " if mesh_custom else "")
                    + "shared-tensor-filter-key=bench sync-invoke=false "
                    "! queue max-size-buffers=4 "
                    "! tensor_sink name=out max-stored=1")
                times_d = []

                def on_dev_batch(b):
                    for t in b.tensors:
                        if hasattr(t, "block_until_ready"):
                            t.block_until_ready()
                    times_d.append(time.monotonic())

                pipe_d.get("out").connect(on_dev_batch)
                _log(f"device-resident pipeline: {dev_batches} batches ...")
                pipe_d.run(timeout=DEADLINE_S)
                if len(times_d) > WARMUP_BATCHES + 1:
                    span_d = times_d[-1] - times_d[WARMUP_BATCHES - 1]
                    fps_dev = (len(times_d) - WARMUP_BATCHES) * BATCH / span_d
                    _log(f"device-resident: {fps_dev:.0f} fps")
            except Exception as e:  # noqa: BLE001 — aux number, fail soft
                _log(f"device-resident pipeline failed: {e}")

        # measured H2D bandwidth — the context that explains the gap
        # between the two fps numbers
        h2d_mb_s = None
        if platform != "cpu" and not partial:
            try:
                blob = np.zeros((32 << 20,), np.uint8)
                jax.device_put(blob).block_until_ready()
                bw = []
                for _ in range(3):
                    t0 = time.monotonic()
                    jax.device_put(blob).block_until_ready()
                    bw.append(blob.nbytes / 1e6 / (time.monotonic() - t0))
                h2d_mb_s = max(bw)
                _log(f"measured H2D bandwidth: {h2d_mb_s:.1f} MB/s")
            except Exception as e:  # noqa: BLE001
                _log(f"H2D bandwidth probe failed: {e}")

        # p50 single-frame end-to-end latency, batch=1 through the same shared
        # backend (same fused-u8 graph) so fps and p50 describe one model.
        # Skipped when the deadline already hit: a stalled device would hang
        # block_until_ready and the partial result would never be printed.
        p50_ms = None
        if not partial:
            _log("compiling batch=1 graph for p50 latency ...")
            lat = []
            x = (np.random.rand(1, 224, 224, 3) * 255).astype(np.uint8)
            out = single.invoke(x)
            out[0].block_until_ready()  # compile
            for _ in range(30):
                t0 = time.monotonic()
                out = single.invoke(x)
                out[0].block_until_ready()
                lat.append(time.monotonic() - t0)
            p50_ms = sorted(lat)[len(lat) // 2] * 1e3

    # FLOPs accounting: model FLOP/s + MFU alongside fps.
    # cost_analysis of the exact batch graph; the persistent cache (or the
    # backend's warm shape) makes the lower+compile ~free. Skipped when the
    # deadline already hit — same stance as the p50 block: a stalled device
    # would hang the compile and the partial result would never print.
    perf = {"model_tflops_per_s": None, "mfu": None}
    if not partial:
        try:  # aux accounting must never cost the fps number already in hand
            from nnstreamer_tpu.models.mobilenet_v2 import filter_model_u8
            from nnstreamer_tpu.utils.flops import compiled_flops, perf_record

            _log("cost analysis for MFU accounting ...")
            # per-frame FLOPs from a batch=1 lower: shape-derived model
            # work is linear in batch for this CNN, the batch=1 compile is
            # cheap (the p50 phase warms the same shape), and it sidesteps
            # compiling a second large (possibly GSPMD-sharded) graph
            # purely for accounting
            frame_flops = compiled_flops(
                filter_model_u8.make(),
                np.zeros((1, 224, 224, 3), np.uint8))
            perf = perf_record(frame_flops, fps,
                               n_chips=len(devices) if mesh_custom else 1,
                               device=devices[0])
            if fps_dev:
                perf_d = perf_record(
                    frame_flops, fps_dev,
                    n_chips=len(devices) if mesh_custom else 1,
                    device=devices[0])
                perf["device_resident_mfu"] = perf_d.get("mfu")
        except Exception as e:  # noqa: BLE001
            _log(f"MFU accounting failed: {e}")

    # value/vs_baseline are the full host-ingest pipeline. The
    # device-resident number (ingest off the critical path) and the
    # measured H2D bandwidth ride along as their own fields so the gap
    # is explained, not hidden.
    result = {
        "metric": "mobilenet_v2_224_pipeline_fps",
        "value": round(fps, 1),
        "unit": "fps",
        "vs_baseline": round(fps / BASELINE_FPS, 3),
        "device_resident_fps": round(fps_dev, 1) if fps_dev else None,
        "device_resident_vs_baseline": (round(fps_dev / BASELINE_FPS, 3)
                                        if fps_dev else None),
        "h2d_mb_per_s": round(h2d_mb_s, 1) if h2d_mb_s else None,
        "p50_latency_ms": round(p50_ms, 2) if p50_ms is not None else None,
        "batch": BATCH,
        "platform": platform,
        "devices": len(devices),
        "mesh": mesh_custom or None,
        "compile_s": round(compile_s, 1),
        **perf,
    }
    if partial:
        result["partial"] = True
        result["batches_measured"] = n_measured
    if early_eos:
        result["early_eos"] = True
    print(json.dumps(result))


if __name__ == "__main__":
    main()
