"""Benchmark suite: every BASELINE.md headline config, one JSON line each.

``bench.py`` stays the driver gate (ONE line: MobileNet-v2 pipeline fps);
this suite is the full evidence set for the remaining headline configs:

  1. mobilenet_v2 image_labeling  (classification, batched, fused u8)
  2. ssd_mobilenet bounding_boxes (detection + decoder post-processing)
  3. posenet pose_estimation      (keypoints + skeleton render)
  4. deeplab image_segment        (segmentation + palette render)
  5. tensor_query sharded inference (2 loopback workers, tensor_shard →
     query clients → ordered re-join — the among-device config)
  6. transformer LM prefill + KV-cache decode (tokens/s, decode step
     time, MFU at a few batch/seq points — models/decoding.py)

Every model config also reports model FLOP/s + MFU (utils/flops.py)
and ``p50_pipeline_ms`` — batch=1 single-frame latency through the FULL
pipeline topology including aggregator + queues (the reference's
per-frame operating point, tensor_filter.c:366-510 invoke statistics).

Bench-regression sentinel (``--diff``): run the PROFILE_r08 sentinel
pipeline (3-stage fused 64x64x3 chain, CPU) under the continuous
profiler, capture a ProfileArtifact, and compare it against a committed
baseline via ``ProfileArtifact.diff`` — exit non-zero when any shared
entry's p99 regressed beyond ``--max-p99-regress`` (best-of-two, same
co-tenant-jitter stance as microbench_overhead). ``--out`` records the
fresh artifact (the BENCH_r11.json trajectory point)::

  python tools/bench_suite.py --diff                       # vs PROFILE_r08
  python tools/bench_suite.py --diff --baseline BENCH_r11.json \
      --max-p99-regress 0.5 --out BENCH_r12.json           # tight same-rig
  python tools/bench_suite.py --diff --smoke               # CI leg

Run:  python tools/bench_suite.py            (on the platform jax selects;
                                              exits non-zero on any error row)
      BENCHS_FRAMES=64 BENCHS_BATCH=8 ...    (size knobs; CPU defaults
      are small so the whole suite finishes in a few minutes)
      BENCHS_PERFRAME_BATCH=N                (model batch for the
      detection/pose/segment configs on accelerators — the decoder stays
      per-frame; 1 = the reference-style unbatched topology)
      BENCHS_SKIP_LM=1 / BENCHS_LM_POINTS=B:P:S[,B:P:S...]  (LM knobs)

Each config prints one JSON object on stdout; a summary table goes to
stderr.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

_T0 = time.monotonic()


def _log(msg: str) -> None:
    print(f"[suite +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


#: configs that printed an error row (or a zero row after a failure);
#: main() exits non-zero when any did
_FAILED: list = []


def _run_fps(pipe, sink_name: str, want: int, warmup: int,
             deadline_s: float) -> tuple:
    """Play `pipe`, time buffers at the sink; returns (fps, measured)."""
    from nnstreamer_tpu.core import MessageType

    warmup = min(warmup, max(1, want - 2))  # tiny smoke runs still measure
    sink = pipe.get(sink_name)
    times = []

    def on_buf(b):
        for t in b.tensors:
            if hasattr(t, "block_until_ready"):
                t.block_until_ready()
        times.append(time.monotonic())

    sink.connect(on_buf)
    pipe.play()
    deadline = time.monotonic() + deadline_s
    while len(times) < want and time.monotonic() < deadline:
        msg = pipe.bus.pop(timeout=0.05)
        if msg is not None and msg.type is MessageType.ERROR:
            pipe.stop()
            raise RuntimeError(f"pipeline ERROR: {msg.data.get('error')}")
        if msg is not None and msg.type is MessageType.EOS:
            break
    pipe.stop()
    if len(times) < warmup + 1:  # need >=1 measured interval past warmup
        raise RuntimeError(f"only {len(times)}/{want} buffers before deadline")
    span = times[-1] - times[warmup - 1]
    return (len(times) - warmup) / span if span > 0 else 0.0, len(times) - warmup


def _pipeline_p50(model: str, in_size: int, dec: str, dtype: str = "float32",
                  n: int = 20, warmup: int = 3,
                  frame_timeout_s: float = 120.0) -> float:
    """Batch=1 single-frame latency through the FULL topology (aggregator
    + queues + filter + decoder), serialized push→sink round trips — the
    reference's per-frame operating point, with element overheads that
    SingleShot.invoke excludes. Returns p50 in ms."""
    import threading

    import numpy as np

    from nnstreamer_tpu.runtime.parse import parse_launch

    pipe = parse_launch(
        f"appsrc name=in caps=other/tensors,format=static,"
        f"dimensions=3:{in_size}:{in_size}:1,types={dtype} "
        "! tensor_aggregator frames-out=1 frames-dim=0 concat=true "
        "! queue max-size-buffers=4 "
        f"! tensor_filter framework=jax model={model} "
        "! queue max-size-buffers=8 "
        f"! {dec} ! tensor_sink name=out max-stored=1")
    done = threading.Event()
    pipe.get("out").connect(lambda b: done.set())
    pipe.play()
    src = pipe.get("in")
    rng = np.random.default_rng(1)
    if dtype == "uint8":
        x = (rng.random((1, in_size, in_size, 3)) * 255).astype(np.uint8)
    else:
        x = rng.random((1, in_size, in_size, 3)).astype(np.float32)
    lats = []
    try:
        for i in range(n + warmup):
            done.clear()
            t0 = time.monotonic()
            src.push_buffer(x)
            if not done.wait(frame_timeout_s):
                raise RuntimeError(f"latency frame {i} timed out")
            if i >= warmup:
                lats.append(time.monotonic() - t0)
    finally:
        pipe.stop()
    return sorted(lats)[len(lats) // 2] * 1e3


def _model_perf(model_entry, frame_shape, example_dtype, fps: float,
                n_chips: int = 1) -> dict:
    """model FLOP/s + MFU fields for a suite row (null-safe). FLOPs come
    from a batch=1 lower (``frame_shape`` has leading dim 1): per-frame
    work is linear in batch for these models and the small compile avoids
    building a second large (possibly GSPMD-sharded) graph just for
    accounting."""
    import numpy as np

    import jax

    from nnstreamer_tpu.utils.flops import compiled_flops, perf_record

    fn = model_entry.make() if hasattr(model_entry, "make") else model_entry
    flops = compiled_flops(fn, np.zeros(frame_shape, example_dtype))
    return perf_record(flops, fps, n_chips=n_chips,
                       device=jax.devices()[0])


def _mesh_fields(mesh_custom: str, n_dev: int) -> dict:
    """Row fields marking a dp-sharded measurement (empty when unmeshed)."""
    return ({"mesh": mesh_custom, "devices": n_dev} if mesh_custom else {})


def _bench_lm_decode(platform: str, on_cpu: bool,
                     deadline_s: float) -> None:
    """Config 6: transformer LM prefill + KV-cache decode. Per (B, P, S)
    point: processed-token throughput for the whole generate (prefill P
    prompt tokens + S cached decode steps, all counted), the marginal
    decode step time / decode tokens/s (subtracting a steps=1 run), and
    MFU from XLA cost analysis of the exact executables."""
    import numpy as np

    import jax

    from nnstreamer_tpu.models.decoding import make_generate
    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from nnstreamer_tpu.utils.flops import (
        compiled_flops,
        count_params,
        mfu,
    )

    if on_cpu:
        cfg = TransformerConfig(vocab=512, dim=128, heads=4, layers=2,
                                max_seq=256)
        points = [(2, 64, 32)]
    else:
        # ~215M-param decoder: big enough that decode is HBM/matmul bound
        # like a real LM, small enough to init+compile alongside the rest
        # of the suite
        cfg = TransformerConfig(vocab=32000, dim=1024, heads=16, layers=12,
                                max_seq=2048)
        points = [(8, 512, 128), (32, 512, 128), (8, 1024, 256)]
    reps = 1 if on_cpu else 3
    try:  # setup fails soft like every other config — the suite must
        # always reach its summary with whatever evidence it has
        if os.environ.get("BENCHS_LM_POINTS"):
            points = []
            for p in os.environ["BENCHS_LM_POINTS"].split(","):
                b, pr, s = (int(v) for v in p.split(":"))
                points.append((b, pr, s))
        _log(f"transformer_lm_decode: dim={cfg.dim} layers={cfg.layers} "
             f"vocab={cfg.vocab} points={points}")
        t_start = time.monotonic()
        params_f32 = init_params(cfg)
        n_params = count_params(params_f32)
        if on_cpu:
            params = params_f32
        else:
            # serving default on an accelerator: bfloat16 weights AND
            # bfloat16 K/V cache (decode is HBM-bound — reading half the
            # bytes per step is the single biggest decode lever);
            # activations stay f32 inside decoding.py
            import jax.numpy as jnp

            params = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if a.dtype == jnp.float32 else a, params_f32)
    except Exception as e:  # noqa: BLE001
        _log(f"transformer_lm_decode setup FAILED: {e}")
        _FAILED.append("transformer_lm_decode")
        print(json.dumps({"config": "transformer_lm_decode",
                          "platform": platform,
                          "error": str(e)[:300]}), flush=True)
        return
    rng = np.random.default_rng(3)
    # the streaming form is rebuilt per point with the SAME serving
    # config as the scan row (bf16 weights+cache, right-sized cache) so
    # the stream-vs-scan delta isolates the per-token dispatch tax and
    # nothing else
    stream_dtype = None if on_cpu else "bfloat16"
    _stream_cache = {}

    def _stream_for(c_len):
        if os.environ.get("BENCHS_SKIP_STREAM"):
            return None
        if c_len not in _stream_cache:
            try:
                from nnstreamer_tpu.models.lm_serving import _LMServingEntry

                _stream_cache[c_len] = _LMServingEntry(
                    cfg, serve_dtype=stream_dtype,
                    cache_len=c_len).make_streaming()
            except Exception as e:  # noqa: BLE001
                _log(f"transformer_lm_decode stream build failed: {e}")
                _stream_cache[c_len] = None
        return _stream_cache[c_len]
    for B, P, S in points:
        name = f"transformer_lm_decode_b{B}_p{P}_s{S}"
        if time.monotonic() - t_start > deadline_s:
            _log(f"{name}: skipped (suite LM deadline)")
            continue
        try:
            prompt = rng.integers(0, cfg.vocab, (B, P)).astype(np.int32)
            # right-sized serving cache: each decode step reads the whole
            # cache, so size it to this point's P+S (128-aligned), not the
            # model's max_seq (decoding.py make_generate cache_len)
            c_len = min(cfg.max_seq, -(-(P + S) // 128) * 128)
            gen = make_generate(cfg, cache_len=c_len)
            if S > 1:
                step_s, t1, tS = _marginal_step(gen, params, prompt, S, reps)
            else:  # prefill-only point (e.g. BENCHS_LM_POINTS=8:512:1)
                jax.block_until_ready(gen(params, prompt, 1))
                t1 = min(_timed(gen, params, prompt, 1, reps=reps))
                tS = t1
                step_s = None
            f1 = compiled_flops(gen, params, prompt, 1, static_argnums=(2,))
            fS = compiled_flops(gen, params, prompt, S, static_argnums=(2,))
            decode_flops_step = ((fS - f1) / (S - 1)
                                 if step_s and fS and f1 and fS > f1
                                 else None)
            total_mfu = mfu(fS / tS if fS else None)
            decode_mfu = mfu(decode_flops_step / step_s
                             if decode_flops_step and step_s else None)
            # the STREAMING form (tensor_generate's per-token host loop):
            # same math, one dispatch per token. Prefill is consumed (the
            # first yielded token) BEFORE the clock starts, so the gap vs
            # the scan's decode_tokens_per_s is the per-token dispatch
            # tax, not prefill; min over reps like every other number.
            stream_tps = None
            stream = _stream_for(c_len) if S > 1 else None
            if stream is not None and S > 1:
                try:
                    s_steps = min(S, 32)
                    jax.block_until_ready(
                        list(stream(prompt, s_steps))[-1])  # compile

                    def _stream_decode_s():
                        it = stream(prompt, s_steps)
                        jax.block_until_ready(next(it))  # prefill done
                        t0 = time.monotonic()
                        last = None
                        for last in it:
                            pass
                        jax.block_until_ready(last)
                        return time.monotonic() - t0
                    t_dec = min(_stream_decode_s() for _ in range(reps))
                    stream_tps = round(B * (s_steps - 1) / t_dec, 1)
                except Exception as e:  # noqa: BLE001
                    _log(f"{name} stream form failed: {e}")
            row = {
                "config": name, "platform": platform,
                "n_params": n_params,
                # blended: ALL processed tokens (P prompt + S generated
                # per sequence) over the whole wall time — consistent
                # with mfu below, which also counts prefill FLOPs
                "processed_tokens_per_s": round(B * (P + S) / tS, 1),
                "decode_tokens_per_s": (round(B / step_s, 1)
                                        if step_s else None),
                "decode_step_ms": (round(step_s * 1e3, 3)
                                   if step_s else None),
                "prefill_s": round(t1, 4),
                "stream_decode_tokens_per_s": stream_tps,
                "mfu": round(total_mfu, 4) if total_mfu else None,
                "decode_mfu": round(decode_mfu, 4) if decode_mfu else None,
            }
            print(json.dumps(row), flush=True)
            _log(f"{name}: {row['processed_tokens_per_s']} tok/s processed, "
                 f"step {row['decode_step_ms']} ms, mfu={row['mfu']}")
        except Exception as e:  # noqa: BLE001 — one point must not sink the suite
            _log(f"{name} FAILED: {e}")
            _FAILED.append(name)
            print(json.dumps({"config": name, "platform": platform,
                              "error": str(e)[:300]}), flush=True)

    # comparison row: the r4 serving configuration (f32 weights + full
    # max_seq cache) at the first point — the delta vs the main row is
    # the bf16 + right-sized-cache win, measured not claimed.
    if (points and points[0][2] > 1 and not on_cpu
            and time.monotonic() - t_start <= deadline_s
            and not os.environ.get("BENCHS_SKIP_F32_ROW")):
        B, P, S = points[0]
        name = f"transformer_lm_decode_f32_fullcache_b{B}_p{P}_s{S}"
        try:
            prompt = rng.integers(0, cfg.vocab, (B, P)).astype(np.int32)
            step32, _, _ = _marginal_step(make_generate(cfg), params_f32,
                                          prompt, S, reps)
            row = {"config": name, "platform": platform,
                   "decode_step_ms": round(step32 * 1e3, 3),
                   "decode_tokens_per_s": round(B / step32, 1)}
            print(json.dumps(row), flush=True)
            _log(f"{name}: step {row['decode_step_ms']} ms")
        except Exception as e:  # noqa: BLE001
            _log(f"{name} FAILED: {e}")
            _FAILED.append(name)
            print(json.dumps({"config": name, "platform": platform,
                              "error": str(e)[:300]}), flush=True)

    # the pallas cached-decode kernel vs the XLA oracle, first point only,
    # f32 weights + full cache (kernel operand dtypes match the oracle
    # row above — its decode_step_ms delta vs THAT row is the kernel win).
    # Gate: real TPU hardware only — on the CPU decoding runs the kernel
    # in interpret mode and the row would measure the pallas interpreter,
    # not the kernel.
    from nnstreamer_tpu.utils.hw_accel import is_tpu_platform

    run_pallas = ((is_tpu_platform(platform)
                   or os.environ.get("BENCHS_FORCE_PALLAS"))
                  and points and points[0][2] > 1
                  and time.monotonic() - t_start <= deadline_s
                  and not os.environ.get("BENCHS_SKIP_PALLAS"))
    if run_pallas:
        B, P, S = points[0]
        name = f"transformer_lm_decode_pallas_b{B}_p{P}_s{S}"
        try:
            from dataclasses import replace

            gen_p = make_generate(replace(cfg, decode_attn="pallas"))
            prompt = rng.integers(0, cfg.vocab, (B, P)).astype(np.int32)
            step_p, _, _ = _marginal_step(gen_p, params_f32, prompt, S, reps)
            row = {"config": name, "platform": platform,
                   "decode_step_ms": round(step_p * 1e3, 3),
                   "decode_tokens_per_s": round(B / step_p, 1)}
            print(json.dumps(row), flush=True)
            _log(f"{name}: step {row['decode_step_ms']} ms")
        except Exception as e:  # noqa: BLE001
            _log(f"{name} FAILED: {e}")
            _FAILED.append(name)
            print(json.dumps({"config": name, "platform": platform,
                              "error": str(e)[:300]}), flush=True)


def _timed(fn, *args, reps: int = 3):
    """Wall time of reps calls of fn(*args), each blocked to completion."""
    import jax

    out = []
    for _ in range(reps):
        t0 = time.monotonic()
        jax.block_until_ready(fn(*args))
        out.append(time.monotonic() - t0)
    return out


def _marginal_step(gen, params, prompt, S: int, reps: int):
    """One timing recipe for every generate variant: warm-compile
    steps=1 and steps=S, take min-of-reps wall for each, and derive the
    marginal per-decode-step time ((tS - t1) / (S - 1)). Returns
    ``(step_s, t1, tS)``."""
    import jax

    jax.block_until_ready(gen(params, prompt, 1))    # compile S=1
    jax.block_until_ready(gen(params, prompt, S))    # compile S
    t1 = min(_timed(gen, params, prompt, 1, reps=reps))
    tS = min(_timed(gen, params, prompt, S, reps=reps))
    return max(tS - t1, 1e-9) / (S - 1), t1, tS


# -- bench-regression sentinel (--diff) --------------------------------------

# the EXACT launch line PROFILE_r08.json was captured from (named
# elements: entry names/topology hash must line up with the baseline)
_SENTINEL = (
    "tensor_src name=src num-buffers={n} framerate=0 dimensions=3:64:64 "
    "types=float32 "
    "! tensor_transform name=stage1 mode=arithmetic option=add:1 "
    "! tensor_transform name=stage2 mode=arithmetic option=mul:2 "
    "! tensor_transform name=stage3 mode=arithmetic option=add:3 "
    "! queue name=q ! tensor_sink name=out max-stored=1")

#: entries with fewer samples than this on either side are not gated
#: (a p99 over a handful of frames is noise)
_DIFF_MIN_COUNT = 50


def _capture_sentinel(frames: int, model_version: str):
    from nnstreamer_tpu.obs import profile as obs_profile
    from nnstreamer_tpu.runtime.parse import parse_launch

    obs_profile.start()
    try:
        pipe = parse_launch(_SENTINEL.format(n=frames))
        pipe.run(timeout=300)
    finally:
        obs_profile.stop()
    art = obs_profile.ProfileArtifact.capture(
        pipe, model_version=model_version)
    obs_profile.reset()
    return art


def _regressions(baseline, fresh, max_regress: float) -> list:
    """Shared entries whose fresh p99 exceeds baseline p99 by more than
    ``max_regress`` (fractional). Compared by (scope, name) —
    ``ProfileArtifact.diff`` tolerates different keys, so a new-rig run
    diffs against the committed dev-rig artifact."""
    out = []
    for scope, names in baseline.diff(fresh).items():
        for name, row in names.items():
            a, b = row.get("a"), row.get("b")
            if a is None or b is None:
                continue
            if (a["count"] < _DIFF_MIN_COUNT
                    or b["count"] < _DIFF_MIN_COUNT):
                continue
            if a["p99_ms"] <= 0:
                continue
            frac = b["p99_ms"] / a["p99_ms"] - 1.0
            if frac > max_regress:
                out.append({"scope": scope, "name": name,
                            "baseline_p99_ms": round(a["p99_ms"], 4),
                            "fresh_p99_ms": round(b["p99_ms"], 4),
                            "regress_frac": round(frac, 3)})
    return out


def diff_main(argv=None) -> int:
    import argparse

    import jax

    from nnstreamer_tpu.obs import profile as obs_profile

    ap = argparse.ArgumentParser(
        description="bench-regression sentinel: fresh profiled run vs a "
                    "committed ProfileArtifact baseline")
    ap.add_argument("--diff", action="store_true", help="(mode marker)")
    ap.add_argument("--baseline", default=None, metavar="ARTIFACT",
                    help="baseline artifact (default: PROFILE_r08.json "
                         "next to the repo root)")
    ap.add_argument("--max-p99-regress", type=float, default=3.0,
                    metavar="FRAC",
                    help="fail when a shared entry's p99 exceeds the "
                         "baseline by more than this fraction (default "
                         "3.0 = 4x — lenient across rigs; tighten for "
                         "same-rig trajectories)")
    ap.add_argument("--frames", type=int, default=2000,
                    help="sentinel frames (matches the r08 capture)")
    ap.add_argument("--out", default=None, metavar="ARTIFACT",
                    help="write the fresh artifact (the BENCH_r1x "
                         "trajectory record)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI leg: fewer frames, same gate")
    args = ap.parse_args(argv)

    # the committed baselines are CPU artifacts — the sentinel must
    # measure the same platform (same stance as microbench_overhead)
    jax.config.update("jax_platforms", "cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    baseline_path = args.baseline or os.path.join(repo, "PROFILE_r08.json")
    baseline = obs_profile.ProfileArtifact.load(baseline_path)
    frames = 600 if args.smoke and args.frames == 2000 else args.frames

    fresh = None
    regressions = []
    # best-of-two: a co-tenant CPU spike must not fail the gate — a real
    # regression shows on BOTH attempts (microbench_overhead stance)
    for attempt in range(2):
        fresh = _capture_sentinel(frames, model_version="r11")
        regressions = _regressions(baseline, fresh,
                                   args.max_p99_regress)
        if not regressions:
            break
        _log(f"--diff attempt {attempt + 1}: {len(regressions)} "
             f"regression(s), {'retrying' if attempt == 0 else 'final'}")

    if args.out:
        fresh.save(args.out)
        _log(f"wrote fresh artifact {args.out}")
    print(json.dumps({
        "baseline": baseline_path,
        "baseline_key": baseline.key,
        "fresh_key": fresh.key,
        "frames": frames,
        "max_p99_regress": args.max_p99_regress,
        "regressions": regressions,
        "summary": {
            scope: {name: row.get("delta_p99_ms")
                    for name, row in names.items()
                    if "delta_p99_ms" in row}
            for scope, names in baseline.diff(fresh).items()},
    }, indent=2))
    if regressions:
        _log(f"FAIL: {len(regressions)} entry(ies) regressed past "
             f"{args.max_p99_regress * 100:.0f}% p99 on both attempts")
        return 1
    _log("OK: no p99 regression past the gate")
    return 0


def main() -> int:
    import numpy as np  # noqa: F401

    import jax

    from nnstreamer_tpu.utils.hw_accel import enable_compilation_cache

    _log(f"persistent XLA compile cache: {enable_compilation_cache()}")
    platform = jax.devices()[0].platform
    _log(f"platform: {platform}")

    on_cpu = platform == "cpu"
    size = int(os.environ.get("BENCHS_SIZE", "96" if on_cpu else "224"))
    batch = int(os.environ.get("BENCHS_BATCH", "8" if on_cpu else "64"))
    frames = int(os.environ.get("BENCHS_FRAMES", "64" if on_cpu else "2048"))
    deadline = float(os.environ.get("BENCHS_DEADLINE", "240"))
    warmup_batches = 2
    # multi-chip window: mesh the batched model stages over every chip
    # (ONE policy shared with bench.py — utils/flops.bench_mesh_policy)
    from nnstreamer_tpu.utils.flops import bench_mesh_policy

    n_dev = len(jax.devices())
    mesh_custom, batch = bench_mesh_policy(n_dev, on_cpu, batch)
    if mesh_custom:
        _log(f"mesh mode: dp over {n_dev} chips (batch={batch})")

    from nnstreamer_tpu.runtime.parse import parse_launch

    results = []

    def record(name, fps, measured_frames, model_batch, extra=None):
        row = {"config": name, "fps": round(fps, 1),
               "measured_frames": measured_frames,
               "batch": model_batch, "platform": platform}
        row.update(extra or {})
        results.append(row)
        print(json.dumps(row), flush=True)

    # -- 1. classification: the bench.py topology + label decode ------------
    name = "mobilenet_v2_image_labeling"
    _log(f"{name}: size=224 batch={batch} frames={frames}")
    try:
        labels = "/tmp/nns_bench_labels.txt"
        with open(labels, "w") as fh:
            fh.write("\n".join(f"class{i}" for i in range(1001)))
        pipe = parse_launch(
            f"tensor_src num-buffers={frames} dimensions=3:224:224:1 "
            "types=uint8 pattern=random "
            f"! tensor_aggregator frames-out={batch} frames-dim=0 concat=true "
            "! queue max-size-buffers=4 "
            "! tensor_filter framework=jax "
            "model=nnstreamer_tpu.models.mobilenet_v2:filter_model_u8 "
            + (f"custom={mesh_custom} " if mesh_custom else "")
            + "sync-invoke=false "
            f"! tensor_decoder mode=image_labeling option1={labels} "
            "! tensor_sink name=out max-stored=1")
        fps_b, n = _run_fps(pipe, "out", frames // batch, warmup_batches, deadline)
        fps1 = fps_b * batch
        # aux measurements (MFU, p50) must never cost the primary fps
        # number already in hand — they fail soft onto the same row
        extra = {}
        try:
            from nnstreamer_tpu.models import mobilenet_v2 as _mnv2

            extra = _model_perf(_mnv2.filter_model_u8, (1, 224, 224, 3),
                                "uint8", fps1,
                                n_chips=n_dev if mesh_custom else 1)
            extra.update(_mesh_fields(mesh_custom, n_dev))
            _log(f"{name}: p50 pipeline latency (batch=1) ...")
            extra["p50_pipeline_ms"] = round(_pipeline_p50(
                "nnstreamer_tpu.models.mobilenet_v2:filter_model_u8", 224,
                f"tensor_decoder mode=image_labeling option1={labels}",
                dtype="uint8"), 2)
        except Exception as e:  # noqa: BLE001
            _log(f"{name} aux (mfu/p50) failed: {e}")
        record(name, fps1, n * batch, batch, extra)
    except Exception as e:
        _log(f"{name} FAILED: {e}")
        _FAILED.append(name)
        record(name, 0.0, 0, batch)

    # -- 2-4. detection / pose / segmentation -------------------------------
    # TPU-first topology (r5): uint8 ingest with normalization fused into
    # the model graph (4× less H2D), model batched via the aggregator, and
    # the DECODER batched too (frames-in=N): candidate parsing / argmax /
    # keypoint gather run as one jitted device reduction per batch, so
    # only compact arrays cross D2H (decoders/base.py make_reduce). The
    # reference-shaped per-frame host decode remains the p50 topology.
    per_frame = [
        # SSD's anchor grid is baked for its 224 input; pose/segment heads
        # are fully convolutional and follow BENCHS_SIZE
        ("ssd_mobilenet_bounding_boxes", 224,
         "nnstreamer_tpu.models.ssd_mobilenet:filter_model_u8",
         "tensor_decoder mode=bounding_boxes "
         "option1=mobilenet-ssd-postprocess option3=,30 option4=224:224"),
        ("posenet_pose_estimation", size,
         "nnstreamer_tpu.models.posenet:filter_model_u8",
         f"tensor_decoder mode=pose_estimation option1={size}:{size} "
         "option2=heatmap"),
        ("deeplab_image_segment", size,
         "nnstreamer_tpu.models.deeplab:filter_model_u8",
         "tensor_decoder mode=image_segment option1=tflite-deeplab"),
    ]
    pf_batch = int(os.environ.get("BENCHS_PERFRAME_BATCH",
                                  "1" if on_cpu else str(batch)))
    # burst-aware sizing: the batched decoder emits frames in bursts of
    # pf_batch, so (a) at least 4 whole batches must run, (b) the frame
    # budget quantizes to full batches (the aggregator drops a partial
    # tail at EOS), and (c) warmup ends on a burst boundary with >=2
    # bursts left in the measured window
    pf_batch = max(1, min(pf_batch, frames // 4))
    pf_frames = (frames // pf_batch) * pf_batch
    pf_warmup = max(warmup_batches, 2) * pf_batch
    for name, in_size, model, dec in per_frame:
        _log(f"{name}: size={in_size} frames={pf_frames} model_batch={pf_batch}")
        try:
            # mesh the batched model stage only when the batch divides the
            # dp axis (same rule as config 1)
            pf_mesh = mesh_custom if (mesh_custom
                                      and pf_batch % n_dev == 0) else ""
            stage = (f"tensor_filter framework=jax model={model} "
                     + (f"custom={pf_mesh} " if pf_mesh else "")
                     + "sync-invoke=false")
            dec_stage = dec
            if pf_batch > 1:
                stage = (
                    f"tensor_aggregator frames-out={pf_batch} frames-dim=0 "
                    "concat=true ! queue max-size-buffers=4 "
                    f"! {stage}")
                dec_stage = f"{dec} frames-in={pf_batch}"
            pipe = parse_launch(
                f"tensor_src num-buffers={pf_frames} "
                f"dimensions=3:{in_size}:{in_size}:1 "
                "types=uint8 pattern=random "
                f"! {stage} "
                "! queue max-size-buffers=8 "
                f"! {dec_stage} ! tensor_sink name=out max-stored=1")
            fps, n = _run_fps(pipe, "out", pf_frames, pf_warmup, deadline)
            extra = {}
            try:  # aux (MFU, p50) fails soft — never costs the fps number
                import importlib

                mod_name, attr = model.split(":")
                entry = getattr(importlib.import_module(mod_name), attr)
                extra = _model_perf(entry, (1, in_size, in_size, 3),
                                    "uint8", fps,
                                    n_chips=n_dev if pf_mesh else 1)
                extra.update(_mesh_fields(pf_mesh, n_dev))
                _log(f"{name}: p50 pipeline latency (batch=1) ...")
                extra["p50_pipeline_ms"] = round(
                    _pipeline_p50(model, in_size, dec, dtype="uint8"), 2)
            except Exception as e:  # noqa: BLE001
                _log(f"{name} aux (mfu/p50) failed: {e}")
            record(name, fps, n, pf_batch, extra)
        except Exception as e:
            _log(f"{name} FAILED: {e}")
            _FAILED.append(name)
            record(name, 0.0, 0, pf_batch)

    # -- 4b. the reference's REAL quantized zoo model on XLA ----------------
    # mobilenet_v2_1.0_224_quant.tflite through the flatbuffer importer
    # (models/tflite_import.py). The headline row runs the int8 execution
    # path (tflite_int8.py: int8 GEMMs, int32 accumulators, requantize —
    # the answer to the reference interpreter's native int8 kernels); the
    # fake-quant byte-parity oracle is recorded as its own row. On the
    # single-core CPU fallback batching past 1 only thrashes cache
    # (measured), so the batch is per-platform. Interpreter match pinned
    # by test_tflite_import. Skipped when the reference tree is absent.
    ref_quant = ("/root/reference/tests/test_models/models/"
                 "mobilenet_v2_1.0_224_quant.tflite")
    q_exec = os.environ.get("BENCHS_QUANT_EXEC", "int8")
    q_batch = int(os.environ.get("BENCHS_QUANT_BATCH",
                                 "1" if on_cpu else str(batch)))
    quant_rows = [("mobilenet_v2_quant_tflite_on_xla", q_exec, q_batch),
                  ("mobilenet_v2_quant_tflite_on_xla_oracle",
                   "fake-quant", q_batch),
                  # the C++ engine (native/csrc/nns_q8.cc) always executes
                  # on the HOST cpu — batch 1, the interpreter's operating
                  # point, so this row pairs with the interpreter row on
                  # every platform
                  ("mobilenet_v2_quant_tflite_int8_native",
                   "int8-native", 1)]
    for name, exec_mode, qb in quant_rows if os.path.exists(ref_quant) else []:
        _log(f"{name}: exec={exec_mode} batch={qb} frames={frames}")
        # the C++ engine executes on the HOST cpu regardless of the jax
        # platform: a mesh label (or a per-chip MFU denominator) on that
        # row would claim accelerator devices for a single-host number
        host_native = exec_mode == "int8-native"
        q_mesh = "" if host_native else mesh_custom
        try:
            q_custom = ",".join(
                p for p in (f"quantized_exec:{exec_mode}",
                            f"batch:{qb}" if qb > 1 else "",
                            q_mesh) if p)
            agg = (f"! tensor_aggregator frames-out={qb} frames-dim=0 "
                   "concat=true " if qb > 1 else "")
            pipe = parse_launch(
                f"tensor_src num-buffers={frames} dimensions=3:224:224:1 "
                "types=uint8 pattern=random "
                f"{agg}"
                "! queue max-size-buffers=4 "
                f"! tensor_filter framework=jax model={ref_quant} "
                f"custom={q_custom} sync-invoke=false "
                "! tensor_sink name=out max-stored=1")
            # first invoke carries the XLA compile (seconds); at ~100 fps
            # per-frame a 2-frame warmup would leave post-compile queue
            # drain inside the measured window — warm a real fraction
            fps_b, n = _run_fps(pipe, "out", frames // qb,
                                max(warmup_batches, (frames // qb) // 3),
                                deadline)
            extra = {"quantized_exec": exec_mode}
            if not host_native:  # host engine is not jit-lowerable: the
                # XLA cost analysis would rebuild the graph for a None
                try:
                    from nnstreamer_tpu.models.tflite_import import load_tflite

                    q_fn, _, _ = load_tflite(
                        ref_quant, {"quantized_exec": exec_mode})
                    extra.update(_model_perf(
                        q_fn, (1, 224, 224, 3), "uint8", fps_b * qb,
                        n_chips=n_dev if q_mesh else 1))
                except Exception as e:  # noqa: BLE001
                    _log(f"{name} aux (mfu) failed: {e}")
            extra.update(_mesh_fields(q_mesh, n_dev))
            record(name, fps_b * qb, n * qb, qb, extra)
        except Exception as e:
            _log(f"{name} FAILED: {e}")
            _FAILED.append(name)
            record(name, 0.0, 0, qb)

    # -- 4c. the SAME quant model on the reference's flagship backend -------
    # framework=tflite (interpreter, host CPU, per-frame — the reference's
    # operating mode, tensor_filter_tensorflow_lite.cc): the self-measured
    # baseline column BASELINE.md asks for. The ratio of 4b to this row is
    # "our XLA path vs the reference's path on identical hardware+file";
    # since r5's int8 execution path + depthwise shift-add it is ~1.0 even
    # on the single-core CPU fallback (r4 was 0.05 with the fake-quant
    # float simulation) and the accelerator adds the MXU on top.
    if os.path.exists(ref_quant):
        name = "mobilenet_v2_quant_tflite_interpreter"
        n_f = min(frames, 128)  # interpreter is host-CPU; keep bounded
        _log(f"{name}: per-frame, frames={n_f}")
        try:
            pipe = parse_launch(
                f"tensor_src num-buffers={n_f} dimensions=3:224:224:1 "
                "types=uint8 pattern=random "
                "! queue max-size-buffers=4 "
                f"! tensor_filter framework=tflite model={ref_quant} "
                "! tensor_sink name=out max-stored=1")
            fps, n = _run_fps(pipe, "out", n_f, 4, deadline)
            record(name, fps, n, 1)
        except Exception as e:
            _log(f"{name} FAILED: {e}")
            _FAILED.append(name)
            record(name, 0.0, 0, 1)

    # -- 5. among-device: sharded stream over 2 loopback query workers ------
    name = "tensor_query_sharded_x2"
    _log(f"{name}: 2 loopback workers, frames={frames}")
    # workers serve the north star's classification model (BASELINE
    # config #5 names no model): uint8 frames on the wire + fused-u8
    # mobilenet, so the sharded stream measures query/shard/re-join
    # mechanics, not a 22 MB/frame logits volume (the r4 worker ran
    # full deeplab and the TPU row was pure D2H)
    servers = []
    try:
        ports = []
        for i in range(2):
            srv = parse_launch(
                f"tensor_query_serversrc name=ssrc id={i} port=0 "
                f"caps=other/tensors,format=static,dimensions=3:{size}:{size}:1,"
                "types=uint8 "
                "! tensor_filter framework=jax "
                "model=nnstreamer_tpu.models.mobilenet_v2:filter_model_u8 "
                f"! tensor_query_serversink id={i}")
            srv.play()
            servers.append(srv)
            ssrc = srv.get("ssrc")
            bind_deadline = time.monotonic() + 5
            while ssrc.bound_port == 0 and time.monotonic() < bind_deadline:
                time.sleep(0.01)
            if ssrc.bound_port == 0:
                raise RuntimeError(f"worker {i} never bound a port")
            ports.append(ssrc.bound_port)
        client = parse_launch(
            f"tensor_src num-buffers={frames} dimensions=3:{size}:{size}:1 "
            "types=uint8 pattern=random "
            "! tensor_shard name=s "
            f"s.src_0 ! queue ! tensor_query_client host=127.0.0.1 "
            f"port={ports[0]} ! u.sink_0 "
            f"s.src_1 ! queue ! tensor_query_client host=127.0.0.1 "
            f"port={ports[1]} ! u.sink_1 "
            "tensor_unshard name=u ! tensor_sink name=out max-stored=1")
        fps, n = _run_fps(client, "out", frames, warmup_batches * 4, deadline)
        record(name, fps, n, 1)
    except Exception as e:
        _log(f"{name} FAILED: {e}")
        _FAILED.append(name)
        record(name, 0.0, 0, 1)
    finally:
        for srv in servers:
            srv.stop()

    # -- 6. transformer LM prefill + KV-cache decode ------------------------
    if not os.environ.get("BENCHS_SKIP_LM"):
        _bench_lm_decode(platform, on_cpu,
                         deadline_s=float(os.environ.get(
                             "BENCHS_LM_DEADLINE", "600")))

    _log("---- summary ----")
    for row in results:
        _log(f"{row['config']:34s} {row['fps']:10.1f} fps  "
             f"({row['platform']}, mfu={row.get('mfu')})")
    if _FAILED:
        _log(f"FAIL: error rows for {_FAILED}")
        return 1
    return 0


if __name__ == "__main__":
    if "--diff" in sys.argv[1:]:
        sys.exit(diff_main(sys.argv[1:]))
    sys.exit(main())
