"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once on one TPU chip, through the entry points a
user calls, at the full width of models the repo has, and checks what comes
out by the repo's own means. One process, no child that needs the chip.

  stream   the README launch line (tensor_src → tensor_aggregator → queue →
           tensor_filter mobilenet_v2:filter_model_u8 → tensor_decoder
           image_labeling frames-in=64 → tensor_sink) at batch 64, 224×224,
           plus the same model behind a tensor_transform so that a fused
           segment runs;
  serving  lm_serving.base.make_continuous(slots=8) behind a
           DecodeScheduler, twelve seeded requests (5..1500 prompt tokens,
           a shared prefix, a page-aligned copy-on-write), then two
           requests through the speculative engine (draft="ngram");
  latent_serving
           the second model family (DeepSeek-V3-shaped: latent attention,
           one pool line a token, dropless experts) through the same engine
           and scheduler at the benchmark configuration's rehearsal sizes,
           served tokens against the plain reference under the near-tie rule;
  window_serving
           the third model family (Mellum-shaped: grouped-query lines,
           window layers that give their pages back beside full layers that
           keep them, dropless softmax top-k experts) the same way, at its
           benchmark configuration's rehearsal sizes with hidden and expert
           widths of 128: whole lanes, so the programs hold the experts'
           kernel (both legs' report says the form, ``experts_form``);
  state_serving
           the fourth model family (Jamba-shaped: state-space layers that
           keep a state a slot beside multi-query attention layers that keep
           lines a token) the same way, at its benchmark configuration's
           rehearsal depth with widths of whole lanes, so that both programs
           hold the state layers' kernels: ten requests over eight slots (a
           slot is reused), one preempt and restore by hand;
  kernels  both Pallas kernels, Mosaic-lowered, at base geometry.

It refuses to run anywhere but on a TPU, prints no result there, and exits
non-zero. A failing leg is recorded, the other legs still run (a chip call
is too dear to stop at the first fault), and the exit code is 1.

Stdout is two JSON lines. The last is the result, these keys and no others:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
The one before it is ``{"report": {...}}``: versions, the compile-cache
directory, and per leg jax's own seconds of tracing and of XLA compilation
(or cache load), the seconds of the steady part, and the counts checked;
they say how long the smoke took and whether the compile cache was warm,
they are not performance results.

    python chip_smoke.py            # on the chip, through the chip tool
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# -- tolerances ---------------------------------------------------------------
# Near-tie rule. On the MXU an f32 matmul runs bf16 passes by default, so two
# differently shaped programs of the same math (paged vs dense, verify's
# multiply-reduce vs step's matmuls, a fused segment vs the u8 entry) round
# differently and an argmax over nearly equal logits can flip. A disagreement
# passes only where the reference's own top-2 gap is below this; a larger gap
# is a wrong program. The precision is NOT raised to make the check pass.
#
# LM: logits are rmsnorm(x) @ embed.T over dim 1024 with embed ~ N(0, 0.02²),
# so they spread about 0.6; one bf16 rounding of each operand (2^-9 relative)
# moves a logit by ~3e-3. Measured on the v5e (PR 21): 4 of 12 paged streams
# leave the dense reference, at reference gaps of 0.0014 to 0.0019. 0.02 is
# ten times that and a thirtieth of the spread.
LM_NEAR_TIE_GAP = 0.02
# Vision: MobileNet-v2 computes in bfloat16 end to end (2^-8 relative per op,
# ~50 layers); logits of the random-weight model spread about 1. Measured
# (PR 21): no label differs; the fused variant's logits differ by 8e-5.
VISION_NEAR_TIE_GAP = 0.1
# Pallas kernels vs the XLA oracle at precision=highest, outputs of order 1
# (up to ~4 where few keys are visible). Mosaic, like XLA, runs a float32 dot
# as bf16 passes by default, so scores carry a 2^-9 rounding of each operand
# and a softmax weight over few keys moves by ~1e-2 of itself; bfloat16 inputs
# add the rounding of the output. Measured on the v5e (PR 21): decode 1.0e-3
# (f32) and 4.9e-4 (bf16), flash 1.5e-2 (f32) and 7.9e-3 (bf16). A masking,
# indexing or scaling fault is an error of order 0.1 to 1.
KERNEL_ATOL = 3e-2

STREAM_LINE = (
    "tensor_src num-buffers={frames} dimensions=3:224:224:1 types=uint8 "
    "pattern=random seed=7 "
    "! tensor_aggregator frames-out={batch} frames-dim=0 concat=true "
    "! queue "
    "! {stage} "
    # the tee only lets the smoke see the filter's own output beside the
    # decoded labels; the labels branch is the README line
    "! tee name=t "
    "t. ! tensor_decoder mode=image_labeling frames-in={batch} "
    "! tensor_sink name=labels max-stored=1 "
    "t. ! queue ! tensor_sink name=logits max-stored=1")
U8_STAGE = ("tensor_filter framework=jax {custom}"
            "model=nnstreamer_tpu.models.mobilenet_v2:filter_model_u8 name=f")
FUSED_STAGE = ("tensor_transform mode=arithmetic "
               "option=typecast:float32,div:127.5,add:-1.0 "
               "! tensor_filter framework=jax "
               "model=nnstreamer_tpu.models.mobilenet_v2:filter_model name=f")


def compile_clock() -> dict:
    """Seconds jax itself reports for tracing and lowering and for XLA
    compilation (or the load from the persistent cache that takes its
    place), and the cache's hits and misses, so a warm run shows as what
    it is whatever else the wall time holds: the program's own compile
    account (``obs.context.compile_account``), which listens from
    ``enable_compilation_cache`` on."""
    from nnstreamer_tpu.obs import context as obs_context

    totals = obs_context.compile_account()["totals"]
    return {"trace_s": totals["trace_s"] + totals["lower_s"],
            "compile_s": totals["compile_s"],
            "cache_hits": totals["cache_hits"],
            "cache_misses": totals["cache_misses"]}


class CheckFailed(AssertionError):
    """A leg's output is wrong (not a crash: the program ran and lied)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def first_divergence(got, ref):
    """Index of the first position where two token/label streams differ,
    or None when the common prefix is the whole of both."""
    n = min(len(got), len(ref))
    for i in range(n):
        if int(got[i]) != int(ref[i]):
            return i
    return None if len(got) == len(ref) else n


def top2_gap(logits) -> float:
    import numpy as np

    top = np.sort(np.asarray(logits, np.float32).ravel())[-2:]
    return float(top[1] - top[0])


def near_tie(name: str, got, ref, ref_logits_at, tol: float) -> dict:
    """The near-tie rule: ``got`` may leave ``ref`` only at a position
    where the reference's own top-2 logit gap is below ``tol``.
    ``ref_logits_at(i)`` → the reference's logits for position ``i``."""
    check(len(got) == len(ref), f"{name}: {len(got)} outputs, reference "
                                f"has {len(ref)}")
    i = first_divergence(got, ref)
    if i is None:
        return {"first_diff": None}
    gap = top2_gap(ref_logits_at(i))
    print(f"chip_smoke: {name}: first difference at {i}: got {int(got[i])}, "
          f"reference {int(ref[i])}, reference top-2 gap {gap:.5f} "
          f"(tolerance {tol})", file=sys.stderr)
    check(gap <= tol, f"{name}: differs from the reference at {i} where the "
                      f"reference's top-2 gap is {gap:.5f} > {tol}: not a "
                      "near tie, a wrong program")
    return {"first_diff": i, "ref_top2_gap": round(gap, 6)}


# -- stream -------------------------------------------------------------------

def _run_stream(stage: str, batch: int, frames: int):
    """Play one stream to EOS; returns (labels, logits buffers, pipe, times)."""
    from nnstreamer_tpu.core import MessageType
    from nnstreamer_tpu.runtime.parse import parse_launch

    pipe = parse_launch(STREAM_LINE.format(frames=frames, batch=batch,
                                           stage=stage))
    labels, raw, stamps = [], [], []
    pipe.get("labels").connect(lambda b: labels.append(b.meta["label_index"]))

    def on_logits(buf):
        raw.append(buf.tensors[0])
        stamps.append(time.monotonic())

    pipe.get("logits").connect(on_logits)
    t0 = time.monotonic()
    pipe.play()
    try:
        msg = pipe.bus.wait_for((MessageType.EOS, MessageType.ERROR),
                                timeout=600)
    finally:
        pipe.stop()
    t_end = time.monotonic()
    check(msg is not None, "stream: no EOS within 600 s")
    check(msg.type is MessageType.EOS,
          f"stream: ERROR from {msg.source}: {msg.data}")
    check(len(raw) == frames // batch and len(labels) == frames,
          f"stream: {len(labels)} labels / {len(raw)} batches for "
          f"{frames} frames")
    # up to the first batch: model build, trace, compile or cache load, H2D
    return labels, raw, pipe, (stamps[0] - t0, t_end - stamps[0])


def stream_leg(batch: int = 64, frames: int = 256,
               compute_dtype: str = "bfloat16") -> dict:
    import numpy as np

    import jax

    from nnstreamer_tpu.models import mobilenet_v2
    from nnstreamer_tpu.models._blocks import resolve_compute_dtype

    resolved = resolve_compute_dtype("auto")
    check(resolved == compute_dtype,
          f"stream: compute dtype resolved to {resolved}, not {compute_dtype}")
    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    out = {"frames": frames, "batch": batch, "compute_dtype": resolved}

    # the reference: a direct jax.jit call of the same entry on the same
    # chip, on the first batch tensor_src will make (same seeded generator)
    rng = np.random.default_rng(7)
    batch0 = np.concatenate([
        rng.integers(0, 127, (1, 224, 224, 3)).astype(np.uint8)
        for _ in range(batch)])
    ref = np.asarray(jax.jit(mobilenet_v2.filter_model_u8.make())(batch0))
    ref_labels = ref.argmax(-1)

    def checked(name: str, stage: str, n_frames: int) -> dict:
        labels, raw, pipe, (first_batch_s, steady_s) = _run_stream(
            stage, batch, n_frames)
        check(all(0 <= lab < ref.shape[1] for lab in labels),
              f"{name}: label out of range")
        for arr in raw:
            check(isinstance(arr, jax.Array),
                  f"{name}: filter output is {type(arr).__name__}, "
                  "not a jax.Array")
            check({d.platform for d in arr.devices()} == {platform},
                  f"{name}: filter output lives on {arr.devices()}")
            check(arr.shape == ref.shape and arr.dtype == ref.dtype,
                  f"{name}: filter output {arr.shape} {arr.dtype}")
        got = np.asarray(raw[0])
        check(bool(np.isfinite(got).all()), f"{name}: non-finite logits")
        res = near_tie(name, labels[:batch], ref_labels,
                       lambda i: ref[i], VISION_NEAR_TIE_GAP)
        res.update(first_batch_s=round(first_batch_s, 2),
                   steady_s=round(steady_s, 3),
                   logits_max_abs_diff=float(np.abs(got - ref).max()),
                   devices_per_output=len(raw[0].devices()),
                   fused_segments=[dict(s.stats) for s in pipe.fused_segments])
        return res

    out["readme_line"] = checked(
        "stream", U8_STAGE.format(custom=""), frames)
    out["fused"] = fused = checked("stream-fused", FUSED_STAGE, 2 * batch)
    segs = fused["fused_segments"]
    check(len(segs) == 1 and segs[0]["dispatches"] > 0
          and segs[0]["defused"] == 0,
          f"stream-fused: fused segment stats {segs}")
    if n_dev > 1:
        # more than one chip here: the same line, batch-sharded over all
        out["mesh"] = mesh = checked(
            "stream-mesh", U8_STAGE.format(custom=f"custom=mesh:dp={n_dev} "),
            frames)
        check(mesh["devices_per_output"] == n_dev,
              f"stream-mesh: output on {mesh['devices_per_output']} of "
              f"{n_dev} devices")
    out["steady_s"] = round(sum(
        v["steady_s"] for v in out.values() if isinstance(v, dict)), 3)
    return out


# -- serving ------------------------------------------------------------------

def _prompts(vocab: int, lengths, page_size: int, seed: int = 21):
    """Seeded prompts of the given lengths. The last two share the first
    one's full-page prefix: one adds its own tail (a registry hit), one IS
    the page-aligned prefix (its last position lands in a shared page, so
    the write copies it first)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]
    shared = (len(prompts[0]) // page_size) * page_size
    check(2 * page_size <= shared < len(prompts[0]),
          "first prompt must span several pages and end inside one")
    tail = rng.integers(0, vocab, len(prompts[0]) - shared).astype(np.int32)
    prompts.append(prompts[0][:shared].copy())
    prompts.append(np.concatenate([prompts[0][:shared], tail]))
    return prompts


def _step_now(engine):
    """One decode step of a hand-driven engine and its own tokens: the
    engine keeps a step in flight, so step, then collect."""
    engine.step()
    return engine.collect()


def _serve(engine, prompts, steps: int):
    """Submit every prompt at once to a DecodeScheduler over ``engine``;
    returns (token streams, metrics snapshot, seconds)."""
    from nnstreamer_tpu.serving import DecodeScheduler

    sched = DecodeScheduler(engine, name="chip-smoke")
    t0 = time.monotonic()
    try:
        reqs = [sched.submit(p, steps=steps) for p in prompts]
        streams = [r.result(timeout=600)[0] for r in reqs]
        snap = sched.metrics_snapshot()
    finally:
        sched.close()  # releases every slot and closes the engine
    return streams, snap, time.monotonic() - t0


def serving_leg(entry=None, slots: int = 8, steps: int = 32,
                lengths=(200, 5, 640, 1500, 5, 640, 1500, 200, 640, 5),
                page_size: int = 16) -> dict:
    import functools

    import numpy as np

    import jax

    from nnstreamer_tpu.models import lm_serving
    from nnstreamer_tpu.models.decoding import make_generate
    from nnstreamer_tpu.models.transformer import forward

    entry = entry or lm_serving.base
    cfg = entry._cfg_serve
    prompts = _prompts(cfg.vocab, lengths, page_size)
    check(len(prompts) > slots, "more requests than slots, so slots churn")
    out = {"requests": len(prompts), "slots": slots, "steps": steps}

    engine = entry.make_continuous(slots=slots, page_size=page_size)
    params = engine.params
    # warm the two programs every request runs, so that their compile
    # stays out of the run
    t0 = time.monotonic()
    engine.admit(0, prompts[1], 2)
    _step_now(engine)
    engine.release(0)
    out["warmup_s"] = round(time.monotonic() - t0, 2)

    streams, snap, took = _serve(engine, prompts, steps)
    out["steady_s"] = round(took, 2)
    pool = snap["kv_pool"]
    out.update(compile_count=snap["compile_count"],
               prefix_hits_total=pool["prefix_hits_total"],
               cow_copies_total=pool["cow_copies_total"],
               completed=snap["completed"])
    for i, toks in enumerate(streams):
        check(len(toks) == steps, f"serving: request {i} got {len(toks)} "
                                  f"tokens, wanted {steps}")
        check(bool(((toks >= 0) & (toks < cfg.vocab)).all()),
              f"serving: request {i} has out-of-vocabulary tokens")
    check(pool["prefix_hits_total"] >= 1, f"serving: no prefix hit: {pool}")
    check(pool["cow_copies_total"] >= 1, f"serving: no COW copy: {pool}")
    check(engine.pool.used_pages == 0,
          f"serving: {engine.pool.used_pages} pages still held after close")
    # step + prefill_chunk + copy_page, whatever the prompt lengths were
    check(snap["compile_count"] == 3,
          f"serving: compile_count {snap['compile_count']}, expected 3")

    # the dense reference: same params, same chip, one request at a time
    generate = make_generate(cfg)
    logits_of = jax.jit(functools.partial(forward, cfg))

    def reference(prompt):
        full = np.asarray(generate(params, prompt[None, :], steps))[0]

        def logits_at(i):
            # position P+i-1 of the reference's own sequence predicts its
            # generated token i
            return logits_of(params, full[None, :])[0, len(prompt) + i - 1]

        return full[len(prompt):], logits_at

    t0 = time.monotonic()
    out["near_ties"] = ties = []
    for i, (prompt, toks) in enumerate(zip(prompts, streams)):
        ref, logits_at = reference(prompt)
        res = near_tie(f"serving[{i}]", toks, ref, logits_at,
                       LM_NEAR_TIE_GAP)
        if res["first_diff"] is not None:
            ties.append({"request": i, **res})

    # speculative decode: _verify_commit on the chip, two requests
    spec = entry.make_continuous(slots=2, draft="ngram",
                                 page_size=page_size)
    spec_prompts = [np.tile(prompts[1], 8), prompts[0]]
    streams, snap, spec_s = _serve(spec, spec_prompts, steps)
    out.update(spec_rounds=snap["spec_rounds"],
               spec_accepted=snap["spec_accepted"],
               spec_compile_count=snap["compile_count"],
               spec_s=round(spec_s, 2))
    check(snap["spec_rounds"] > 0, "speculative: no verify round ran")
    # prefill_chunk + verify_commit
    check(snap["compile_count"] == 2,
          f"speculative: compile_count {snap['compile_count']}, expected 2")
    check(spec.pool.used_pages == 0, "speculative: pages held after close")
    for i, (prompt, toks) in enumerate(zip(spec_prompts, streams)):
        check(len(toks) == steps, f"speculative[{i}]: {len(toks)} tokens")
        ref, logits_at = reference(prompt)
        res = near_tie(f"speculative[{i}]", toks, ref, logits_at,
                       LM_NEAR_TIE_GAP)
        if res["first_diff"] is not None:
            ties.append({"request": f"spec{i}", **res})
    out["reference_s"] = round(time.monotonic() - t0 - spec_s, 2)
    return out


#: the window leg's widths over its configuration's rehearsal sizes: whole
#: lanes, so that on a TPU its programs hold the experts' kernel
#: (``ops/moe_grouped.py``) as the benchmark's do, not the grouped product
#: a width of 32 falls back to. The latent leg keeps its rehearsal widths
#: and so the grouped product: at 128 its seed has a near tie in the router
#: (sigmoid weights scaled by 2.448 over two of eight experts: an expert
#: swapped moves a logit by far more than a rounding does), and on the chip
#: a served token lay 0.0894462 under the reference's best through the
#: kernel and through the grouped product alike, 0.0 on two other seeds
#: (PR 32)
EXPERT_WIDTHS = {"hidden_size": 128, "moe_intermediate_size": 128}


def _experts_form(slots: int, cfg, serve_dtype: str) -> str:
    """The form the leg's decode step holds its expert layers in."""
    from nnstreamer_tpu.ops import moe_grouped

    return moe_grouped.form(slots, cfg.hidden_size,
                            cfg.moe_intermediate_size, serve_dtype)


def _reference_gaps(leg: str, reference, key, sizes, served, steps: int,
                    width: int, tol: float = LM_NEAR_TIE_GAP) -> list:
    """For every ``(prompt, tokens)`` of ``served``, how far each served
    token's logit lies under the plain reference's best, teacher-forced on
    what was served; checks the largest against the near-tie tolerance."""
    import numpy as np

    tokens = np.zeros((len(served), width), np.int32)
    at = np.zeros((len(served), steps), np.int32)
    for i, (p, toks) in enumerate(served):
        check(len(toks) == steps, f"{leg}[{i}]: {len(toks)} tokens")
        tokens[i, :len(p)] = p
        tokens[i, len(p):len(p) + steps - 1] = toks[:-1]
        at[i] = len(p) - 1 + np.arange(steps)
    exact = reference.logits_for(key, sizes, tokens, at)["none"]
    gaps = [exact[i].max(-1) - np.take_along_axis(
        exact[i], np.asarray(toks)[:, None], 1)[:, 0]
        for i, (_, toks) in enumerate(served)]
    worst = float(max(g.max() for g in gaps))
    check(worst <= tol,
          f"{leg}: a served token lies {worst:.5f} under the reference's "
          f"best (tolerance {tol}): not a near tie, a wrong program")
    return gaps


def latent_serving_leg(serve_dtype: str = "bfloat16", slots: int = 4,
                       steps: int = 12, lengths=(37, 5, 20, 9, 30, 14),
                       seed: int = 27) -> dict:
    """The second model family through the same engine and scheduler: the
    DeepSeek-V3-shaped block (latent attention, dropless experts) at the
    benchmark configuration's rehearsal sizes, seeded weights, against the
    plain reference (``benchmark/references/deepseek_v3_lm.py``, float32 at
    ``highest``), teacher-forced on what was served: a served token may
    leave the reference's best only by a near tie of its logits."""
    import numpy as np

    import jax.numpy as jnp

    from benchmark.lib import harness
    from benchmark.lib.weights import seed_key
    from benchmark.references import deepseek_v3_lm as reference
    from nnstreamer_tpu.models.deepseek_v3 import DeepseekV3Config
    from nnstreamer_tpu.models.lm_serving import _LMServingEntry

    _, config = harness.find_cell(harness.load_benchmark(),
                                  "kanana2_decode_saturated")
    config = {**config, **config["rehearsal"]}
    cfg = DeepseekV3Config.from_published(config)
    sizes, key = reference.sizes(config), seed_key(seed)
    params = reference.program_params(key, sizes, jnp.dtype(serve_dtype))

    class Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    engine = Seeded(cfg, serve_dtype=serve_dtype).make_continuous(
        slots=slots, **{k: v for k, v in config["engine"].items()
                        if k != "slots"})
    check(engine.family.name == "deepseek_v3" and len(engine._pools) == 1,
          "latent: the engine took another family or geometry")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    streams, snap, took = _serve(engine, prompts, steps)
    out = {"requests": len(prompts), "steady_s": round(took, 2),
           "completed": snap["completed"],
           "compile_count": snap["compile_count"],
           "line_widths": snap["kv_pool"]["line_widths"],
           "moe_assignments": snap["moe_assignments"],
           "moe_experts_touched": snap["moe_experts_touched"],
           "experts_form": _experts_form(slots, cfg, serve_dtype)}
    check(snap["completed"] == len(prompts), f"latent: {snap['completed']} "
                                             f"of {len(prompts)} completed")
    check(engine.pool.used_pages == 0, "latent: pages held after close")
    # every prompt token and every decoded row, top-k experts in each of
    # the expert layers: nothing dropped
    rows = sum(len(p) + steps - 1 for p in prompts)
    per_row = cfg.num_experts_per_tok * (cfg.num_hidden_layers
                                         - cfg.first_k_dense_replace)
    check(snap["moe_assignments"] == rows * per_row,
          f"latent: {snap['moe_assignments']} assignments for {rows} rows")
    gaps = _reference_gaps("latent", reference, key, sizes,
                           list(zip(prompts, streams)), steps,
                           max(len(p) for p in prompts) + steps)
    out["served_gap_max"] = float(max(g.max() for g in gaps))
    return out


def window_serving_leg(serve_dtype: str = "bfloat16", slots: int = 4,
                       steps: int = 30, lengths=(37, 7, 20, 9, 45, 14),
                       seed: int = 31) -> dict:
    """The third model family through the same engine and scheduler: the
    Mellum-shaped block (grouped-query attention, three window layers to
    one full layer, dropless softmax top-k experts) at the benchmark
    configuration's rehearsal sizes, seeded weights, against the plain
    reference (``benchmark/references/mellum_lm.py``, float32 at
    ``highest``), teacher-forced on what was served. The contexts cross the
    window inside prefill and while decoding, so pages of the window kind
    are given back on the served path; none of either kind is left."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from benchmark.lib import harness
    from benchmark.lib.weights import seed_key
    from benchmark.references import mellum_lm as reference
    from nnstreamer_tpu.models.lm_serving import _LMServingEntry
    from nnstreamer_tpu.models.mellum import MellumConfig

    _, config = harness.find_cell(harness.load_benchmark(),
                                  "mellum2_longctx_decode")
    config = {**config, **config["rehearsal"], **EXPERT_WIDTHS}
    cfg = MellumConfig.from_published(config)
    sizes, key = reference.sizes(config), seed_key(seed)
    params = reference.program_params(key, sizes, jnp.dtype(serve_dtype))

    class Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    engine = Seeded(cfg, serve_dtype=serve_dtype).make_continuous(
        slots=slots, **{k: v for k, v in config["engine"].items()
                        if k != "slots"})
    check(engine.family.name == "mellum"
          and engine.kinds == ("full", "window") and len(engine._pools) == 4,
          "window: the engine took another family or geometry")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    streams, snap, took = _serve(engine, prompts, steps)
    out = {"requests": len(prompts), "steady_s": round(took, 2),
           "completed": snap["completed"],
           "compile_count": snap["compile_count"],
           "window_pages_released": engine.window_pages_released,
           "moe_assignments": snap["moe_assignments"],
           "moe_experts_touched": snap["moe_experts_touched"],
           "experts_form": _experts_form(slots, cfg, serve_dtype)}
    check(snap["completed"] == len(prompts), f"window: {snap['completed']} "
                                             f"of {len(prompts)} completed")
    # one step and one chunk program, whatever the lengths
    check(snap["compile_count"] == 2,
          f"window: compile_count {snap['compile_count']}, expected 2")
    check(out["experts_form"] == "kernel" or jax.default_backend() != "tpu",
          f"window: the step's expert layers take the form "
          f"{out['experts_form']!r} on a TPU")
    check(all(p.used_pages == 0 for p in engine.pools_by_kind.values()),
          "window: pages held after close")
    check(engine.window_pages_released > 0,
          "window: no page was given back behind the window")
    # every prompt token and every decoded row, top-k experts in every
    # layer: nothing dropped
    rows = sum(len(p) + steps - 1 for p in prompts)
    per_row = cfg.num_experts_per_tok * cfg.num_hidden_layers
    check(snap["moe_assignments"] == rows * per_row,
          f"window: {snap['moe_assignments']} assignments for {rows} rows")
    gaps = _reference_gaps("window", reference, key, sizes,
                           list(zip(prompts, streams)), steps,
                           cfg.max_position_embeddings)
    out["served_gap_max"] = float(max(g.max() for g in gaps))
    return out


# the fourth family's smoke widths: an attention line of 128 and an inner
# width of 512, whole lanes, so that on the chip the step's attention, the
# step's state update and the launch's scan run as kernels; at a width of
# 256 the weights' std is 0.06 (0.15 at the rehearsal's 32: logits of size 1)
# Its logits are of size 1, ten times the other legs' (which keep the
# benchmark's std of 0.02 at widths of 32: logits of size 0.1), and bfloat16
# moves them as much more. Measured on the v5e (PR 33): the largest gap of
# 330 served tokens 0.049; the cell itself, at the same logit size, 0.11-0.16
# over 8,000 tokens a run. A token altered or a state not reset reads gaps
# of order 1.
STATE_NEAR_TIE_GAP = 0.15
STATE_WIDTHS = {"hidden_size": 256, "num_attention_heads": 2,
                "intermediate_size": 256, "mamba_dt_rank": 16,
                "weight_std": 0.06, "max_position_embeddings": 128}


def state_serving_leg(serve_dtype: str = "bfloat16", slots: int = 8,
                      steps: int = 30,
                      lengths=(37, 7, 20, 9, 45, 14, 3, 26, 11, 33),
                      seed: int = 33) -> dict:
    """The fourth model family through the same engine and scheduler: the
    Jamba-shaped block (six state-space layers and two multi-query attention
    layers, dense gated MLPs, a tied head) at the benchmark configuration's
    rehearsal depth, seeded weights, against the plain reference
    (``benchmark/references/jamba_lm.py``, float32 at ``highest``),
    teacher-forced on what was served. First one sequence by hand: preempted
    mid-decode, another served in its slot, restored, continued; then ten
    requests over eight slots through the scheduler, so that a slot starts a
    second sequence over the first one's state."""
    import numpy as np

    import jax.numpy as jnp

    from benchmark.lib import harness
    from benchmark.lib.weights import seed_key
    from benchmark.references import jamba_lm as reference
    from nnstreamer_tpu.models.jamba import JambaConfig
    from nnstreamer_tpu.models.lm_serving import _LMServingEntry

    _, config = harness.find_cell(harness.load_benchmark(),
                                  "jamba2_reasoning_saturated")
    config = {**config, **config["rehearsal"], **STATE_WIDTHS}
    cfg = JambaConfig.from_published(config)
    sizes, key = reference.sizes(config), seed_key(seed)
    params = reference.program_params(key, sizes, jnp.dtype(serve_dtype))

    class Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    geometry = {k: v for k, v in config["engine"].items()
                if k not in ("slots", "pages")}
    engine = Seeded(cfg, serve_dtype=serve_dtype).make_continuous(
        slots=slots, **geometry)
    check(engine.family.name == "jamba" and engine.kinds == ("full",)
          and engine.state_layers == 6 and len(engine._states) == 2,
          "state: the engine took another family or geometry")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    # by hand: preempt mid-decode, other traffic in the slot, restore
    first, other = prompts[0], prompts[1]
    moved = [engine.admit(0, first, steps)]
    for _ in range(9):
        moved.append(int(_step_now(engine)[0]))
    blob = engine.preempt(0)
    engine.admit(0, other, 8)
    for _ in range(5):
        _step_now(engine)
    engine.release(0)
    engine.restore(0, blob)
    for _ in range(steps - 10):
        moved.append(int(_step_now(engine)[0]))
    engine.release(0)
    check(engine.pool.used_pages == 0, "state: pages held after the "
                                       "restored sequence left")
    streams, snap, took = _serve(engine, prompts, steps)
    out = {"requests": len(prompts), "steady_s": round(took, 2),
           "completed": snap["completed"],
           "compile_count": snap["compile_count"],
           "state_bytes": snap["state"]["bytes"],
           "state_slots_live": snap["state_slots_live"],
           "chunk": engine.chunk}
    check(snap["completed"] == len(prompts), f"state: {snap['completed']} "
                                             f"of {len(prompts)} completed")
    # one step and one chunk program, whatever the lengths
    check(snap["compile_count"] == 2,
          f"state: compile_count {snap['compile_count']}, expected 2")
    check(engine.pool.used_pages == 0, "state: pages held after close")
    check(len(prompts) > slots, "state: no slot was reused")
    gaps = _reference_gaps("state", reference, key, sizes,
                           [(first, moved)] + list(zip(prompts, streams)),
                           steps, cfg.max_position_embeddings,
                           tol=STATE_NEAR_TIE_GAP)
    out["restored_gap_max"] = float(gaps[0].max())
    out["served_gap_max"] = float(max(g.max() for g in gaps))
    return out


# -- kernels ------------------------------------------------------------------

def kernels_leg(B: int = 8, H: int = 16, T: int = 2048, D: int = 64,
                interpret: bool = False) -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.pallas_attention import (
        dense_attention,
        flash_attention,
    )
    from nnstreamer_tpu.ops.pallas_decode import (
        cached_decode_attention,
        dense_cached_decode,
    )

    rng = np.random.default_rng(3)
    pos = T - T // 3  # a valid prefix that ends inside a block
    out = {"geometry": [B, H, T, D], "pos": pos, "steady_s": 0.0}
    wrong = []

    def run(name, kernel, oracle, args):
        lowered = jax.jit(kernel).lower(*args)
        if not interpret:
            check("tpu_custom_call" in lowered.as_text(),
                  f"{name}: no tpu_custom_call in the lowered module")
        compiled = lowered.compile()
        t1 = time.monotonic()
        got = np.asarray(compiled(*args).astype(jnp.float32))
        t2 = time.monotonic()
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(oracle)(*(
                jnp.asarray(a, jnp.float32) if a.ndim else a for a in args)))
        err = float(np.abs(got - want).max())
        out[name] = {"max_abs_err": err, "atol": KERNEL_ATOL}
        out["steady_s"] = round(out["steady_s"] + t2 - t1, 3)
        if not (np.isfinite(got).all() and err <= KERNEL_ATOL):
            wrong.append(f"{name}: max |kernel - oracle| {err:.3e} "
                         f"> {KERNEL_ATOL}")

    for dt in (jnp.float32, jnp.bfloat16):
        def arr(*shape):
            return jnp.asarray(rng.standard_normal(shape), dt)

        run(f"cached_decode_attention[{dt.__name__}]",
            lambda q, k, v, p: cached_decode_attention(
                q, k, v, p, block_k=128, interpret=interpret),
            dense_cached_decode,
            (arr(B, H, 1, D), arr(B, H, T, D), arr(B, H, T, D),
             jnp.asarray(pos, jnp.int32)))
        run(f"flash_attention[{dt.__name__}]",
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=interpret),
            dense_attention,
            (arr(1, H, T, D), arr(1, H, T, D), arr(1, H, T, D)))
    check(not wrong, "; ".join(wrong))
    return out


# -- main ---------------------------------------------------------------------

def result_line(summary: dict) -> dict:
    """The last line of stdout: the verdict and the device as jax reports
    it, these keys and no others. Everything else is in the report line."""
    dev = summary["device"]
    return {"ok": bool(summary["ok"]),
            "device": {"platform": str(dev["platform"]),
                       "kind": str(dev["kind"]),
                       "count": int(dev["count"])}}


def main() -> int:
    t_start = time.monotonic()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax selected platform "
              f"{dev.platform!r} ({dev.device_kind}). Nothing was run.",
              file=sys.stderr)
        return 2

    from importlib import metadata

    import jaxlib

    from nnstreamer_tpu import native
    from nnstreamer_tpu.utils.hw_accel import enable_compilation_cache

    if not native.available():
        # g++ is installed: a pure-Python run would be something else
        print("chip_smoke: the native library did not build or load. "
              "Nothing was run.", file=sys.stderr)
        return 1
    cache_dir = enable_compilation_cache()
    summary = {
        "ok": False,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": metadata.version("libtpu")},
        "compile_cache_dir": cache_dir,
        "native_available": True,
        "legs": {},
    }
    legs = {"kernels": kernels_leg, "stream": stream_leg,
            "serving": serving_leg, "latent_serving": latent_serving_leg,
            "window_serving": window_serving_leg,
            "state_serving": state_serving_leg}
    for name, leg in legs.items():
        t0, before = time.monotonic(), compile_clock()
        try:
            res = leg()
            res["passed"] = True
        except Exception as e:  # noqa: BLE001 — recorded, exit code 1 below
            traceback.print_exc()
            res = {"passed": False, "error": f"{type(e).__name__}: {e}"[:500]}
        after = compile_clock()
        res.update({k: round(after[k] - before[k], 2) for k in after})
        res["wall_s"] = round(time.monotonic() - t0, 2)
        summary["legs"][name] = res
        print(f"chip_smoke: {name}: "
              f"{'passed' if res['passed'] else 'FAILED'} "
              f"in {res['wall_s']} s", file=sys.stderr)
    summary["ok"] = all(leg["passed"] for leg in summary["legs"].values())
    summary["compile_s"] = round(compile_clock()["compile_s"], 2)
    summary["wall_s"] = round(time.monotonic() - t_start, 2)
    print(json.dumps({"report": summary}))
    print(json.dumps(result_line(summary)), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
