"""Driver for configurations of kind ``lm_serving_moe_cca``: a ZAYA-shaped
model (attention in a compressed latent whose projection keeps a state a
slot beside the lines a token keeps, a top-1 expert layer behind an MLP
router whose activations go down the stack, a tied head) behind the same
paged continuous-batching engine and ``DecodeScheduler`` as ``lm_serving``,
under the same request traffic.

Everything between the scheduler and the clock is ``drivers/lm_serving.py``'s
(the warm-up, the drive loop, the window's samples, the check against the
plain reference) and the expert layers' proxy is ``lm_serving_moe_mla``'s;
this file replaces ``build`` (the entry takes the family's configuration
type, weights come layer by layer, the engine keeps a state a slot in every
attention layer beside its pages) and hands the readers what the new layers
add:

* ``moe_steps``: per decode step of the window, what the program's expert
  layers counted (``PagedLMEngine.layer_counts``): experts reached, expert
  slots, assignments, the largest load;
* ``op_scopes``: per program, device operation → the label of the
  ``jax.named_scope`` regions it was traced under, from the compiled
  programs' ``op_name`` metadata, keyed as ``lib/xplane.py`` keys a trace's
  operations. The engine's ``attn.full`` surrounds a whole attention part,
  so an operation inside it keeps both names (``attn.full.cca.in``,
  ``attn.full.cca.mix``, ``attn.full.cca.out``, ``attn.full.merge``; the
  paged kernel's calls ``attn.full.kernel``; the lines' write and the
  queries' layout ``attn.full`` alone): ``attn.full`` finds the whole part.
  Outside it ``moe.router``, ``moe.experts`` (its kernel's calls
  ``moe.experts.kernel``), ``merge`` (the expert part's) and ``head``.
  Taken in traced runs only (set-up time: one cache load each);
* ``state``: the engine's ``state_stats()`` as the window closes, and
  ``ramp_s``: from the first ramp request sent to the window's opening.

The check's padded shape is that of the requests that finished (the cell's
long answers are cut by the window's end and are not compared). Nothing
here raises where the program lacks a scope or a counter: the fact is then
absent and the reader returns ``None``.
"""
from __future__ import annotations

import gc
import re

from benchmark.drivers.lm_serving import check, drive, warm, window_samples
from benchmark.drivers.lm_serving_moe_mla import MoEProxy
from benchmark.lib import harness, stats, traffic, weights
from benchmark.lib.xplane import op_label

OUTER = "attn.full"
INNER = ("cca.in", "cca.mix", "cca.out", "merge")
KERNELS = {"paged_line_attention": "attn.full.kernel",
           "grouped_experts": "moe.experts.kernel"}
ALONE = ("moe.router", "moe.experts", "merge", "head")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def build(config: dict, seed: int):
    """``(scheduler, proxy, model configuration)`` for a configuration."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models.lm_serving import _LMServingEntry
    from nnstreamer_tpu.models.zaya import ZayaConfig
    from nnstreamer_tpu.serving import DecodeScheduler

    reference = harness.reference_for(config)
    mcfg = ZayaConfig.from_published(config)
    params = reference.program_params(
        weights.seed_key(seed), reference.sizes(config),
        jnp.dtype(config["serve_dtype"]))

    class _Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    engine = _Seeded(mcfg, serve_dtype=config["serve_dtype"]).make_continuous(
        paged=True, **config["engine"])
    proxy = MoEProxy(engine)
    sched = DecodeScheduler(proxy, name="benchmark",
                            max_depth=config.get("queue_depth", 4096),
                            predictive_shed=False)
    return sched, proxy, mcfg


def scope_of(op_name: str):
    """``jit(_step)/attn.full/cca.mix/mul`` → ``attn.full.cca.mix``;
    ``jit(_step)/moe.experts/jit(_call)/grouped_experts/pallas_call`` →
    ``moe.experts.kernel``; ``jit(_step)/merge/add`` → ``merge``."""
    parts = op_name.split("/")
    for part in parts:
        if part in KERNELS:
            return KERNELS[part]
    if OUTER in parts:
        inner = next((p for p in parts if p in INNER), None)
        return OUTER if inner is None else f"{OUTER}.{inner}"
    return next((p for p in parts if p in ALONE), None)


def scopes_in(hlo_text: str) -> dict:
    """Operation (as ``lib/xplane.op_label`` keys it) → label, for the
    instructions of an optimized HLO module that carry one."""
    out = {}
    for line in hlo_text.splitlines():
        found = _OP_NAME.search(line)
        scope = scope_of(found.group(1)) if found else None
        if scope is not None and " = " in line:
            text = line.strip()
            if text.startswith("ROOT "):
                text = text[5:]
            out[op_label(text)] = scope
    return out


def op_scopes(engine, programs) -> dict:
    """``{program: {operation: label}}`` of the engine's two programs, from
    their compiled text (the same lowering as the calls that ran: the
    executables come from the compile cache)."""
    import jax
    import jax.numpy as jnp

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    S, NB, C = engine.slots, engine.blocks_per_slot, engine.chunk
    K = len(engine.kinds)
    params = jax.tree_util.tree_map(like, engine.params)
    pools = [like(p) for p in engine._pools]
    states = [like(s) for s in engine._states]
    i32 = jnp.int32
    scalar = jax.ShapeDtypeStruct((), i32)
    args = {
        "_step": (jax.ShapeDtypeStruct((S, 1), i32),
                  jax.ShapeDtypeStruct((S,), i32),
                  jax.ShapeDtypeStruct((S,), jnp.bool_),
                  *[jax.ShapeDtypeStruct((S, NB), i32)] * K,
                  *pools, *states, jax.ShapeDtypeStruct((S,), i32)),
        "_prefill_chunk": (jax.ShapeDtypeStruct((C,), i32), scalar, scalar,
                           *[jax.ShapeDtypeStruct((NB,), i32)] * K,
                           *pools, scalar, *states),
    }
    out = {}
    for name in programs:
        if name in args:
            text = getattr(engine, name).func.lower(
                params, *args[name]).compile().as_text()
            out[name] = scopes_in(text)
    return out


def run(ctx) -> dict:
    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    clock, tracer = ctx["clock"], ctx["tracer"]
    sched, proxy, mcfg = build(config, seed)
    opened = {}

    def on_open():
        opened.update(clock=clock.read(), snap=sched.metrics_snapshot())

    try:
        warm(sched, proxy, config, mcfg.vocab)
        proxy.moe_steps.clear()
        scopes = (op_scopes(proxy._engine, config["programs"].values())
                  if tracer is not None else None)
        items = traffic.requests(mix, seed, ctx["seconds"], mcfg.vocab)
        t0, cutoff, records = drive(sched, proxy, items, ctx["seconds"],
                                    tracer, on_open)
        snap1 = sched.metrics_snapshot()
        after = clock.read()
        if tracer is not None:
            tracer.stop()
    finally:
        sched.close()
    before, snap0 = opened["clock"], opened["snap"]
    setup_s = t0 - ctx["t_start"]  # a ramp is set-up
    peak = harness.memory_peak_bytes()
    geometry = config["engine"]
    left = sched.metrics_snapshot()["kv_pool"]["pages_used"]

    from nnstreamer_tpu.serving.request import SchedulerClosedError

    # closing the scheduler at the window's end cuts what is in flight:
    # that is not a failure, anything else that ended a request is
    failed = sum(1 for r in records
                 if r["request"].done() and r["request"].error is not None
                 and not isinstance(r["request"].error, SchedulerClosedError))
    ttft, tpot, out_tokens = window_samples(records, t0, cutoff)
    steps = [s for s in proxy.steps if t0 <= s[0] <= cutoff]
    moe_steps = [m for m in proxy.moe_steps if t0 <= m[0] <= cutoff]
    ticks = [t for t in proxy.ticks if t0 <= t <= cutoff]
    first_tokens = sum(1 for r in records
                       if r["token_t"] and t0 <= r["token_t"][0] <= cutoff)
    ramp_sent = [r["sent_t"] for r in records if r["ramp"]]
    # the most pages at any step of the run, ramp included
    pages_peak = max((s[3] for s in proxy.steps), default=0)

    def rows(snap):  # real and padded rows from the program's own counters
        padded = snap["decode_steps"] * snap["slots"]
        return snap["batch_occupancy"] * padded, padded

    facts = {
        "window_s": cutoff - t0,
        "trace_bounds": tracer.bounds if tracer else None,
        "config": config, "mix": mix,
        "setup_compile_s": before["compile_s"],
        "compiles_in_window": after["compiles"] - before["compiles"],
        "ramp_s": t0 - min(ramp_sent) if ramp_sent else None,
        "gen_late_ms": [(r["sent_t"] - r["due_t"]) * 1e3
                        for r in records if r["due_t"] >= t0],
        "queue_wait_ms": [r["request"].metrics["queue_wait_s"] * 1e3
                          for r in records if r["due_t"] >= t0
                          and "queue_wait_s" in r["request"].metrics],
        "batch_rows": (rows(snap1)[0] - rows(snap0)[0],
                       rows(snap1)[1] - rows(snap0)[1]),
        "prefill_chunks": len(ticks), "first_tokens": first_tokens,
        "pool_pages_used_peak": max((s[3] for s in steps), default=0),
        "pool_pages": geometry["pages"],
        "pool_tokens": geometry["pages"] * geometry["page_size"],
        "decode_steps": steps,
        "moe_steps": moe_steps,
        "moe_expert_slots": proxy._engine.family.expert_slots,
        "state": snap1.get("state"),
        "op_scopes": scopes,
        "ttft_ms": ttft, "tpot_ms": tpot, "out_tokens": out_tokens,
    }
    state = snap1.get("state") or {}
    shed = [(name, snap1[name] - snap0[name], 0) for name in (
        "preempted", "shed_queue_full", "shed_memory", "shed_overload")]
    sched = proxy = None
    gc.collect()
    # the reference's padded shape: what finished, not what the window cut
    lengths = [(r["prompt_len"], r["steps"]) for r in records
               if r["request"].done() and r["request"].error is None]
    correct, checks = check(ctx, records, lengths, mcfg)
    checks += [("pages_left", left, 0),
               ("pages_peak", pages_peak, geometry["pages"])]
    checks += shed  # nothing preempted or refused inside the window
    checks += [("state_bytes", state.get("bytes"), None),
               ("prefill_launches_in_window", len(ticks), None)]
    if (not records or not tpot or left or any(n for _, n, _ in shed)
            or pages_peak > geometry["pages"]):
        correct = False
    end_to_end = {"setup_s": setup_s}
    if ttft:
        end_to_end["ttft_p50_ms"] = stats.median(ttft)
    if tpot:
        end_to_end["tpot_p50_ms"] = stats.median(tpot)
    return {"correct": correct and failed == 0, "attempted": len(records),
            "failed": failed, "memory_peak_bytes": peak, "checks": checks,
            "end_to_end": end_to_end, "facts": facts}
