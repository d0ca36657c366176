"""Driver for configurations of kind ``lm_serving_moe_mla``: a
DeepSeek-V3-shaped model (latent attention, dropless experts with shared
ones) behind the same paged continuous-batching engine and
``DecodeScheduler`` as ``lm_serving``, under the same request traffic.

Everything between the scheduler and the clock is ``drivers/lm_serving.py``'s
(the proxy, the warm-up, the drive loop, the window's samples, the check
against the plain reference); this file replaces ``build`` (the entry takes
the family's configuration type, weights come layer by layer) and hands the
readers what the new layers add:

* ``moe_steps``: per decode step of the window, what the program's expert
  layers counted (``PagedLMEngine.layer_counts``): experts reached, expert
  slots, assignments, the largest load;
* ``op_scopes``: per program, device operation → the ``jax.named_scope``
  region it was traced under (``mla``, ``moe.route``, ``moe.experts``,
  ``moe.shared``, ``mlp``, ``head``), from the compiled programs'
  ``op_name`` metadata, keyed as ``lib/xplane.py`` keys a trace's
  operations. Taken in traced runs only (set-up time: one cache load each).
"""
from __future__ import annotations

import gc
import re

from benchmark.drivers.lm_serving import (
    EngineProxy,
    check,
    drive,
    warm,
    window_samples,
)
from benchmark.lib import harness, stats, traffic, weights
from benchmark.lib.xplane import op_label

SCOPES = ("mla", "moe.route", "moe.experts", "moe.shared", "mlp", "head")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class MoEProxy(EngineProxy):
    """The proxy, and beside every decode step what the expert layers
    counted in it."""

    def __init__(self, engine):
        super().__init__(engine)
        self.moe_steps = []  # (end time, {counter: value of this step})

    def step(self):
        before = dict(self._engine.layer_counts["step"])
        out = super().step()
        after = self._engine.layer_counts["step"]
        self.moe_steps.append(
            (self.steps[-1][0], {k: after[k] - before[k] for k in after}))
        return out


def build(config: dict, seed: int):
    """``(scheduler, proxy, model configuration)`` for a configuration."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models.deepseek_v3 import DeepseekV3Config
    from nnstreamer_tpu.models.lm_serving import _LMServingEntry
    from nnstreamer_tpu.serving import DecodeScheduler

    reference = harness.reference_for(config)
    mcfg = DeepseekV3Config.from_published(config)
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
    """``jit(_step)/jit(main)/moe.experts/mul`` → ``moe.experts``."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    # the TPU compiler's grouped-product kernels lose their op_name
    # (``ragged-dot-none``): only the routed experts issue them
    return "moe.experts" if op_name.startswith("ragged-dot") else None


def scopes_in(hlo_text: str) -> dict:
    """Operation (as ``lib/xplane.op_label`` keys it) → scope, for the
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
    """``{program: {operation: scope}}`` of the engine's two programs, from
    their compiled text (the same lowering as the calls that ran: the
    executables come from the compile cache)."""
    import jax
    import jax.numpy as jnp

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    S, NB, C = engine.slots, engine.blocks_per_slot, engine.chunk
    params = jax.tree_util.tree_map(like, engine.params)
    pools = [like(p) for p in engine._pools]
    i32 = jnp.int32
    args = {
        "_step": (jax.ShapeDtypeStruct((S, 1), i32),
                  jax.ShapeDtypeStruct((S,), i32),
                  jax.ShapeDtypeStruct((S,), jnp.bool_),
                  jax.ShapeDtypeStruct((S, NB), i32)),
        "_prefill_chunk": (jax.ShapeDtypeStruct((C,), i32),
                           jax.ShapeDtypeStruct((), i32),
                           jax.ShapeDtypeStruct((), i32),
                           jax.ShapeDtypeStruct((NB,), i32)),
    }
    out = {}
    for name in programs:
        if name in args:
            text = getattr(engine, name).func.lower(
                params, *args[name], *pools).compile().as_text()
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

    from nnstreamer_tpu.serving.request import SchedulerClosedError

    # closing the scheduler at the window's end cuts what is in flight:
    # that is not a failure, anything else that ended a request is
    failed = sum(1 for r in records
                 if r["request"].done() and r["request"].error is not None
                 and not isinstance(r["request"].error, SchedulerClosedError))
    ttft, tpot, out_tokens = window_samples(records, t0, cutoff)
    steps = [s for s in proxy.steps if t0 <= s[0] <= cutoff]
    ticks = [t for t in proxy.ticks if t0 <= t <= cutoff]
    first_tokens = sum(1 for r in records
                       if r["token_t"] and t0 <= r["token_t"][0] <= cutoff)

    def rows(snap):  # real and padded rows from the program's own counters
        padded = snap["decode_steps"] * snap["slots"]
        return snap["batch_occupancy"] * padded, padded

    facts = {
        "window_s": cutoff - t0,
        "trace_bounds": tracer.bounds if tracer else None,
        "config": config, "mix": mix,
        "setup_compile_s": before["compile_s"],
        "compiles_in_window": after["compiles"] - before["compiles"],
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
        "moe_steps": [m for m in proxy.moe_steps if t0 <= m[0] <= cutoff],
        "moe_expert_slots": proxy._engine.family.expert_slots,
        "op_scopes": scopes,
        "ttft_ms": ttft, "tpot_ms": tpot, "out_tokens": out_tokens,
    }
    sched = proxy = None
    gc.collect()
    lengths = [(int(it["prompt"].size), it["steps"]) for it in items]
    correct, checks = check(ctx, records, lengths, mcfg)
    if not records or not tpot:
        correct = False
    end_to_end = {"setup_s": setup_s}
    if ttft:
        end_to_end["ttft_p50_ms"] = stats.median(ttft)
    if tpot:
        end_to_end["tpot_p50_ms"] = stats.median(tpot)
    return {"correct": correct and failed == 0, "attempted": len(records),
            "failed": failed, "memory_peak_bytes": peak, "checks": checks,
            "end_to_end": end_to_end, "facts": facts}
