"""Driver for configurations of kind ``lm_serving_moe_window``: a
Mellum-shaped model (grouped-query attention, window layers beside full
ones, a dropless softmax top-k expert layer in every block) behind the same
paged continuous-batching engine and ``DecodeScheduler`` as ``lm_serving``,
under the same request traffic.

Everything between the scheduler and the clock is ``drivers/lm_serving.py``'s
(the proxy, the warm-up, the drive loop, the window's samples, the check
against the plain reference); this file replaces ``build`` (the entry takes
the family's configuration type, weights come layer by layer, the engine
keeps pages by layer kind) and hands the readers what the new layers add:

* ``moe_steps``: per decode step of the window, what the program's expert
  layers counted (``PagedLMEngine.layer_counts``) and, beside them, the
  visible tokens of the live sequences in a window layer (``ctx_window``:
  at most the window each) and the pages in use of each kind;
* ``op_scopes``: per program, device operation → the ``jax.named_scope``
  region it was traced under (``attn.window``, ``attn.full``, ``moe.route``,
  ``moe.experts``, ``head``), from the compiled programs' ``op_name``
  metadata, keyed as ``lib/xplane.py`` keys a trace's operations. Taken in
  traced runs only (set-up time: one cache load each);
* ``pool_pages_by_kind`` and their peaks, and ``ramp_s``: from the first
  ramp request sent to the window's opening.

The check's padded shape is that of the requests that finished (the cell's
long answers are cut by the window's end and are not compared), so the
reference computes 8832 positions a sequence and not the limit.
"""
from __future__ import annotations

import gc
import re

from benchmark.drivers.lm_serving import check, drive, warm, window_samples
from benchmark.drivers.lm_serving_moe_mla import MoEProxy
from benchmark.lib import harness, stats, traffic, weights
from benchmark.lib.xplane import op_label

SCOPES = ("attn.window", "attn.full", "moe.route", "moe.experts", "head")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class WindowProxy(MoEProxy):
    """The expert layers' proxy, and beside every decode step what the
    window layers saw and the pages each kind held."""

    def step(self):
        window = self._engine.family.window
        # what this step attends to, before its token is noted
        seen = sum(min(r["prompt_len"] + len(r["token_t"]), window)
                   for r in self._active.values())
        out = super().step()
        self.moe_steps[-1][1].update(
            ctx_window=seen,
            **{f"pages_{kind}": pool.used_pages
               for kind, pool in self._engine.pools_by_kind.items()})
        return out


def build(config: dict, seed: int):
    """``(scheduler, proxy, model configuration)`` for a configuration."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models.lm_serving import _LMServingEntry
    from nnstreamer_tpu.models.mellum import MellumConfig
    from nnstreamer_tpu.serving import DecodeScheduler

    reference = harness.reference_for(config)
    mcfg = MellumConfig.from_published(config)
    params = reference.program_params(
        weights.seed_key(seed), reference.sizes(config),
        jnp.dtype(config["serve_dtype"]))

    class _Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    engine = _Seeded(mcfg, serve_dtype=config["serve_dtype"]).make_continuous(
        paged=True, **config["engine"])
    proxy = WindowProxy(engine)
    sched = DecodeScheduler(proxy, name="benchmark",
                            max_depth=config.get("queue_depth", 4096),
                            predictive_shed=False)
    return sched, proxy, mcfg


def scope_of(op_name: str):
    """``jit(_step)/jit(main)/attn.window/mul`` → ``attn.window``."""
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
    K = len(engine.kinds)
    params = jax.tree_util.tree_map(like, engine.params)
    pools = [like(p) for p in engine._pools]
    i32 = jnp.int32
    args = {
        "_step": (jax.ShapeDtypeStruct((S, 1), i32),
                  jax.ShapeDtypeStruct((S,), i32),
                  jax.ShapeDtypeStruct((S,), jnp.bool_),
                  *[jax.ShapeDtypeStruct((S, NB), i32)] * K),
        "_prefill_chunk": (jax.ShapeDtypeStruct((C,), i32),
                           jax.ShapeDtypeStruct((), i32),
                           jax.ShapeDtypeStruct((), i32),
                           *[jax.ShapeDtypeStruct((NB,), i32)] * K),
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
    bound = proxy._engine.held_blocks["window"]
    left = {kind: s["pages_used"]  # after close: every page given back
            for kind, s in sched.metrics_snapshot()["kv_pools"].items()}

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
    # the most pages of each kind at any step of the run, ramp included
    peaks = {kind: max((c[f"pages_{kind}"] for _, c in proxy.moe_steps),
                       default=0) for kind in geometry["pages"]}

    def rows(snap):  # real and padded rows from the program's own counters
        padded = snap["decode_steps"] * snap["slots"]
        return snap["batch_occupancy"] * padded, padded

    pages = geometry["pages"]
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
        # the pool the older readers know is the full kind's: the one that
        # grows with the contexts
        "pool_pages_used_peak": max((s[3] for s in steps), default=0),
        "pool_pages": pages["full"],
        "pool_tokens": pages["full"] * geometry["page_size"],
        "pool_pages_by_kind": pages,
        "pool_pages_used_peak_by_kind": {
            kind: max((c[f"pages_{kind}"] for _, c in moe_steps), default=0)
            for kind in pages},
        "pool_pages_left_by_kind": left,
        "decode_steps": steps,
        "moe_steps": moe_steps,
        "moe_expert_slots": proxy._engine.family.expert_slots,
        "op_scopes": scopes,
        "ttft_ms": ttft, "tpot_ms": tpot, "out_tokens": out_tokens,
    }
    sched = proxy = None
    gc.collect()
    # the reference's padded shape: what finished, not what the window cut
    lengths = [(r["prompt_len"], r["steps"]) for r in records
               if r["request"].done() and r["request"].error is None]
    correct, checks = check(ctx, records, lengths, mcfg)
    # a slot holds at most ceil((window + width) / page) + 1 window pages
    held = {"full": pages["full"],
            "window": min(pages["window"], geometry["slots"] * bound)}
    checks += [(f"pages_left_{kind}", n, 0) for kind, n in left.items()]
    checks += [(f"pages_peak_{kind}", peaks[kind], held[kind])
               for kind in pages]
    checks.append(("prefill_launches_in_window", len(ticks), None))
    if (not records or not tpot or any(left.values())
            or any(peaks[kind] > held[kind] for kind in pages)):
        correct = False
    end_to_end = {"setup_s": setup_s}
    if ttft:
        end_to_end["ttft_p50_ms"] = stats.median(ttft)
    if tpot:
        end_to_end["tpot_p50_ms"] = stats.median(tpot)
    return {"correct": correct and failed == 0, "attempted": len(records),
            "failed": failed, "memory_peak_bytes": peak, "checks": checks,
            "end_to_end": end_to_end, "facts": facts}
