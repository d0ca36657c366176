"""Driver for configurations of kind ``lm_serving_looped``: an Ouro-shaped
model (one stack of layers that every token runs ``total_ut_steps`` times,
a cache line for every pass of every layer, an exit gate that says which
pass's hidden state the head reads) behind the same paged
continuous-batching engine and ``DecodeScheduler`` as ``lm_serving``, under
the same request traffic.

Everything between the scheduler and the clock is ``drivers/lm_serving.py``'s
(the proxy, the warm-up, the drive loop, the window's samples, the check
against the plain reference, when a closed loop's file ran out); this file
has its own ``build`` (the entry takes the family's configuration type,
weights come layer by layer) and ``run`` (the other drivers' close over
their own ``build``), and hands the readers what the loop adds:

* ``op_scopes``: per program, device operation → the ``jax.named_scope``
  region it was traced under (``attn.full``, ``mlp``, ``loop.exit``,
  ``head``), from the compiled programs' ``op_name`` metadata, keyed as
  ``lib/xplane.py`` keys a trace's operations; the loop's body is a
  computation of the same module, so its operations are found as any
  other's. Taken in traced runs only (set-up time: one cache load each);
* ``exit_passes``: what the engine's steps counted inside the window, the
  live slots whose logits came from pass 1, 2, ... (``layer_counts["step"]``
  at the window's end less its opening);
* ``ramp_s``: from the first ramp request sent to the window's opening.

The check's padded shape is that of the requests that finished (answers the
window's end cuts are not compared). Nothing here raises where the program
has no loop: the facts are then absent and the readers return ``None``.
"""
from __future__ import annotations

import gc
import re

from benchmark.drivers.lm_serving import (
    EngineProxy,
    check,
    drive,
    ran_out_s,
    warm,
    window_samples,
)
from benchmark.lib import harness, stats, traffic, weights
from benchmark.lib.xplane import op_label

SCOPES = ("attn.full", "mlp", "loop.exit", "head")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"(?:calls|condition|body|to_apply)=%[\w.\-]+")
# what a scope is not inherited through: a loop's carry, a result
_OPAQUE = frozenset({"parameter", "tuple", "while", "conditional", "call",
                     "constant"})
_HOPS = 6


def build(config: dict, seed: int):
    """``(scheduler, proxy, model configuration)`` for a configuration."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models.lm_serving import _LMServingEntry
    from nnstreamer_tpu.models.ouro import OuroConfig
    from nnstreamer_tpu.serving import DecodeScheduler

    reference = harness.reference_for(config)
    mcfg = OuroConfig.from_published(config)
    params = reference.program_params(
        weights.seed_key(seed), reference.sizes(config),
        jnp.dtype(config["serve_dtype"]))

    class _Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    engine = _Seeded(mcfg, serve_dtype=config["serve_dtype"]).make_continuous(
        paged=True, **config["engine"])
    proxy = EngineProxy(engine)
    sched = DecodeScheduler(proxy, name="benchmark",
                            max_depth=config.get("queue_depth", 4096),
                            predictive_shed=False)
    return sched, proxy, mcfg


def scope_of(op_name: str):
    """``jit(_step)/jit(main)/while/body/loop.exit/mul`` → ``loop.exit``."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return None


def scopes_in(hlo_text: str) -> dict:
    """Operation (as ``lib/xplane.op_label`` keys it) → scope, for the
    instructions of an optimized HLO module.

    An instruction that carries a scope in its ``op_name`` has that one.
    The TPU compiler streams this model's weights into fast memory ahead of
    the products that read them (``slice-start`` / ``slice-done``,
    ``copy-start`` / ``copy-done`` pairs, bitcasts and converts between
    them), and those instructions carry no ``op_name``: on the device a
    product is then short and the wait for its weights (the ``-done``) is
    where the time goes. So an instruction without a scope takes the scope
    of the first instruction that reads its result and has one, through at
    most ``_HOPS`` such instructions; what feeds a loop's carry or a
    computation's result (the transposes hoisted out of the loop) stays
    without."""
    own, users, text_of = {}, {}, {}
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name, opcode, rest = found.groups()
        text = line.strip()
        text_of[name] = (text[5:] if text.startswith("ROOT ") else text,
                         opcode)
        named = _OP_NAME.search(line)
        own[name] = scope_of(named.group(1)) if named else None
        for operand in _OPERAND.findall(_CALLED.sub("", rest)):
            users.setdefault(operand, []).append(name)

    def resolve(name, hops):
        if own.get(name) is not None:
            return own[name]
        if hops == 0 or text_of[name][1] in _OPAQUE:
            return None
        for user in users.get(name, ()):
            if user in text_of:
                scope = resolve(user, hops - 1)
                if scope is not None:
                    return scope
        return None

    out = {}
    for name, (text, _opcode) in text_of.items():
        scope = resolve(name, _HOPS)
        if scope is not None:
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
    scalar = jax.ShapeDtypeStruct((), i32)
    args = {
        "_step": (jax.ShapeDtypeStruct((S, 1), i32),
                  jax.ShapeDtypeStruct((S,), i32),
                  jax.ShapeDtypeStruct((S,), jnp.bool_),
                  *[jax.ShapeDtypeStruct((S, NB), i32)] * K, *pools),
        "_prefill_chunk": (jax.ShapeDtypeStruct((C,), i32), scalar, scalar,
                           *[jax.ShapeDtypeStruct((NB,), i32)] * K, *pools),
    }
    out = {}
    for name in programs:
        if name in args:
            text = getattr(engine, name).func.lower(
                params, *args[name]).compile().as_text()
            out[name] = scopes_in(text)
    return out


def exit_counts(engine) -> dict:
    """What the engine's steps have counted so far under the names
    ``exit_pass_<t>``; empty for a program without them."""
    counts = getattr(engine, "layer_counts", {}).get("step", {})
    return {k: v for k, v in counts.items() if k.startswith("exit_pass_")}


def run(ctx) -> dict:
    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    clock, tracer = ctx["clock"], ctx["tracer"]
    sched, proxy, mcfg = build(config, seed)
    opened = {}

    def on_open():
        opened.update(clock=clock.read(), snap=sched.metrics_snapshot(),
                      exits=exit_counts(proxy._engine))

    try:
        warm(sched, proxy, config, mcfg.vocab)
        scopes = (op_scopes(proxy._engine, config["programs"].values())
                  if tracer is not None else None)
        items = traffic.requests(mix, seed, ctx["seconds"], mcfg.vocab)
        t0, cutoff, records = drive(sched, proxy, items, ctx["seconds"],
                                    tracer, on_open)
        snap1 = sched.metrics_snapshot()
        exits1 = exit_counts(proxy._engine)
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
    ticks = [t for t in proxy.ticks if t0 <= t <= cutoff]
    first_tokens = sum(1 for r in records
                       if r["token_t"] and t0 <= r["token_t"][0] <= cutoff)
    ramp_sent = [r["sent_t"] for r in records if r["ramp"]]

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
        "exit_passes": {k: v - opened["exits"].get(k, 0)
                        for k, v in exits1.items()},
        "op_scopes": scopes,
        "ttft_ms": ttft, "tpot_ms": tpot, "out_tokens": out_tokens,
    }
    sched = proxy = None
    gc.collect()
    # the reference's padded shape: what finished, not what the window cut
    lengths = [(r["prompt_len"], r["steps"]) for r in records
               if r["request"].done() and r["request"].error is None]
    correct, checks = check(ctx, records, lengths, mcfg)
    checks += [("pages_left", left, 0),
               ("prefill_launches_in_window", len(ticks), None)]
    # what the scheduler did to requests besides serving them, in the window
    checks += [(name, snap1[name] - snap0[name], None) for name in (
        "preempted", "shed_queue_full", "shed_memory", "shed_overload")]
    # a closed loop's list is meant to outlast the window: the second its
    # last request was sent, or null (recorded among the checks: the result
    # line's own key for it is one cell's, by a test of the benchmark)
    checks.append(("traffic_ran_out_s", ran_out_s(items, records, t0), None))
    if not records or not tpot or left:
        correct = False
    end_to_end = {"setup_s": setup_s}
    if ttft:
        end_to_end["ttft_p50_ms"] = stats.median(ttft)
    if tpot:
        end_to_end["tpot_p50_ms"] = stats.median(tpot)
    return {"correct": correct and failed == 0, "attempted": len(records),
            "failed": failed, "memory_peak_bytes": peak, "checks": checks,
            "end_to_end": end_to_end, "facts": facts}
