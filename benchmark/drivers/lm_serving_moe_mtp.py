"""Driver for configurations of kind ``lm_serving_moe_mtp``: an
EXAONE-MoE-shaped model (grouped-query attention with q/k norms, window
layers beside full ones, a dense block before dropless sigmoid top-k expert
layers with a shared expert, a chip's share of the experts and of the
vocabulary, an MTP layer that drafts) behind the same paged
continuous-batching engine and ``DecodeScheduler`` as ``lm_serving``, under
the same request traffic.

The engine is its own burst engine here: a pass is a *round*
(``PagedLMEngine._round``: the draft verified over two positions a slot and
the next one drafted, one program), the scheduler calls ``step_tokens`` and a
slot gets 1 or 2 tokens a pass. Everything between the scheduler and the
clock is ``drivers/lm_serving.py``'s (the warm-up, the drive loop, the
window's samples, the check against the plain reference); this file
replaces ``build``, puts ``step_tokens`` into the proxy, and hands the
readers what the new layers add:

* ``moe_steps``: per round of the window, what the program's expert layers
  counted (``PagedLMEngine.layer_counts``: the stack's sparse layers and
  the MTP block's), the visible tokens of the live sequences in a window
  layer (``ctx_window``: both rows', at most the window and one each), the
  pages in use of each kind, and the round's own account (``rows``,
  ``proposed``, ``accepted``, ``emitted``: the engine's ``spec_*`` sums);
* ``op_scopes``: per program, device operation → the ``jax.named_scope``
  region it was traced under. An operation of the MTP block keeps both
  names (``mtp.block.attn.full``, ``mtp.block.moe.experts``), so ``mtp.``
  finds the whole MTP layer and ``attn.`` / ``moe.`` the stack's alone.
  Taken in traced runs only (set-up time: one cache load each);
* the drafts: beside every request's tokens the draft the engine held after
  each pass (``next_draft``), which the check sets against the reference's
  MTP scores as it sets the served tokens against its main scores.
"""
from __future__ import annotations

import gc
import re
import time

import numpy as np

from benchmark.drivers.lm_serving import (
    EngineProxy,
    check,
    drive,
    warm,
    window_samples,
)
from benchmark.lib import harness, stats, traffic, weights
from benchmark.lib.correct import served_gaps
from benchmark.lib.xplane import op_label

OUTER = ("mtp.embed", "mtp.block", "mtp.head")
INNER = ("attn.window", "attn.full", "moe.route", "moe.experts", "moe.shared",
         "mlp", "head")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class RoundProxy(EngineProxy):
    """The proxy of an engine whose pass is a round: ``step_tokens`` in
    place of ``step``, every token of a burst stamped with the pass's end,
    and beside every round what the expert layers counted, what the window
    layers saw, the pages each kind held and what the round accepted."""

    def __init__(self, engine):
        super().__init__(engine)
        self.moe_steps = []  # (end time, {counter: value of this round})

    def prefill_tick(self):
        done = super().prefill_tick()
        for slot, _first in done:
            # the launch left the first draft: of the token after the first
            self._active[slot]["drafts"] = [
                (1, int(self._engine.next_draft[slot]))]
        return done

    def step_tokens(self):
        eng = self._engine
        window = eng.family.window
        # what this round attends to, before its tokens are noted: the
        # first row's context, and one position more for the second
        context = seen = 0
        for r in self._active.values():
            n = r["prompt_len"] + len(r["token_t"])
            context += n + 1
            seen += min(n, window) + 1
        before = dict(eng.layer_counts["step"])
        spec = (eng.spec_proposed, eng.spec_accepted, eng.spec_emitted)
        with self._span("bench:step"):
            bursts = eng.step_tokens()
        now = time.monotonic()
        for slot, record in self._active.items():
            record["token_t"] += [now] * len(bursts[slot])
            if bursts[slot]:
                # the draft the engine holds now is of the token after
                # the tokens this request has so far
                record["drafts"].append((len(record["token_t"]),
                                         int(eng.next_draft[slot])))
        self.steps.append((now, len(self._active), context,
                           eng.pool.used_pages))
        after = eng.layer_counts["step"]
        self.moe_steps.append((now, {
            **{k: after[k] - before[k] for k in after},
            "ctx_window": seen, "rows": 2 * len(self._active),
            "proposed": eng.spec_proposed - spec[0],
            "accepted": eng.spec_accepted - spec[1],
            "emitted": eng.spec_emitted - spec[2],
            **{f"pages_{kind}": pool.used_pages
               for kind, pool in eng.pools_by_kind.items()}}))
        return bursts


def build(config: dict, seed: int):
    """``(scheduler, proxy, model configuration)`` for a configuration."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models.exaone_moe import ExaoneMoeConfig
    from nnstreamer_tpu.models.lm_serving import _LMServingEntry
    from nnstreamer_tpu.serving import DecodeScheduler

    reference = harness.reference_for(config)
    mcfg = ExaoneMoeConfig.from_published(reference.model_config(config))
    params = reference.program_params(
        weights.seed_key(seed), reference.sizes(config),
        jnp.dtype(config["serve_dtype"]))

    class _Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    engine = _Seeded(mcfg, serve_dtype=config["serve_dtype"]).make_continuous(
        paged=True, **config["engine"])
    proxy = RoundProxy(engine)
    sched = DecodeScheduler(proxy, name="benchmark",
                            max_depth=config.get("queue_depth", 4096),
                            predictive_shed=False)
    return sched, proxy, mcfg


def scope_of(op_name: str):
    """``jit(_round)/jit(main)/attn.window/mul`` → ``attn.window``;
    ``.../mtp.block/moe.experts/...`` → ``mtp.block.moe.experts``."""
    parts = op_name.split("/")
    outer = next((p for p in parts if p in OUTER), None)
    inner = next((p for p in parts if p in INNER), None)
    if inner is None and op_name.startswith("ragged-dot"):
        # the TPU compiler's grouped-product kernels lose their op_name
        # (``ragged-dot-none``): only the routed experts issue them
        inner = "moe.experts"
    if outer is None:
        return inner
    return outer if inner is None or outer != "mtp.block" \
        else f"{outer}.{inner}"


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
    scalar = jax.ShapeDtypeStruct((), i32)
    args = {
        "_round": (jax.ShapeDtypeStruct((S, 3), i32),
                   jax.ShapeDtypeStruct((S,), jnp.bool_),
                   *[jax.ShapeDtypeStruct((S, NB), i32)] * K, *pools,
                   jax.ShapeDtypeStruct((S, 3), i32)),
        "_prefill_chunk": (jax.ShapeDtypeStruct((C,), i32), scalar, scalar,
                           *[jax.ShapeDtypeStruct((NB,), i32)] * K, *pools,
                           scalar),
    }
    out = {}
    for name in programs:
        if name in args:
            text = getattr(engine, name).func.lower(
                params, *args[name]).compile().as_text()
            out[name] = scopes_in(text)
    return out


def draft_check(ctx, records: list, lengths: list) -> tuple:
    """The drafts the timed path made, against the reference's MTP scores:
    for a seeded sample of the finished requests (the one ``check`` picks),
    for every draft, how far its reference MTP logit lies below the
    reference MTP's best at that row. The reference is teacher-forced on
    what was served. Returns ``(correct, [(name, value, limit)])``."""
    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    limits = config["check"]
    done = [r for r in records
            if r["request"] is not None and r["request"].done()
            and r["request"].error is None]
    if not done:
        return False, []
    order = np.random.default_rng([int(seed), 9]).permutation(len(done))
    longest = max(range(len(done)), key=lambda i: (
        done[i]["prompt_len"] + done[i]["request"].steps))
    picked = [longest] + [int(i) for i in order if i != longest]
    picked = [done[i] for i in picked[:int(mix["check_sample"])]]
    reference = harness.reference_for(config)
    sizes = reference.sizes(config)
    pad = min(-(-max(p + s for p, s in lengths) // 128) * 128,
              sizes.positions)
    rows_pad = max(s for _, s in lengths)
    tokens = np.zeros((len(picked), pad), np.int32)
    rows = np.zeros((len(picked), rows_pad), np.int32)
    drafts = []
    for i, r in enumerate(picked):
        served = r["request"].result()[0]
        p, n = r["prompt_len"], len(served)
        tokens[i, :p] = r["prompt"]
        tokens[i, p:p + n - 1] = served[:-1]
        # a draft held after ``m`` tokens is of token ``p + m``, made from
        # the MTP row ``p + m - 2`` (the stack's output there and the token
        # after it); drafts of tokens past the request's last are not
        # compared (their row's next token was never served)
        kept = [(m, d) for m, d in r["drafts"] if m < n]
        rows[i, :len(kept)] = [p + m - 2 for m, _ in kept]
        drafts.append(np.asarray([d for _, d in kept], np.int64))
    _, mtp = reference.both_logits_for(
        weights.seed_key(seed), sizes, tokens, rows[:, :1], rows)
    gaps = np.concatenate([
        served_gaps(np.asarray(mtp["none"])[i, :len(d)], d)
        for i, d in enumerate(drafts) if len(d)])
    checks, ok = [], True
    for name, value in (("draft_gap_max", float(gaps.max())),
                        ("draft_gap_mean", float(gaps.mean()))):
        limit = limits[name + "_limit"]
        checks.append((name, value, limit))
        ok = ok and value <= limit
    checks.append(("drafts_compared", int(gaps.size), None))
    return ok, checks


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
    engine = proxy._engine
    bound = engine.held_blocks["window"]
    expert_slots = (engine.family.expert_slots
                    + engine.drafts * engine.family.cfg.held[1])
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
    pages = geometry["pages"]
    # the most pages of each kind at any round of the run, ramp included
    peaks = {kind: max((c[f"pages_{kind}"] for _, c in proxy.moe_steps),
                       default=0) for kind in pages}

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
        "moe_expert_slots": expert_slots,
        "op_scopes": scopes,
        "ttft_ms": ttft, "tpot_ms": tpot, "out_tokens": out_tokens,
    }
    shed = [(name, snap1[name] - snap0[name], 0) for name in (
        "preempted", "shed_queue_full", "shed_memory", "shed_overload")]
    sched = proxy = engine = None
    gc.collect()
    # the reference's padded shape: what finished, not what the window cut
    lengths = [(r["prompt_len"], r["steps"]) for r in records
               if r["request"].done() and r["request"].error is None]
    correct, checks = check(ctx, records, lengths, mcfg)
    drafts_ok, more = draft_check(ctx, records, lengths) if lengths \
        else (False, [])
    checks += more
    # a slot holds at most ceil((window + width) / page) + 1 window pages
    held = {"full": pages["full"],
            "window": min(pages["window"], geometry["slots"] * bound)}
    checks += [(f"pages_left_{kind}", n, 0) for kind, n in left.items()]
    checks += [(f"pages_peak_{kind}", peaks[kind], held[kind])
               for kind in pages]
    checks += shed  # nothing preempted or refused inside the window
    checks.append(("prefill_launches_in_window", len(ticks), None))
    if (not records or not tpot or not drafts_ok or any(left.values())
            or any(n for _, n, _ in shed)
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
