"""Driver for configurations of kind ``lm_serving``: a decoder-only language
model behind the program's paged continuous-batching engine and its
``DecodeScheduler``, under the request traffic a mix's generator gives it
(``lib/traffic.py``): open loop, closed loop, or both.

The system under test is built through the program's normal entry point,
``models.lm_serving._LMServingEntry(...).make_continuous(paged=True, ...)``,
and driven through ``serving.DecodeScheduler.submit``. The benchmark hands it
weights (``references/<reference>.program_params``, one jitted call from the
seed, in the serving type) and stands between the scheduler and the engine
with a thin delegating proxy: that is where the host spans on the trace's
clock, the per-pass counters and the benchmark's own clock for every token
come from. The program's stamps are not read for an end-to-end metric.

Window: a request is sent when it is due and the request it waits for has
finished. Requests marked ``ramp`` are sent before the window opens, which
it does once each has its first token; that time is set-up. Every request
sent is a sample. Time to first token runs from the due time (for a request
that waited for another, from that one's last token); a request whose first
token is not out at the window's end enters with the time it has waited so
far. The gap between tokens of a request is taken over the tokens it emitted
inside the window, so a request that the window's start or end cuts still
counts with what it did inside; nothing outside the window is measured.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import harness, stats, traffic, weights
from benchmark.lib.correct import served_gaps


class EngineProxy:
    """Delegates the scheduler's calls to the engine, and notes around each
    what the benchmark needs: a host span, the time each token came out,
    the batch and context of each decode step, the pool's occupancy."""

    def __init__(self, engine):
        import jax

        self._engine = engine
        self._span = jax.profiler.TraceAnnotation
        self._by_prompt = {}    # id(prompt array) -> request record
        self._prefilling = {}   # slot -> record
        self._active = {}       # slot -> record
        self._preempted = {}    # id(blob) -> record
        self.ticks = []         # end time of every prefill chunk
        self.steps = []         # (end time, active, context tokens, pages)

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def track(self, prompt: np.ndarray, record: dict) -> None:
        self._by_prompt[id(prompt)] = record

    def admit_start(self, slot, tokens, steps):
        record = self._by_prompt.get(id(tokens))
        if record is None:
            raise RuntimeError(
                "benchmark proxy: admit_start got an array that was not "
                "submitted (the scheduler copies prompts now: track by "
                "another key)")
        with self._span("bench:admit_start"):
            self._engine.admit_start(slot, tokens, steps)
        self._prefilling[slot] = record

    def prefill_tick(self):
        with self._span("bench:prefill_tick"):
            done = self._engine.prefill_tick()
        now = time.monotonic()
        self.ticks.append(now)
        for slot, _first in done:
            record = self._prefilling.pop(slot)
            record["token_t"].append(now)
            self._active[slot] = record
        return done

    def step(self):
        with self._span("bench:step"):
            out = self._engine.step()
        now = time.monotonic()
        context = 0
        for record in self._active.values():
            # this step attended to the prompt and every token before it
            context += record["prompt_len"] + len(record["token_t"])
            record["token_t"].append(now)
        self.steps.append((now, len(self._active), context,
                           self._engine.pool.used_pages))
        return out

    def release(self, slot):
        with self._span("bench:release"):
            self._engine.release(slot)
        self._active.pop(slot, None)
        self._prefilling.pop(slot, None)

    def preempt(self, slot):
        blob = self._engine.preempt(slot)
        self._preempted[id(blob)] = self._active.pop(slot)
        return blob

    def restore(self, slot, blob):
        self._engine.restore(slot, blob)
        self._active[slot] = self._preempted.pop(id(blob))


def build(config: dict, seed: int):
    """``(scheduler, proxy, transformer config)`` for a configuration."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models.lm_serving import _LMServingEntry
    from nnstreamer_tpu.models.transformer import TransformerConfig
    from nnstreamer_tpu.serving import DecodeScheduler

    reference = harness.reference_for(config)
    sizes = reference.sizes(config)
    tcfg = TransformerConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        heads=config["num_attention_heads"],
        layers=config["num_hidden_layers"],
        mlp_mult=config["ffn_dim"] // config["hidden_size"],
        max_seq=config["max_position_embeddings"])
    dtype = jnp.dtype(config["serve_dtype"])
    params = jax.jit(lambda key: reference.program_params(key, sizes, dtype))(
        weights.seed_key(seed))

    class _Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    engine = _Seeded(tcfg, serve_dtype=config["serve_dtype"]).make_continuous(
        paged=True, **config["engine"])
    proxy = EngineProxy(engine)
    sched = DecodeScheduler(proxy, name="benchmark",
                            max_depth=config.get("queue_depth", 4096),
                            predictive_shed=False)
    return sched, proxy, tcfg


def _record(item: dict) -> dict:
    prompt = item["prompt"]
    return {"prompt": prompt, "prompt_len": int(prompt.size),
            "steps": item["steps"], "due_s": item.get("due_s"),
            "after": item.get("after"), "ramp": bool(item.get("ramp")),
            "due_t": None, "token_t": [], "sent_t": None, "request": None}


def warm(sched, proxy, config: dict, vocab: int) -> None:
    """Every program the window uses, once: a prompt of more than one
    chunk through the scheduler, a few decode steps with two slots live."""
    chunk = config["engine"]["chunk"]
    reqs = []
    for n in (chunk + 3, 5):
        prompt = np.arange(n, dtype=np.int32) % vocab
        proxy.track(prompt, _record({"prompt": prompt, "steps": 4}))
        reqs.append(sched.submit(prompt, steps=4))
    for r in reqs:
        r.result(timeout=900)
    proxy.ticks.clear()
    proxy.steps.clear()


RAMP_LIMIT_S = 240.0


def drive(sched, proxy, items: list, seconds: float, tracer,
          on_open=None) -> tuple:
    """Send each request when it is due and the one it waits for has
    finished; returns ``(t0, cutoff, records sent)``. One thread, sleeping
    between arrivals (2 ms at a time while a request waits for another).
    ``on_open`` is called as the window opens, after the ramp."""
    import jax

    records = [_record(item) for item in items]
    unsent = list(range(len(records)))

    def finished_at(i):  # when request i finished, or None
        req = records[i]["request"]
        if req is None or not req.done():
            return None
        if req.error is not None:
            raise RuntimeError(f"request {i} failed with one waiting for "
                               f"it: {req.error!r}")
        return records[i]["token_t"][-1]

    def send_ready(now, t0):
        """Send what is ready (``t0`` None: the window is not open yet);
        returns when to look again."""
        wake = now + 0.05
        for i in list(unsent):
            rec = records[i]
            timed, waits = rec["due_s"] is not None, rec["after"] is not None
            if t0 is None and not rec["ramp"] and (timed or not waits):
                continue  # due at a time of the window
            due_t = t0 + rec["due_s"] if timed and t0 is not None else now
            if waits:
                done_t = finished_at(rec["after"])
                if done_t is None:
                    wake = min(wake, now + 0.002)
                    continue
                due_t = max(done_t, due_t) if timed else done_t
            if due_t > now:
                wake = min(wake, due_t)
                continue
            proxy.track(rec["prompt"], rec)
            with jax.profiler.TraceAnnotation("bench:submit"):
                rec["request"] = sched.submit(rec["prompt"],
                                              steps=rec["steps"])
            rec["due_t"], rec["sent_t"] = due_t, time.monotonic()
            unsent.remove(i)
        return wake

    ramp = [r for r in records if r["ramp"]]
    if ramp:
        give_up = time.monotonic() + RAMP_LIMIT_S
        while not all(r["token_t"] for r in ramp):
            now = time.monotonic()
            if now > give_up:
                raise RuntimeError("the ramp's requests have no first token "
                                   f"after {RAMP_LIMIT_S} s")
            time.sleep(max(0.0, min(send_ready(now, None) - now, 0.01)))
    if on_open is not None:
        on_open()
    t0 = time.monotonic()
    cutoff = t0 + seconds
    while True:
        now = time.monotonic()
        if tracer is not None:
            tracer.poll(now, t0)
        if now >= cutoff:
            break
        wake = send_ready(now, t0)
        time.sleep(max(0.0, min(wake, cutoff) - time.monotonic()))
    return t0, cutoff, [r for r in records if r["request"] is not None]


def window_samples(records: list, t0: float, cutoff: float) -> tuple:
    """``(ttft_ms, tpot_ms, out_tokens)`` of a window: for every request
    that was due in it, its time from due to first token (one whose first
    token is not out by ``cutoff`` enters with the time it has waited); the
    mean token gap of every request with two tokens inside the window; and
    the tokens that came out inside it."""
    ttft, tpot, out_tokens = [], [], 0
    for r in records:
        inside = [t for t in r["token_t"] if t0 <= t <= cutoff]
        out_tokens += len(inside)
        if r["due_t"] >= t0:
            first = r["token_t"][0] if r["token_t"] else cutoff
            ttft.append((min(first, cutoff) - r["due_t"]) * 1e3)
        if len(inside) >= 2:
            tpot.append(stats.tpot_ms(inside[0], inside[-1], len(inside)))
    return ttft, tpot, out_tokens


def check(ctx, records: list, lengths: list, tcfg) -> tuple:
    """Compare a seeded sample of the finished requests, the longest among
    them, with the plain reference: for every served token, how far its
    reference logit lies below the reference's best. Returns ``(correct,
    [(name, value, limit)])``. Runs after the program's state is freed."""
    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    limits = config["check"]
    done = [r for r in records
            if r["request"] is not None and r["request"].done()
            and r["request"].error is None]
    checks, ok = [], True
    for r in done:
        toks = r["request"].result()[0]
        good = (len(toks) == r["request"].steps
                and len(r["token_t"]) >= len(toks)
                and bool(((toks >= 0) & (toks < tcfg.vocab)).all()))
        ok = ok and good
    checks.append(("finished_requests_well_formed", int(ok and bool(done)), 1))
    ok = ok and bool(done)
    if not done:
        return ok, checks
    order = np.random.default_rng([int(seed), 9]).permutation(len(done))
    longest = max(range(len(done)), key=lambda i: (
        done[i]["prompt_len"] + done[i]["request"].steps))
    picked = [longest] + [int(i) for i in order if i != longest]
    picked = picked[:int(mix["check_sample"])]
    quants = ("none", *ctx.get("control", ()))
    gaps = served_logit_gaps(
        config, seed, [(done[i]["prompt"], done[i]["request"].result()[0])
                       for i in picked], lengths, quants)
    flat = np.concatenate(gaps["none"])
    for q in quants[1:]:  # the control's readings, beside the program's
        lower = np.concatenate(gaps[q])
        checks += [(f"control_{q}_gap_max", float(lower.max()), None),
                   (f"control_{q}_gap_mean", float(lower.mean()), None)]
    for name, value in (("served_gap_max", float(flat.max())),
                        ("served_gap_mean", float(flat.mean()))):
        limit = limits[name + "_limit"]
        checks.append((name, value, limit))
        ok = ok and value <= limit
    checks.append(("served_tokens_compared", int(flat.size), None))
    return ok, checks


def served_logit_gaps(config: dict, seed: int, pairs: list, lengths: list,
                      quants=("none",)) -> dict:
    """``{quant: [gaps per request]}``: for each (prompt, served tokens)
    pair and each served token, the reference's best logit at that
    position minus the reference's logit of the token the run produced
    under ``"none"``; under a lower precision, of the token that precision
    puts first. ``lengths`` are the (prompt, output) lengths of all the
    run's requests: they fix the padded shape, whichever were picked."""
    reference = harness.reference_for(config)
    sizes = reference.sizes(config)
    longest = max(p + s for p, s in lengths)
    pad = min(-(-longest // 128) * 128, sizes.positions)
    rows_pad = max(s for _, s in lengths)
    tokens = np.zeros((len(pairs), pad), np.int32)
    rows = np.zeros((len(pairs), rows_pad), np.int32)
    for i, (prompt, served) in enumerate(pairs):
        n = len(served)
        tokens[i, :prompt.size] = prompt
        tokens[i, prompt.size:prompt.size + n - 1] = served[:-1]
        rows[i, :n] = prompt.size - 1 + np.arange(n)
    logits = reference.logits_for(weights.seed_key(seed), sizes, tokens, rows,
                                  quants)
    out = {}
    exact = np.asarray(logits["none"])
    for q in quants:
        lower = np.asarray(logits[q])
        out[q] = []
        for i, (_prompt, served) in enumerate(pairs):
            n = len(served)
            chosen = served if q == "none" else lower[i, :n].argmax(-1)
            out[q].append(served_gaps(exact[i, :n], chosen))
    return out


def run(ctx) -> dict:
    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    clock, tracer = ctx["clock"], ctx["tracer"]
    sched, proxy, tcfg = build(config, seed)
    opened = {}

    def on_open():
        opened.update(clock=clock.read(), snap=sched.metrics_snapshot())

    try:
        warm(sched, proxy, config, tcfg.vocab)
        items = traffic.requests(mix, seed, ctx["seconds"], tcfg.vocab)
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
        "ttft_ms": ttft, "tpot_ms": tpot, "out_tokens": out_tokens,
    }
    sched = proxy = None
    gc.collect()
    lengths = [(int(it["prompt"].size), it["steps"]) for it in items]
    correct, checks = check(ctx, records, lengths, tcfg)
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
