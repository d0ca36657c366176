"""Stand-alone measurement behind the width of a prefill launch (PR 30).

``PagedLMEngine._prefill_chunk`` of both benchmark configurations, built as
their drivers build them (``benchmark/drivers/<kind>.build``: the cells'
weights, pool and engine settings), at widths 32 / 64 / 128 / 256 / 512:
device ms a launch and a prompt token, so that PERF.md can show where the
width the engine derives (``serving.lm_engine.prefill_width``) lies on the
curve. The rule is switched off here (the chip lookup answers "unknown", so
``chunk=`` stands as given) and what it would choose is printed beside.

At the rule's width each configuration is timed once more with the serving
limit cut to that width (``max_positions=``): a launch whose attention
covers the chunk's own positions and nothing else. Until PR 42 the
difference was what gathering, masking and scoring ``max_seq`` padded
positions cost a wide launch (``padded_attention_share``: the estimate the
walk over a slot's blocks, ``ops.paged_attention.chunk_line_attention``,
started from). Since the launch walks the blocks its slot holds, the rule's
width is also timed at ``start`` 0, mid-prompt and at the serving limit's
end, each beside ``ctx_read`` (the positions its layers read, the engine's
own count) and ``over_cut_limit_ms``: what the walk still costs over no
context but the launch's own.

Since PR 44 a launch of whole pages writes its lines a page at a time
(``serving.lm_engine.write_pages``). Beside the rule's width's ms stand the
ms of the launch's writes alone, in the row form and in the page form
(``writes_ms``: the engine's pools, a full table and one array of lines a
pool through every write the launch issues, nothing else in the program),
the updates each form issues, and the us a write.

``--pair`` (since PR 45) is the short form for a change to what both
programs run: one build a configuration at the rule's width, ``_step``
with every slot live at the middle of the serving limit (``step_ms``, the
pools handed from step to step, one wait at the end), then the launch at
the same ``start``, and nothing else. A parent and a change are two runs of
it in one chip call, each from its own tree.

Prints one JSON line per (configuration, width, context, start).

    chiprun -- python tools/prefill_width_forms.py [configuration ...]
        [widths=256,512] [--pair]

Any configuration ``BENCHMARK.json`` lists (the two above by default).

``--rehearse`` runs the same path at the files' ``rehearsal`` sizes (the
CPU: what it prints there is no device time).
"""
import copy
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402
from nnstreamer_tpu.serving import lm_engine  # noqa: E402
from nnstreamer_tpu.utils import flops  # noqa: E402

WIDTHS = (32, 64, 128, 256, 512)
CONFIGS = ("opt_1.3b", "kanana2_30b_a3b_l8")
SEED, REPS = 30, 10


def _timed(run, reps=REPS) -> "tuple[float, float]":
    """``(first call's seconds, mean ms of reps more)`` of ``run(reps)``."""
    t0 = time.perf_counter()
    run(1)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run(reps)
    return first_s, 1e3 * (time.perf_counter() - t0) / reps


def _tables(engine, slots=None) -> tuple:
    """A slot's table by kind of layer, full of distinct pages; ``slots``
    of them, each slot's pages its own as far as the pool goes."""
    shape = (*(() if slots is None else (slots,)), engine.blocks_per_slot)
    blocks = np.arange(np.prod(shape), dtype=np.int32).reshape(shape)
    return tuple(jnp.asarray(1 + blocks % pool.pages)
                 for pool in engine.pools_by_kind.values())


def writes_ms(engine, start: int, n_valid: int) -> dict:
    """The writes of one launch alone, by form: every pool of every
    pass-layer written once with the launch's ``(C, width)`` lines, at the
    rows its table names, through ``lm_engine.write_rows`` (an update a
    line) and ``lm_engine.write_pages`` (an update a page), the pools
    donated from call to call as the engine's are."""
    C, pg, P = engine.chunk, engine.page_size, len(engine.line_widths)
    writes = engine.chunk_lines(1)[0]  # one a pool a pass-layer
    q = start + jnp.arange(C)
    lines = tuple(jnp.ones((C, w), jnp.float32) for w in engine.line_widths)

    def program(form):
        def run(tables, lines, n_valid, *pools):
            # a value of the call, as the launch's is: nothing folds away
            valid = jnp.arange(C) < n_valid
            out = []
            for k, (kind, pool) in enumerate(engine.pools_by_kind.items()):
                dest = jnp.where(valid, tables[k][q // pg], 0)
                for mine, line in zip(pools[k * P:(k + 1) * P], lines):
                    for i in range(engine.kind_layers[kind]):
                        row0 = i * (pool.pages + 1)
                        if form == "rows":
                            mine = lm_engine.write_rows(
                                mine, row0, dest, q % pg, line + i)
                        else:
                            mine = lm_engine.write_pages(
                                mine, row0, dest[::pg],
                                valid.reshape(-1, pg, 1),
                                (line + i).reshape(-1, pg, line.shape[-1]))
                    out.append(mine)
            return tuple(out)

        return jax.jit(run, donate_argnums=tuple(
            range(3, 3 + len(engine._pools))))

    got, tables = {"writes": writes}, _tables(engine)
    rows = jnp.asarray(n_valid, jnp.int32)
    for form in ("rows", "pages") if engine.chunk_pages else ("rows",):
        jitted = program(form)

        def run(reps):
            for _ in range(reps):
                engine._pools = jitted(tables, lines, rows, *engine._pools)
            jax.block_until_ready(engine._pools)

        # a call is a fraction of a ms: enough of them in flight that the
        # device, not the host's dispatch, sets the time
        ms = _timed(run, 10 * REPS)[1]
        got[f"writes_{form}_ms"] = round(ms, 4)
        got[f"us_a_write_{form}"] = round(1e3 * ms / writes, 2)
    got["updates_rows"], got["updates_pages"] = engine.chunk_lines(n_valid)
    return got


def step_ms(engine, pos: int) -> dict:
    """Mean device-bound ms of one ``_step`` with every slot live at
    ``pos``: ``REPS`` back to back, the token and the pools handed from one
    to the next as the engine does, one wait at the end."""
    S = engine.slots
    fixed = (np.full((S,), pos, np.int32), np.ones((S,), bool),
             *_tables(engine, S))
    join = np.full((S,), -1, np.int32)
    kept = len(engine._pools) + len(engine._states)
    token = jnp.zeros((S, 1), jnp.int32)

    def run(reps):
        nonlocal token
        for _ in range(reps):
            _out, token, *rest = engine._step(
                token, *fixed, *engine._pools, *engine._states, join)
            engine._keep(rest[-kept:])
        jax.block_until_ready(engine._pools)

    first_s, ms = _timed(run)
    return {"program": "_step", "slots": S, "pos": pos,
            "ms_per_step": round(ms, 3), "first_call_s": round(first_s, 1)}


def launch_ms(config: dict, width: int, positions=None, start=512,
              writes=False, step=False) -> dict:
    """Mean device-bound ms of one launch at ``start`` (or as near below it
    as the context allows): ``REPS`` back to back, the pools handed from
    one to the next as the engine does, one wait at the end. ``writes``:
    the launch's writes alone beside it (``writes_ms``). ``step``: the same
    engine's ``_step`` first, under ``"step"`` (``step_ms``)."""
    config = copy.deepcopy(config)
    config["engine"]["chunk"] = width
    if positions is not None:
        config["engine"]["max_positions"] = positions
    sched, proxy, _ = harness.driver_for(config).build(config, SEED)
    try:
        engine = proxy._engine
        assert engine.chunk == width, (engine.chunk, width)
        stepped = ({"step": step_ms(engine, engine.max_seq // 2)}
                   if step else {})
        # a chunk in the middle of a prompt where the context allows one:
        # every row valid, the slot's table full of distinct pages
        start = min(start, engine.max_seq - width)
        args = (jnp.arange(width, dtype=jnp.int32) % engine.family.vocab,
                jnp.asarray(start, jnp.int32), jnp.asarray(width, jnp.int32),
                *_tables(engine))
        kept = len(engine._pools) + len(engine._states)
        slot = jnp.int32(0)

        def run(reps):
            for _ in range(reps):
                # a family with state layers: slot 0's rows of its arrays;
                # a drafting family: "no next token" (a prompt's last launch)
                _row, *rest = engine._prefill_chunk(
                    *args, *engine._pools,
                    *((slot, *engine._states) if engine._states else ()),
                    *((jnp.int32(-1),) if engine.drafts else ()))
                engine._keep(rest[-kept:])
            jax.block_until_ready(engine._pools)

        compile_s, ms = _timed(run)
        alone = writes_ms(engine, start, width) if writes else {}
    finally:
        sched.close()
    return {"width": width, "context": engine.max_seq, "start": start,
            "ctx_read": engine.chunk_ctx(start, width)[0],
            "ms_per_launch": round(ms, 3),
            "ms_per_token": round(ms / width, 4),
            "first_call_s": round(compile_s, 1), **alone, **stepped}


def main():
    names = [a for a in sys.argv[1:]
             if not a.startswith("--") and "=" not in a] or CONFIGS
    widths = next((tuple(map(int, a[7:].split(","))) for a in sys.argv[1:]
                   if a.startswith("widths=")), WIDTHS)
    rehearse = "--rehearse" in sys.argv  # the files' CPU sizes: no timing
    device = jax.devices()[0]
    configs = {}
    for name in names:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{name}.json")) as fh:
            config = json.load(fh)
        if rehearse:
            config = {**config, **config["rehearsal"]}
        geometry = config["engine"]
        configs[name] = config, lm_engine.prefill_width(
            geometry["chunk"], config["max_position_embeddings"],
            geometry["page_size"], jnp.dtype(config["serve_dtype"]).itemsize)
    flops.ridge_flops_per_byte = lambda device=None: None  # chunk= stands
    for name, (config, rule) in configs.items():
        limit = config["max_position_embeddings"]
        head = {"config": name, "device": device.device_kind,
                "rule_width": rule}
        if "--pair" in sys.argv:
            page = config["engine"]["page_size"]
            pair = launch_ms(config, rule, start=limit // 2 // page * page,
                             step=True)
            print(json.dumps({**head, **pair.pop("step")}), flush=True)
            print(json.dumps({**head, "program": "_prefill_chunk", **pair}),
                  flush=True)
            gc.collect()
            continue
        served = {}
        for width in dict.fromkeys(
                w for w in (*widths, rule) if w <= limit):
            try:
                served[width] = launch_ms(config, width,
                                          writes=width == rule)
            except Exception as e:  # a width that does not compile or fit
                served[width] = {"width": width, "error":
                                 f"{type(e).__name__}: {str(e)[:300]}"}
            print(json.dumps({**head, **served[width]}), flush=True)
            gc.collect()
        if "error" in served[rule]:
            continue
        bare = launch_ms(config, rule, positions=rule)
        print(json.dumps({**head, **bare}), flush=True)
        gc.collect()
        # the walk: the first launch of a prompt, one mid-prompt (timed
        # above) and the one that ends at the serving limit
        for start in dict.fromkeys(min(s, limit - rule)
                                   for s in (0, 512, limit - rule)):
            walk = (served[rule] if start == served[rule]["start"]
                    else launch_ms(config, rule, start=start))
            over = walk["ms_per_launch"] - bare["ms_per_launch"]
            print(json.dumps({**head, **walk,
                              "over_cut_limit_ms": round(over, 3)}),
                  flush=True)
            gc.collect()


if __name__ == "__main__":
    main()
