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

Prints one JSON line per (configuration, width, context, start).

    chiprun -- python tools/prefill_width_forms.py [configuration ...]
        [widths=256,512]

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


def launch_ms(config: dict, width: int, positions=None, start=512) -> dict:
    """Mean device-bound ms of one launch at ``start`` (or as near below it
    as the context allows): ``REPS`` back to back, the pools handed from
    one to the next as the engine does, one wait at the end."""
    config = copy.deepcopy(config)
    config["engine"]["chunk"] = width
    if positions is not None:
        config["engine"]["max_positions"] = positions
    sched, proxy, _ = harness.driver_for(config).build(config, SEED)
    try:
        engine = proxy._engine
        assert engine.chunk == width, (engine.chunk, width)
        NB = engine.blocks_per_slot
        # a chunk in the middle of a prompt where the context allows one:
        # every row valid, the slot's table full of distinct pages
        start = min(start, engine.max_seq - width)
        args = (jnp.arange(width, dtype=jnp.int32) % engine.family.vocab,
                jnp.asarray(start, jnp.int32), jnp.asarray(width, jnp.int32),
                jnp.asarray(1 + np.arange(NB, dtype=np.int32)))

        def run(reps):
            pools = engine._pools
            for _ in range(reps):
                _logits, *rest = engine._prefill_chunk(*args, *pools)
                pools = tuple(rest[-len(pools):])
            engine._pools = pools
            jax.block_until_ready(pools)

        t0 = time.perf_counter()
        run(1)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(REPS)
        ms = 1e3 * (time.perf_counter() - t0) / REPS
    finally:
        sched.close()
    return {"width": width, "context": engine.max_seq, "start": start,
            "ctx_read": engine.chunk_ctx(start, width)[0],
            "ms_per_launch": round(ms, 3),
            "ms_per_token": round(ms / width, 4),
            "first_call_s": round(compile_s, 1)}


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
        served = {}
        for width in dict.fromkeys(
                w for w in (*widths, rule) if w <= limit):
            try:
                served[width] = launch_ms(config, width)
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
