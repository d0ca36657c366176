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
covers the chunk's own positions and no padding. The difference is what
gathering, masking and scoring ``max_seq`` padded positions costs a wide
launch: ``padded_attention_share``, the number a chunk kernel over the
pages a slot holds (ROADMAP S3) starts from.

Prints one JSON line per (configuration, width, context).

    chiprun -- python tools/prefill_width_forms.py [configuration ...]

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


def launch_ms(config: dict, width: int, positions=None) -> dict:
    """Mean device-bound ms of one launch: ``REPS`` back to back, the pools
    handed from one to the next as the engine does, one wait at the end."""
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
        start = min(512, engine.max_seq - width)
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
    return {"width": width, "context": engine.max_seq,
            "ms_per_launch": round(ms, 3),
            "ms_per_token": round(ms / width, 4),
            "first_call_s": round(compile_s, 1)}


def main():
    names = [a for a in sys.argv[1:] if not a.startswith("--")] or CONFIGS
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
                w for w in (*WIDTHS, rule) if w <= limit):
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
        share = 1.0 - bare["ms_per_launch"] / served[rule]["ms_per_launch"]
        print(json.dumps({**head, **bare,
                          "padded_attention_share": round(share, 4)}),
              flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
