"""Stand-alone measurement behind the routed experts' products (PR 32).

One expert layer (``ops.moe_grouped``: the products alone, after the
router) at the four shapes the benchmark's expert cells run, over stacks
of the cells' size filled from a seed, in each form tried:

* ``ragged``        — ``plain_grouped_experts``: the assignments sorted by
  expert, three ``jax.lax.ragged_dot`` (the only form until PR 32);
* ``ragged_halves`` — the same over the launch's rows in two calls of
  half the rows each (ROADMAP S10's first cheap form: 128 assignment rows
  a call at Mellum's step);
* ``ragged_pad8``, ``ragged_pad16`` — the same with 8 or 16 rows that
  reach no expert appended, so the assignment rows are not 256 (S10's
  second: XLA picks its tiling by the row count);
* ``kernel_f<tile>_d<depth>`` — ``kernel_grouped_experts`` at ``tile``
  hidden columns a tile (``whole``: the expert's matrices in one copy
  each) and ``depth`` ring buffers.

Shapes: ``mellum`` 64 experts of ``2304 × 896`` top-8, ``kanana`` 128 of
``2048 × 768`` top-6; ``step`` 32 rows, ``launch`` 256. The routing is
drawn so that a step reaches the share of experts the cells' counters
read (``moe_experts_touched_share``: 98.5% and 75.7%; ledger, PR 31).
Each form runs ``LAYERS`` layers with weights of their own a call, so the
time printed is per layer with the weights cold in HBM.

Prints one JSON line per (shape, form): ms a layer, the share of what
reading the reached experts once at the chip's HBM rate would take, and
the largest difference from ``ragged``.

    chiprun -- python tools/moe_grouped_forms.py [form prefix ...]
    python tools/moe_grouped_forms.py --rehearse      (CPU, tiny widths)
"""
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nnstreamer_tpu.ops import moe_grouped as mg  # noqa: E402
from nnstreamer_tpu.utils import flops  # noqa: E402

LAYERS = 4
SHAPES = {
    # rows, top-k, experts, D, F, spread of the experts' popularity
    "mellum_step": dict(T=32, k=8, E=64, D=2304, F=896, skew=0.0),
    "mellum_launch": dict(T=256, k=8, E=64, D=2304, F=896, skew=0.0),
    "kanana_step": dict(T=32, k=6, E=128, D=2048, F=768, skew=0.4),
    "kanana_launch": dict(T=256, k=6, E=128, D=2048, F=768, skew=0.4),
}
REHEARSAL = dict(T=32, k=4, E=16, D=256, F=256, skew=0.4)


def routing(shape, rng):
    """``(flat (T, k), weights (T, k), sizes (E,))``: top-k of a popularity
    a layer plus noise a row; the weights are a softmax over the chosen."""
    T, k, E = shape["T"], shape["k"], shape["E"]
    score = rng.normal(0, shape["skew"], (1, E)) + rng.gumbel(size=(T, E))
    flat = np.argsort(-score, axis=1)[:, :k].astype(np.int32)
    chosen = np.take_along_axis(score, flat, axis=1)
    weights = np.exp(chosen - chosen.max(1, keepdims=True))
    weights = (weights / weights.sum(1, keepdims=True)).astype(np.float32)
    return flat, weights, np.bincount(flat.ravel(), minlength=E).astype(
        np.int32)


def halves(h, wg, wu, wd, flat, weights, sizes):
    T, E = h.shape[0], wg.shape[0]
    out = []
    for rows in (slice(0, T // 2), slice(T // 2, T)):
        part = jnp.zeros((E + 1,), jnp.int32).at[
            flat[rows].reshape(-1)].add(1)[:E]
        out.append(mg.plain_grouped_experts(h[rows], wg, wu, wd, flat[rows],
                                            weights[rows], part))
    return jnp.concatenate(out)


def padded(h, wg, wu, wd, flat, weights, sizes, *, extra):
    T, E = h.shape[0], wg.shape[0]
    return mg.plain_grouped_experts(
        jnp.pad(h, ((0, extra), (0, 0))), wg, wu, wd,
        jnp.pad(flat, ((0, extra), (0, 0)), constant_values=E),
        jnp.pad(weights, ((0, extra), (0, 0))), sizes)[:T]


def forms_of(shape, interpret):
    kernel = functools.partial(mg.kernel_grouped_experts, interpret=interpret)
    out = {"ragged": mg.plain_grouped_experts, "ragged_halves": halves,
           "ragged_pad8": functools.partial(padded, extra=8),
           "ragged_pad16": functools.partial(padded, extra=16)}
    F = shape["F"]
    for tile, depth in ((F, 2), (F // 2, 2), (F // 2, 3), (F // 3, 3),
                        (128, 2), (128, 3), (128, 4)):
        if tile % 128 == 0 and F % tile == 0:
            name = f"kernel_f{'whole' if tile == F else tile}_d{depth}"
            out.setdefault(name, functools.partial(kernel, tile_f=tile,
                                                   depth=depth))
    return out


def main():
    rehearse = "--rehearse" in sys.argv[1:]
    only = [a for a in sys.argv[1:] if not a.startswith("--")]
    rng = np.random.default_rng(32)
    hbm = flops.hbm_bytes_per_s_per_chip()
    shapes = {"rehearsal": REHEARSAL} if rehearse else SHAPES
    made, weights_of = None, None
    for name, shape in shapes.items():
        T, E, D, F = (shape[x] for x in ("T", "E", "D", "F"))
        if made != (E, D, F):  # a configuration's step and launch share
            made, weights_of = (E, D, F), None  # the last one's are freed
            keys = jax.random.split(jax.random.PRNGKey(32), 3 * LAYERS)
            weights_of = [
                tuple((jax.random.normal(keys[3 * li + i], s, jnp.bfloat16)
                       * 0.03).astype(jnp.bfloat16)
                      for i, s in enumerate(((E, D, F), (E, D, F), (E, F, D))))
                for li in range(LAYERS)]
        h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
        routes = [routing(shape, rng) for _ in range(LAYERS)]
        reached = float(np.mean([(r[2] > 0).mean() for r in routes]))
        least_ms = 1e3 * reached * E * 3 * D * F * 2 / hbm if hbm else None
        ref = None
        for form, fn in forms_of(shape, rehearse).items():
            if only and form != "ragged" and not any(
                    form.startswith(o) for o in only):
                continue  # named forms only, beside their oracle

            def layers(h, routes, weights_of, fn=fn):
                out = [fn(h, *w, *r) for w, r in zip(weights_of, routes)]
                return out[0], sum(o.sum() for o in out)

            row = {"shape": name, "form": form, "rows": T * shape["k"],
                   "reached_share": round(reached, 4),
                   "max_load": int(max(r[2].max() for r in routes))}
            try:
                run = jax.jit(layers)
                t0 = time.perf_counter()
                first, _ = jax.block_until_ready(run(h, routes, weights_of))
                row["first_call_s"] = round(time.perf_counter() - t0, 2)
                reps = 1 if rehearse else 10
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = run(h, routes, weights_of)
                jax.block_until_ready(out)
                ms = 1e3 * (time.perf_counter() - t0) / reps / LAYERS
                if not rehearse:  # a CPU's time is no device number
                    row["ms_per_layer"] = round(ms, 4)
                    row["share_of_hbm_rate"] = round(least_ms / ms, 4)
                if form == "ragged":
                    ref = first
                row["max_diff"] = float(jnp.abs(first - ref).max())
                row["ref_absmax"] = float(jnp.abs(ref).max())
            except Exception as e:  # a form the compiler refuses
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
