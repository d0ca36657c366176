"""Stand-alone measurement behind the decode step's attention (PR 28, 37,
48).

One layer's step attention at the benchmark cells' shapes, over pools of
the cells' size filled from a seed, in each form tried:

* ``gather``  — ``ops.paged_attention.plain_line_attention``: take every
  slot's whole block table, mask, softmax (the step's form until PR 28);
* ``blocked`` — a ``fori_loop`` in XLA over blocks of pages up to the
  batch's longest context, online softmax (dynamic trip count);
* ``jaxlib``  — ``jax.experimental.pallas.ops.tpu.paged_attention`` with
  ``kv_heads = 1``, ``head_dim`` the line and ``pool[None]``;
* ``kernel``  — ``ops.paged_attention.kernel_line_attention`` at several
  pages a block (``None``: the number it derives from the line's bytes);
* ``kernel_by_head`` — the same kernel handed head-wide rows, ``(S, KV * K
  * G, head_dim)``: each key head's rows against that head's part of a
  line alone (PR 48; whatever ``contracts_by_head`` says of the shape).
  Only where a shape's lines hold key heads side by side (``KV``): there
  every whole-line form gets the block-diagonal query of the same heads,
  and every form's result is compared head by head.

Shapes: ``opt`` 16 slots × 128 pages of ``(16, 2048)``, keys and values in
two pools of 24 layers × 1025 rows; ``kanana`` 32 slots × 192 pages of
``(16, 640)``, one pool of 8 layers × 6145 rows; ``mellum_full`` and
``mellum_window`` 32 slots × 768 pages of ``(16, 512)``, two pools of 3
layers × 13313 rows and of 9 × 3073, the second seen from ``length − 1024``
on with the table's entries behind that given back; ``jamba`` 128 slots ×
96 pages of ``(64, 128)``, two pools of 2 layers × 12289 rows;
``kexaone_full`` and ``kexaone_window`` 64 slots × 256 pages of ``(16,
1024)``, 64 heads over 8 key heads of 128, two pools of 2 layers × 16385
rows and of 4 × 1025, the second seen from ``length − 128`` on, with two
queries a slot (the round) and with one. Lengths as
the cells' are: one slot of 300 (chat), sixteen of 300–830 (saturated),
thirty-two of 400–3072 × 0.66 (kanana), thirty-two of 2.3k–9k (mellum),
128 of 1.0k–4.6k (jamba), sixty-four of 128–4096 with a mean near 1,400
(kexaone), and every slot full (the guard; from the first
position on in every shape). Each form runs every layer of the pool once a
call (the rows differ by layer as in the engine), so the time printed is
per layer with the pool's lines cold in HBM.

Prints one JSON line per (shape, lengths, form): ms a layer, the GB/s of
visible lines that is (a v5e's HBM gives 819), the pages the kernel copies
over the pages that hold a visible line (``ops.paged_attention.
pages_fetched``, the kernel's own rule: 1.0 since PR 37, when it stopped
copying whole blocks), and the largest difference from ``gather``'s output
(float32 at ``HIGHEST``).

    chiprun -- python tools/paged_attention_forms.py [form prefix ...] \
        [shape=<name prefix>]
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

from nnstreamer_tpu.ops import paged_attention as pa  # noqa: E402

EXACT = jax.lax.Precision.HIGHEST
HBM_GB_S = 819  # a v5e's, benchmark/lib/peaks.py
SHAPES = {
    # slots, heads (queries a slot), line, page, blocks a slot, layers,
    # pages, pools, the positions a layer looks back, the cells' lengths
    # and, where key heads lie side by side in a line, how many
    "opt": dict(S=16, H=32, W=2048, pg=16, NB=128, L=24, pages=1024,
                pools=2, window=None, mixes=("chat", "saturated", "full"),
                KV=32),
    "kanana": dict(S=32, H=32, W=640, pg=16, NB=192, L=8, pages=6144,
                   pools=1, window=None, mixes=("kanana", "full")),
    "mellum_full": dict(S=32, H=32, W=512, pg=16, NB=768, L=3, pages=13312,
                        pools=2, window=None, mixes=("mellum", "full"),
                        KV=4),
    "mellum_window": dict(S=32, H=32, W=512, pg=16, NB=768, L=9, pages=3072,
                          pools=2, window=1024, mixes=("mellum",)),
    "jamba": dict(S=128, H=20, W=128, pg=64, NB=96, L=2, pages=12288,
                  pools=2, window=None, mixes=("jamba", "full")),
    "kexaone_full": dict(S=64, H=64, W=1024, pg=16, NB=256, L=2,
                         pages=16384, pools=2, window=None,
                         mixes=("kexaone", "full"), KV=8, queries=(2, 1)),
    "kexaone_window": dict(S=64, H=64, W=1024, pg=16, NB=256, L=4,
                           pages=1024, pools=2, window=128,
                           mixes=("kexaone", "full"), KV=8, queries=(2, 1)),
}


def lengths_of(shape, name, rng):
    S, ctx = shape["S"], shape["NB"] * shape["pg"]
    out = np.zeros((S,), np.int32)
    if name == "chat":
        out[3] = 300
    elif name == "saturated":
        out[:] = rng.integers(300, 831, S)
    elif name == "kanana":
        out[:] = rng.integers(400, ctx + 1, S) * 0.66  # mean 37% of ctx
    elif name == "mellum":
        out[:] = rng.integers(2300, 9001, S)
    elif name == "jamba":
        out[:] = rng.integers(1000, 4601, S)
    elif name == "kexaone":
        out[:] = 128 + (ctx - 129) * rng.random(S) ** 2  # mean near 1,400
    elif name == "full":
        out[:] = ctx
    return out


def blocked(q, kpool, vpool, rows, lengths, scale, PB=8):
    S, NB = rows.shape
    PG = kpool.shape[1]
    T = PB * PG
    H = q.shape[1]

    def body(i, carry):
        m, l, acc = carry
        r = jax.lax.dynamic_slice(rows, (0, i * PB), (S, PB))
        k = jnp.take(kpool, r, axis=0, mode="clip").reshape(S, T, -1)
        v = k if vpool is kpool else jnp.take(
            vpool, r, axis=0, mode="clip").reshape(S, T, -1)
        sc = jnp.einsum("shj,scj->shc", q, k, precision=EXACT) * scale
        at = i * T + jnp.arange(T)
        sc = jnp.where(at[None, None] < lengths[:, None, None], sc, -1e30)
        m_new = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        acc = alpha[..., None] * acc + jnp.einsum(
            "shc,scj->shj", p, v, precision=EXACT)
        return m_new, alpha * l + p.sum(-1), acc

    init = (jnp.full((S, H), -1e30), jnp.zeros((S, H)),
            jnp.zeros((S, H, vpool.shape[-1])))
    blocks = (lengths.max() + T - 1) // T
    _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
    return jnp.where((lengths > 0)[:, None, None],
                     acc / jnp.maximum(l, 1e-30)[..., None], 0.0)


def jaxlib(q, kpool, vpool, rows, lengths, scale, PB=8):
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention,
    )

    return paged_attention(q * scale, kpool[None], vpool[None], lengths,
                           rows, pages_per_compute_block=PB)


FORMS = {
    "gather": pa.plain_line_attention,
    "blocked": blocked,
    "jaxlib": jaxlib,
    **{f"kernel_pb{pb}": functools.partial(pa.kernel_line_attention,
                                           pages_per_block=pb)
       for pb in (None, 4, 8, 16, 32, 64)},
    "kernel_by_head": pa.kernel_line_attention,
}


def operands(qh, KV, by_head):
    """``qh (S, K, H, Dh)``, the heads' queries → a form's rows: head-wide
    ``(S, KV * K * G, Dh)`` or block-diagonal over whole lines ``(S, K * H,
    KV * Dh)``."""
    S, K, H, Dh = qh.shape
    if by_head:
        return qh.reshape(S, K, KV, H // KV, Dh).swapaxes(1, 2).reshape(
            S, K * H, Dh)
    own = (jnp.arange(H)[:, None] // (H // KV)
           == jnp.arange(KV * Dh)[None, :] // Dh)
    return jnp.where(own, jnp.tile(qh, (1, 1, 1, KV)), 0.0).reshape(
        S, K * H, KV * Dh)


def by_heads(out, K, KV, by_head):
    """A form's result → ``(S, K, H, Dh)``: every head's own part."""
    S, H = out.shape[0], out.shape[1] // K
    if by_head:
        return out.reshape(S, KV, K, H // KV, -1).swapaxes(1, 2).reshape(
            S, K, H, -1)
    out = out.reshape(S, K, H, KV, -1)
    head = (jnp.arange(H) // (H // KV))[None, None, :, None, None]
    return jnp.take_along_axis(out, head, axis=3)[:, :, :, 0]


def main():
    only = [a for a in sys.argv[1:] if "=" not in a]
    which = [a.split("=", 1)[1] for a in sys.argv[1:]
             if a.startswith("shape=")]
    rng = np.random.default_rng(28)
    for shape_name, shape in SHAPES.items():
        if which and not any(shape_name.startswith(w) for w in which):
            continue
        S, H, W, NB, L, pg = (shape[k] for k in
                              ("S", "H", "W", "NB", "L", "pg"))
        R = shape["pages"] + 1
        keys = jax.random.split(jax.random.PRNGKey(28), 3)
        pools = tuple(jax.random.normal(k, (L * R, pg, W), jnp.bfloat16)
                      for k in keys[:shape["pools"]])
        kpool, vpool = pools[0], pools[-1]
        KV = shape.get("KV", 1)
        scale = 0.125
        for mix, K in ((m, k) for m in shape["mixes"]
                       for k in shape.get("queries", (1,))):
            # the heads' queries, or rows over whole lines as they come
            q = jax.random.normal(
                keys[2], (S, K, H, W // KV) if KV > 1 else (S, K * H, W),
                jnp.float32)
            lengths = lengths_of(shape, mix, rng)
            if K > 1:  # the first query's; the last sees K - 1 more
                lengths = np.minimum(lengths, NB * pg - (K - 1))
            windowed = shape["window"] is not None
            # each query's first visible position; the walk's is the first's
            starts = np.zeros((S, K), np.int32)
            if windowed:
                starts = np.maximum(lengths[:, None] + np.arange(K)[None, :]
                                    - shape["window"], 0).astype(np.int32)
            first = starts[:, 0]
            bt = np.stack([rng.permutation(shape["pages"])[:NB] + 1
                           for _ in range(S)]).astype(np.int32)
            # the pages behind the window were given back
            bt[np.arange(NB)[None, :] < (first // pg)[:, None]] = 0
            seen = lengths + (K - 1)   # the walk's end: the last query's
            visible = int((seen - first).sum())
            held = int(((-(-seen // pg) - first // pg)
                        * (lengths > 0)).sum())
            if K == 1:
                starts = first
            ref = None
            for form, fn in FORMS.items():
                if only and form != "gather" and not any(
                        form.startswith(o) for o in only):
                    continue  # named forms only, beside their oracle
                if form in ("blocked", "jaxlib") and (
                        windowed or pg != 16 or K > 1):
                    continue  # neither knows a first visible position
                by_head = form == "kernel_by_head"
                if by_head and KV == 1:
                    continue  # nothing to take apart
                named = int(form[9:]) if form[9:].isdigit() else 0
                if named > NB or named * pg * W * 2 > 2 * pa.BLOCK_BYTES:
                    continue  # megabytes a buffer: nothing to learn

                def layers(q, bt, lengths, starts, kpool, vpool, fn=fn,
                           form=form):
                    more = {}
                    if form not in ("blocked", "jaxlib"):
                        more = dict(starts=starts if windowed else None,
                                    queries=K)
                    out = [fn(q, kpool, vpool, li * R + bt, lengths, scale,
                              **more) for li in range(L)]
                    return out[0], sum(o.sum() for o in out)

                row = {"shape": shape_name, "lengths": mix, "form": form,
                       "tokens": visible, "queries": K}
                try:
                    run = jax.jit(layers)
                    args = (operands(q, KV, by_head) if KV > 1 else q, bt,
                            lengths, starts, kpool, vpool)
                    got, _ = jax.block_until_ready(run(*args))
                    reps = 5
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        out = run(*args)
                    jax.block_until_ready(out)
                    ms = 1e3 * (time.perf_counter() - t0) / reps / L
                    row["ms_per_layer"] = round(ms, 4)
                    row["visible_gb_s"] = round(
                        visible * W * 2 * len(pools) / ms / 1e6, 1)
                    row["of_peak"] = round(
                        row["visible_gb_s"] / HBM_GB_S, 3)
                    if form.startswith("kernel"):
                        row["fetched_over_visible"] = round(
                            pa.pages_fetched(seen, first, pg) / held, 3)
                    if KV > 1:
                        got = by_heads(got, K, KV, by_head)
                    if form == "gather":
                        ref = got
                    row["max_diff"] = float(jnp.abs(got - ref).max())
                    row["ref_absmax"] = float(jnp.abs(ref).max())
                except Exception as e:  # a form the compiler refuses
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                print(json.dumps(row), flush=True)
        del pools, kpool, vpool


if __name__ == "__main__":
    main()
