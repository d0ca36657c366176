"""Stand-alone measurement behind the latent pool's line layout (PR 27).

A DeepSeek-V3 token keeps 576 values a layer (512 latent + 64 rotary), four
and a half lane rows of 128. Three ways to store them, each timed on the
chip as the decode step uses the pool: per layer, write one line per slot,
take the slots' rows, score whole lines against per-head queries, softmax,
weighted sum of whole lines (``Precision.HIGHEST``, as the engine's step):

* ``declared`` — rows ``(layers*(pages+1), page, 576)``, the tiling left to
  the compiler;
* ``padded``   — rows ``(..., page, 640)``, the line zero-padded to five
  lane rows, queries padded alike;
* ``split``    — latent rows ``(..., page, 512)`` and the rotary keys of a
  page packed flat, ``(..., page*64)``, unpacked after the take.

Prints one JSON line per layout: device bytes the pool really takes
(``memory_stats`` before and after it is made), ms a layer, and the
largest difference of its output from ``declared``'s.

    chiprun -- python tools/mla_line_layout.py
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

S, H, PG, NB, L, PAGES = 32, 32, 16, 192, 8, 6144
LAT, ROPE = 512, 64
CTX, R = NB * PG, PAGES + 1
EXACT = jax.lax.Precision.HIGHEST


def in_use():
    return int(jax.devices()[0].memory_stats()["bytes_in_use"])


def attend(q, ctx, visible):
    att = jnp.einsum("shl,scl->shc", q, ctx, precision=EXACT) / jnp.sqrt(192.0)
    att = jax.nn.softmax(jnp.where(visible[:, None], att, -1e30), axis=-1)
    return jnp.einsum("shc,scl->shl", att, ctx, precision=EXACT)[..., :LAT]


def make(layout):
    width = {"declared": LAT + ROPE, "padded": 640}.get(layout)

    def pools():
        if layout == "split":
            return (jnp.zeros((L * R, PG, LAT), jnp.bfloat16),
                    jnp.zeros((L * R, PG * ROPE), jnp.bfloat16))
        return (jnp.zeros((L * R, PG, width), jnp.bfloat16),)

    def step(q, lines, dest, offs, bt, visible, *pool):
        out = 0.0
        for li in range(L):
            rows = li * R + bt
            if layout == "split":
                lat, rope = pool
                lat = lat.at[li * R + dest, offs].set(lines[..., :LAT])
                cols = offs[:, None] * ROPE + jnp.arange(ROPE)[None]
                rope = rope.at[(li * R + dest)[:, None], cols].set(
                    lines[..., LAT:])
                pool = (lat, rope)
                c = jnp.take(lat, rows, axis=0, mode="clip").reshape(
                    S, CTX, LAT)
                kr = jnp.take(rope, rows, axis=0, mode="clip").reshape(
                    S, CTX, ROPE)
                att = (jnp.einsum("shl,scl->shc", q[..., :LAT], c,
                                  precision=EXACT)
                       + jnp.einsum("shl,scl->shc", q[..., LAT:], kr,
                                    precision=EXACT)) / jnp.sqrt(192.0)
                att = jax.nn.softmax(
                    jnp.where(visible[:, None], att, -1e30), axis=-1)
                o = jnp.einsum("shc,scl->shl", att, c, precision=EXACT)
            else:
                (p,) = pool
                pad = width - (LAT + ROPE)
                ln = jnp.pad(lines, ((0, 0), (0, pad)))
                qq = jnp.pad(q, ((0, 0), (0, 0), (0, pad)))
                p = p.at[li * R + dest, offs].set(ln)
                pool = (p,)
                ctx = jnp.take(p, rows, axis=0, mode="clip").reshape(
                    S, CTX, width)
                o = attend(qq, ctx, visible)
            out = out + o
            q = q + 1e-3 * jnp.pad(o, ((0, 0), (0, 0), (0, ROPE)))
        return (out, *pool)

    n = 2 if layout == "split" else 1
    return pools, jax.jit(step, donate_argnums=tuple(range(6, 6 + n)))


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("mla_line_layout: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, H, LAT + ROPE)), jnp.float32)
    lines = jnp.asarray(rng.standard_normal((S, LAT + ROPE)), jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(PAGES)[:S * NB].reshape(S, NB) + 1,
                     jnp.int32)
    pos = jnp.asarray(rng.integers(300, CTX - 1, S), jnp.int32)
    dest = bt[jnp.arange(S), pos // PG]
    offs = pos % PG
    visible = jnp.arange(CTX)[None] <= pos[:, None]
    first = None
    for layout in ("declared", "padded", "split"):
        pools, step = make(layout)
        before = in_use()
        pool = pools()
        jax.block_until_ready(pool)
        held = in_use() - before
        for _ in range(3):  # fill some lines, warm
            out, *pool = step(q, lines, dest, offs, bt, visible, *pool)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(20):
            out, *pool = step(q, lines, dest, offs, bt, visible, *pool)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / 20 / L * 1e3
        got = np.asarray(out)
        first = got if first is None else first
        print(json.dumps({
            "layout": layout, "pool_bytes": held,
            "bytes_a_token": held / (R * PG), "ms_a_layer": ms,
            "max_abs_diff_from_declared": float(np.abs(got - first).max())}),
            flush=True)
        del pool, out
    return 0


if __name__ == "__main__":
    sys.exit(main())
