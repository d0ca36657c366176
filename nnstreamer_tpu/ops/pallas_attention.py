"""Pallas TPU flash attention kernel.

The reference's hand-written SIMD layer is its ORC kernels
(``gst/nnstreamer/elements/nnstreamer-orc.orc``, SURVEY.md §2.3); the
TPU-native analog is pallas. XLA already fuses the elementwise pipeline
math, so pallas is reserved for what fusion can't deliver — here, the
O(S²) attention score matrix never materializing in HBM: Q stays blocked
in VMEM, K/V blocks stream through, and the online-softmax running max /
denominator keep the result exact (flash-attention recurrence).

Grid: one program per (batch, head, q-block); each program loops over
K/V blocks with ``lax.fori_loop`` (bounded to the causal prefix).
VMEM per program: the whole-sequence K and V blocks are double-buffered
across grid steps and D pads to 128 lanes, so
2·2·S_kv·max(D, 128)·itemsize plus the q/out blocks must stay under the
16 MiB scoped-VMEM limit. What the v5e compiler accepts at D ≤ 128 and 16
heads (compiled against the v5e topology, PR 21): float32 up to S = 4096
(S = 8192 is refused, over by 128–256 KiB), bfloat16 up to S = 8192
(S = 16384 is refused). Shard longer sequences over ``sp`` first so each
shard's S_kv stays VMEM-resident.

No package code calls this kernel: the context-parallel paths
(parallel/context.py) compute their per-shard attention in plain jnp.
It is covered by tests (interpret mode on CPU) and by ``chip_smoke.py``
(Mosaic on the chip); whether it stays is ROADMAP D5/S8.

``flash_attention(..., interpret=True)`` runs the same kernel through the
pallas interpreter on CPU — that is how tests cover it without a TPU.
:func:`dense_attention` is the XLA oracle both compare against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def dense_attention(q, k, v, causal: bool = True):
    """The XLA oracle: the O(S²) score matrix, materialized."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        S = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                 scale: float):
    block_q = q_ref.shape[2]
    D = q_ref.shape[3]
    S = k_ref.shape[2]
    qi = pl.program_id(2)

    q = q_ref[0, 0] * scale                       # (bq, D)

    m0 = jnp.full((block_q, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, D), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(ki * block_k, block_k), :]   # (bk, D)
        v_blk = v_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bq, bk)
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(k_pos <= q_pos, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # causal: K blocks past this Q block's diagonal contribute nothing
    n_k = ((qi + 1) * block_q + block_k - 1) // block_k if causal \
        else S // block_k
    _, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Exact attention, O(S) memory. q/k/v: (B, H, S, D) -> (B, H, S, D)."""
    B, H, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide "
                         f"seq {S}")
    scale = 1.0 / (D ** 0.5)
    grid = (B, H, S // block_q)
    kernel = functools.partial(_attn_kernel, block_k=block_k, causal=causal,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
