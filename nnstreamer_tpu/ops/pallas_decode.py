"""Pallas TPU kernel for single-token cached-decode attention.

The KV-cache decode step is the LM serving hot op: one query token
attends against the whole cache — pure HBM bandwidth, no reuse. XLA's
default lowering materializes the masked (B, H, 1, max_seq) score tensor
and reads the cache twice (scores pass + weighted-sum pass); this kernel
streams K/V blocks through VMEM once with the online-softmax recurrence
(same math as ops/pallas_attention.py, degenerate q-block of 1) and
bounds the loop to the valid prefix, so positions past ``pos`` are never
read at all — at long max_seq with a short prefix that is most of the
cache.

Opt-in via ``TransformerConfig(decode_attn="pallas")`` — the XLA path
stays the default and the equivalence oracle (test_pallas_ops pins the
kernel against it; test_decoding pins generate() token-exactness).
``interpret=True`` runs the kernel on CPU — how tests cover it without
a TPU. :func:`dense_cached_decode` is the XLA oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def dense_cached_decode(q, ck, cv, pos):
    """The XLA oracle: decode_step's masked dense path."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    T = ck.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, ck) * scale
    visible = (jnp.arange(T) <= pos)[None, None, None, :]
    s = jnp.where(visible, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), cv)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, block_k: int,
                   scale: float):
    D = q_ref.shape[3]
    pos = pos_ref[0]

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (1, D)

    m0 = jnp.full((1, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((1, 1), jnp.float32)
    a0 = jnp.zeros((1, D), jnp.float32)

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(ki * block_k, block_k), :]   # (bk, D)
        v_blk = v_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (1, bk)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        valid = k_pos <= pos
        s = jnp.where(valid, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # only blocks intersecting the valid prefix [0, pos] are ever read
    n_k = (pos + block_k) // block_k
    _, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def cached_decode_attention(q, k, v, pos, block_k: int = 128,
                            interpret: bool = False):
    """One-token attention against a cache prefix.

    q: (B, H, 1, D); k/v: (B, H, T, D) caches; ``pos`` scalar int32 —
    positions ``<= pos`` are attended (cache[pos] holds the current
    token's K/V, already written). Returns (B, H, 1, D).
    """
    B, H, _, D = q.shape
    T = k.shape[2]
    block_k = min(block_k, T)
    if T % block_k:
        raise ValueError(
            f"block_k {block_k} must divide the cache length {T}")
    scale = 1.0 / (D ** 0.5)
    kernel = functools.partial(_decode_kernel, block_k=block_k, scale=scale)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h: (0,)),
            pl.BlockSpec((1, 1, 1, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=interpret,
    )(pos_arr, q, k, v)
