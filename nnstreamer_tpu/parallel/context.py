"""Context (sequence) parallel attention: ring attention + Ulysses.

The reference has no attention/sequence concept (SURVEY.md §5.7) — its
axis-wise streaming primitives (``tensor_aggregator`` windows,
``tensor_merge``/``split``) are the closest analog. For a TPU-native
framework long context is first-class, so this module provides the two
standard context-parallel attention schemes, both expressed over a mesh
axis (conventionally ``"sp"``) with XLA collectives riding ICI:

* **Ring attention** (`ring_attention`): every device holds a Q block and
  rotates K/V blocks around the ring with ``lax.ppermute``, accumulating a
  numerically-stable online softmax (flash-attention style running max /
  denominator).  Communication is neighbor-to-neighbor — the ICI-friendly
  pattern — and overlaps naturally with the per-block matmuls.
* **Ulysses** (`ulysses_attention`): ``lax.all_to_all`` reshards from
  sequence-sharded to head-sharded, runs exact local attention per head
  group, and reshards back.  Requires ``heads % sp == 0``.

Both are written to run **inside** ``shard_map`` (they reference a mesh
axis name); `make_context_attention` wraps either in ``shard_map`` over a
concrete mesh so callers (models/transformer.py) can drop it in where a
plain attention call would go.
"""
from __future__ import annotations

from functools import partial
from typing import Optional


def _online_block(q, k, v, bias_mask, m, l, o, scale):
    """One blockwise online-softmax accumulation step.

    q:(B,H,Sq,D) k,v:(B,H,Sk,D) bias_mask:(Sq,Sk) bool (True = attend).
    m:(B,H,Sq,1) running max, l: running denom, o: running numerator.
    """
    import jax.numpy as jnp

    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    scores = jnp.where(bias_mask[None, None], scores, -1e30)
    m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new) * bias_mask[None, None]
    alpha = jnp.exp(m - m_new)
    l = l * alpha + p.sum(axis=-1, keepdims=True)
    o = o * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l, o


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True):
    """Blockwise ring attention over mesh axis ``axis_name``.

    Must be called inside ``shard_map``.  q/k/v are the *local* sequence
    blocks ``(B, H, S_local, D)``; the global sequence is the concatenation
    of blocks in axis order.  Returns the local output block.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    r = lax.axis_index(axis_name)
    B, H, Sl, D = q.shape
    scale = 1.0 / (D ** 0.5)

    m = jnp.full((B, H, Sl, 1), -1e30, q.dtype)
    l = jnp.zeros((B, H, Sl, 1), q.dtype)
    o = jnp.zeros((B, H, Sl, D), q.dtype)

    # device j receives from (j+1)%n: after t rotations we hold block (r+t)%n
    perm = [((j + 1) % n, j) for j in range(n)]
    rows = jnp.arange(Sl)
    cols = jnp.arange(Sl)

    def body(t, carry):
        k_t, v_t, m, l, o = carry
        k_idx = (r + t) % n
        if causal:
            mask = (k_idx * Sl + cols)[None, :] <= (r * Sl + rows)[:, None]
        else:
            mask = jnp.ones((Sl, Sl), bool)
        m, l, o = _online_block(q, k_t, v_t, mask, m, l, o, scale)
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        return k_t, v_t, m, l, o

    carry = (k, v, m, l, o)
    # n is static (mesh size); unrolled python loop keeps each block's
    # matmul + ppermute visible to XLA for comm/compute overlap.
    for t in range(n):
        carry = body(t, carry)
    _, _, m, l, o = carry
    return o / jnp.maximum(l, 1e-30)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = True):
    """Ulysses (DeepSpeed-style) all-to-all attention over ``axis_name``.

    Must be called inside ``shard_map`` with local blocks (B, H, S_local, D)
    and ``H % axis_size == 0``.  all_to_all swaps the shard axis from
    sequence to heads, local attention is exact over the full sequence,
    then the inverse all_to_all restores sequence sharding.
    """
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    B, H, Sl, D = q.shape
    if H % n:
        raise ValueError(f"ulysses needs heads ({H}) divisible by sp ({n})")
    scale = 1.0 / (D ** 0.5)

    def to_heads(x):  # (B,H,Sl,D) -> (B,H/n,S,D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(x):    # (B,H/n,S,D) -> (B,H,Sl,D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    S = qh.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    att = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    att = att / att.sum(axis=-1, keepdims=True)
    oh = jnp.einsum("bhqk,bhkd->bhqd", att, vh)
    return to_seq(oh)


def make_context_attention(mesh, impl: str = "ring", causal: bool = True,
                           batch_axis: str = "dp", head_axis: str = "tp",
                           seq_axis: str = "sp"):
    """Wrap ring/ulysses attention in shard_map over ``mesh``.

    Returns ``attn(q, k, v)`` taking global (B, H, S, D) arrays (logically
    global — physically sharded B over dp, H over tp, S over sp) and
    returning the same-shaped output.  Drop-in for a full attention call
    inside a jitted program.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if impl == "ring":
        fn = partial(ring_attention, axis_name=seq_axis, causal=causal)
    elif impl == "ulysses":
        fn = partial(ulysses_attention, axis_name=seq_axis, causal=causal)
    else:
        raise ValueError(f"unknown context-attention impl '{impl}'")

    spec = P(batch_axis, head_axis, seq_axis, None)
    return shard_map(
        lambda q, k, v: fn(q, k, v),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
