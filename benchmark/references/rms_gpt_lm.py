"""Plain reference of the served language model, and its weights.

The block is the repo's GPT-2-shaped demonstrator at OPT-1.3B's sizes:
token embedding + learned positions, pre-norm blocks of full multi-head
causal attention and a ReLU MLP, a final norm and a tied output head.
Departures from OPT (arXiv:2205.01068), listed in the configuration under
``assumed``: RMS norm with a gain and no bias where OPT has LayerNorm, no
biases on the linear layers, no position offset of 2.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, no cache,
no batching tricks, nothing imported from the program. Weights are the
bfloat16 values the program was handed (``lib/weights.py``), widened.
``quant`` puts the reference into a lower precision for the control runs:
weights, keys and values pass through int8 or fp8 and back.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from benchmark.lib.lower_precision import QUANT
from benchmark.lib.weights import exact_normalish, std_exponent

WEIGHT_STD = 0.02

Sizes = collections.namedtuple(
    "Sizes", "vocab hidden layers heads ffn positions")


def sizes(config: dict) -> Sizes:
    """The sizes this reference needs, under the source's own key names."""
    return Sizes(vocab=config["vocab_size"], hidden=config["hidden_size"],
                 layers=config["num_hidden_layers"],
                 heads=config["num_attention_heads"], ffn=config["ffn_dim"],
                 positions=config["max_position_embeddings"])


def _e0() -> int:
    return std_exponent(WEIGHT_STD)


def table_weights(key, sz: Sizes, dtype):
    """Embedding and position tables: ``(embed (V, D), pos (S, D))``."""
    k_embed, k_pos = jax.random.split(jax.random.fold_in(key, 0))
    return (exact_normalish(k_embed, (sz.vocab, sz.hidden), _e0(), dtype),
            exact_normalish(k_pos, (sz.positions, sz.hidden), _e0(), dtype))


def layer_weights(key, layer, sz: Sizes, dtype):
    """One block's matrices, by the program's names. ``layer`` may be
    traced (the program's weights are made under ``lax.map``)."""
    d, f = sz.hidden, sz.ffn
    k = jax.random.split(jax.random.fold_in(key, layer + 1), 4)
    e0 = _e0()
    return {"wqkv": exact_normalish(k[0], (d, 3 * d), e0, dtype),
            "wo": exact_normalish(k[1], (d, d), e0, dtype),
            "w1": exact_normalish(k[2], (d, f), e0, dtype),
            "w2": exact_normalish(k[3], (f, d), e0, dtype)}


def program_params(key, sz: Sizes, dtype):
    """The whole parameter tree in the program's layout and serving type,
    made in one traced call (norm gains are ones, as the program's own
    initialiser has them)."""
    embed, pos = table_weights(key, sz, dtype)
    stacked = jax.lax.map(
        lambda li: layer_weights(key, li, sz, dtype), jnp.arange(sz.layers))
    ones = jnp.ones((sz.hidden,), dtype)
    blocks = [{"ln1": ones, "ln2": ones,
               **{name: w[i] for name, w in stacked.items()}}
              for i in range(sz.layers)]
    return {"embed": embed, "pos": pos, "blocks": blocks, "out_norm": ones}



def _rms(x, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def embed_tokens(embed, pos, tokens):
    """tokens (S,) -> activations (S, D) in float32."""
    s = tokens.shape[0]
    return embed[tokens].astype(jnp.float32) + pos[:s].astype(jnp.float32)


def block(x, w, heads: int, quant: str = "none"):
    """One pre-norm block over one sequence: x (S, D) float32."""
    q8 = QUANT[quant]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if q8 is not None:  # one scale per output channel
        w = {k: q8(v, 0) for k, v in w.items()}
    s, d = x.shape
    dh = d // heads
    qkv = _rms(x) @ w["wqkv"]
    q, k, v = (t.reshape(s, heads, dh).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))
    if q8 is not None:  # the cache: one scale per head and position
        k, v = q8(k, -1), q8(v, -1)
    att = (q @ k.transpose(0, 2, 1)) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal[None], att, -1e30), axis=-1)
    x = x + (att @ v).transpose(1, 0, 2).reshape(s, d) @ w["wo"]
    return x + jax.nn.relu(_rms(x) @ w["w1"]) @ w["w2"]


def head_logits(x_rows, embed, quant: str = "none"):
    """Final norm and tied head on chosen rows: (n, D) -> (n, V)."""
    e = embed.astype(jnp.float32)
    if QUANT[quant] is not None:
        e = QUANT[quant](e, 1)
    return _rms(x_rows) @ e.T


def logits_for(key, sz: Sizes, tokens, rows, quants=("none",)) -> dict:
    """Reference logits of a batch of sequences at chosen rows, layer by
    layer so that one layer's weights are resident at a time. ``tokens``
    (K, S) int32 and ``rows`` (K, n) int32 are padded to fixed lengths by
    the caller (padding follows the real tokens, and attention is causal),
    so every call reuses one compiled program per function. Returns
    ``{quant: (K, n, V) float32}``."""
    with jax.default_matmul_precision("highest"):
        embed, pos = _tables(key, sz)
        x0 = _embed(embed, pos, tokens)
        xs = {q: x0 for q in quants}
        for li in range(sz.layers):
            w = _layer(key, li, sz)
            for q in quants:
                xs[q] = _block(xs[q], w, sz.heads, q)
        return {q: _head(jnp.take_along_axis(xs[q], rows[:, :, None], axis=1),
                         embed, q) for q in quants}


@functools.partial(jax.jit, static_argnums=(1,))
def _tables(key, sz):
    return table_weights(key, sz, jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(key, li, sz):
    return layer_weights(key, li, sz, jnp.bfloat16)


_embed = jax.jit(jax.vmap(embed_tokens, in_axes=(None, None, 0)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(x, w, heads, quant):
    return jax.vmap(lambda one: block(one, w, heads, quant))(x)


@functools.partial(jax.jit, static_argnums=(2,))
def _head(x_rows, embed, quant):
    return jax.vmap(lambda one: head_logits(one, embed, quant))(x_rows)
