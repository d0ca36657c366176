"""Plain reference of an Ouro-shaped language model (``model_type: ouro``;
here Ouro-2.6B, ByteDance; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741), and its weights.

Sizes (the published ``config.json``): hidden 2048, 48 layers, 16 query
heads over 16 key/value heads of 128 (full multi-head, no grouping), a
SwiGLU MLP of 5632 (``hidden_act`` silu), vocabulary 49152 with an untied
head, ``rms_norm_eps`` 1e-6, rotary theta 1e6 over the whole head dimension
in half-split pairs, ``rope_scaling`` null, no sliding window
(``use_sliding_window`` false, ``layer_types`` all ``full_attention``),
65536 positions, no bias in attention or MLP; ``total_ut_steps`` 4,
``early_exit_threshold`` 1.

One layer, sandwich norms (four RMS norms with gains ``g1..g4``)::

    h <- h + N_g2(Attn(N_g1(h)))
    h <- h + N_g4(W_down(silu(W_gate u) * W_up u)),   u = N_g3(h)

``Attn``: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``, queries and keys
rotated (pair ``(i, i + 64)`` of a head by ``pos * theta ** (-2 i / 128)``),
query head ``n`` reads key/value head ``n // (heads / kv_heads)``, scores
``q . k * head_dim ** -0.5``, the query at position ``i`` sees key ``j`` iff
``j <= i``, softmax, ``(A v) W_o``.

The loop: ``h_0 = E[tokens]``. For pass ``t = 1 .. 4``: the 48 layers in
order over ``h_(t-1)`` with the same weights every pass, then ``h_t =
N_gf(.)`` (the final norm closes EVERY pass, and its output is what the
next pass starts from), and the gate ``lambda_t = sigmoid(w_g . h_t + b_g)``
(one ``Linear(2048, 1)`` on the model, shared by the passes). No cache
here: pass ``t`` of layer ``l`` attends over the keys and values that pass
``t`` of layer ``l`` computes at the earlier positions of the same full
forward, which is what a cache line for every pass of every layer holds
(cache index ``(t - 1) * 48 + l``, 192 a token).

The exit rule (the published default path, no weighted mixing of logits):
``p_t = lambda_t * prod_(s<t)(1 - lambda_s)`` for ``t < 4``, ``p_4 =
prod_(s<4)(1 - lambda_s)``; ``c_t = sum_(s<=t) p_s``; a token's hidden state
is ``h_T`` with ``T`` the first ``t`` where ``c_t >= early_exit_threshold``,
the last pass if none; logits ``= W_head h_T``. All four passes run for every
token whatever ``T`` is. At the published threshold 1 that is the last pass
unless a gate saturates.

Departures and assumptions, each also in the configuration's ``assumed``:
the equations above are ISSUE 43's reading of the published
``modeling_ouro.py`` (no network here: where the sandwich norms sit, that
the final norm closes every pass, where the gate sits and the exit rule
cannot be checked against the file); attention is computed a block of
queries at a time (all keys at once): the same sums; a sequence at a time,
a layer at a time, so that one layer's weights are resident.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, no cache,
no kernel, nothing imported from the program. Weights are the bfloat16
values the program was handed, widened, made layer by layer from the seed
by ``lib/weights.py`` (odd 8-bit integers times powers of two, std 0.02:
exact in bfloat16 and float32); norm gains one, the gate's weight seeded
and its bias zero. ``quant`` puts the reference into a lower precision for
the control runs: the matrices and the cached lines (rotated keys, values)
pass through int8 or fp8 and back.
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.lower_precision import QUANT
from benchmark.lib.weights import exact_normalish, std_exponent

WEIGHT_STD = 0.02  # every matrix, unless the sizes say otherwise

Sizes = collections.namedtuple(
    "Sizes", "vocab hidden layers heads kv_heads head_dim ffn eps theta "
             "passes threshold positions std")


def sizes(config: dict) -> Sizes:
    """The sizes this reference needs, under the source's own key names."""
    return Sizes(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        ffn=config["intermediate_size"], eps=config["rms_norm_eps"],
        theta=float(config["rope_theta"]),
        passes=config["total_ut_steps"],
        threshold=float(config["early_exit_threshold"]),
        positions=config["max_position_embeddings"],
        # a rehearsal's widths are a hundredth of the published ones: at std
        # 0.02 the gate and the head would say the same of every token
        std=config.get("weight_std", WEIGHT_STD))


def parameters(sz: Sizes) -> int:
    """The model's parameter count."""
    d = sz.hidden
    layer = (2 * d * sz.heads * sz.head_dim + 2 * d * sz.kv_heads
             * sz.head_dim + 3 * d * sz.ffn + 4 * d)
    return sz.layers * layer + 2 * sz.vocab * d + d + d + 1


def _e0(std: float) -> int:
    return std_exponent(std)


def table_weights(key, sz: Sizes, dtype):
    """What is not a layer's: the embedding ``(V, D)``, the untied head
    ``(D, V)``, the gate's weight ``(D,)`` and bias."""
    e0 = _e0(sz.std)
    return {"embed": exact_normalish(jax.random.fold_in(key, 0),
                                     (sz.vocab, sz.hidden), e0, dtype),
            "head": exact_normalish(
                jax.random.fold_in(key, sz.layers + 1),
                (sz.hidden, sz.vocab), e0, dtype),
            "gate_w": exact_normalish(
                jax.random.fold_in(key, sz.layers + 2), (sz.hidden,), e0,
                dtype),
            "gate_b": jnp.zeros((), dtype),
            "out_norm": jnp.ones((sz.hidden,), dtype)}


def layer_weights(key, layer, sz: Sizes, dtype):
    """A layer's matrices and gains, by the program's names. ``layer`` may
    be traced."""
    d, f, e0 = sz.hidden, sz.ffn, _e0(sz.std)
    wide, narrow = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    k = jax.random.split(jax.random.fold_in(key, layer + 1), 8)
    ones = jnp.ones((d,), dtype)
    return {"ln1": ones, "ln2": ones, "ln3": ones, "ln4": ones,
            "wq": exact_normalish(k[0], (d, wide), e0, dtype),
            "wk": exact_normalish(k[1], (d, narrow), e0, dtype),
            "wv": exact_normalish(k[2], (d, narrow), e0, dtype),
            "wo": exact_normalish(k[3], (wide, d), e0, dtype),
            "mlp": {"w_gate": exact_normalish(k[4], (d, f), e0, dtype),
                    "w_up": exact_normalish(k[5], (d, f), e0, dtype),
                    "w_down": exact_normalish(k[6], (f, d), e0, dtype)}}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _tables(key, sz, dtype):
    return table_weights(key, sz, dtype)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(key, li, sz, dtype):
    return layer_weights(key, li, sz, dtype)


def program_params(key, sz: Sizes, dtype):
    """The whole parameter tree in the program's layout and serving type:
    one compiled call per layer, so that the temporaries of one layer's
    making (not of all) lie beside the weights."""
    blocks = [_layer(key, jnp.int32(li), sz, dtype)
              for li in range(sz.layers)]
    return {**_tables(key, sz, dtype), "blocks": blocks}


def _rms(x, gain, eps):
    return x * gain / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotate(x, sz: Sizes):
    """Rotary positions on half-split pairs: ``x (S, heads, head_dim)`` at
    positions ``0 .. S - 1``."""
    half = sz.head_dim // 2
    freq = sz.theta ** (-2.0 * np.arange(half) / sz.head_dim)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, w, sz: Sizes, q8=None):
    """Causal attention with rotary positions over one sequence's normed
    rows ``h (S, D)``, a block of queries at a time."""
    s = h.shape[0]
    q = _rotate((h @ w["wq"]).reshape(s, sz.heads, sz.head_dim), sz)
    k = _rotate((h @ w["wk"]).reshape(s, sz.kv_heads, sz.head_dim), sz)
    v = h @ w["wv"]
    if q8 is not None:  # the cache: one scale per position and line
        k = q8(k.reshape(s, -1), -1).reshape(k.shape)
        v = q8(v, -1)
    v = v.reshape(s, sz.kv_heads, sz.head_dim)
    group = sz.heads // sz.kv_heads
    rows = math.gcd(s, 256)  # queries a block
    q = q.reshape(s // rows, rows, sz.kv_heads, group, sz.head_dim)
    keys = jnp.arange(s)

    def one(args):
        qb, first = args
        at = first + jnp.arange(rows)
        att = jnp.einsum("qkgd,ckd->kgqc", qb, k) * sz.head_dim ** -0.5
        seen = keys[None, :] <= at[:, None]
        att = jax.nn.softmax(jnp.where(seen[None, None], att, -1e30), -1)
        return jnp.einsum("kgqc,ckd->qkgd", att, v)

    o = jax.lax.map(one, (q, jnp.arange(s // rows) * rows))
    return o.reshape(s, sz.heads * sz.head_dim) @ w["wo"]


MATRICES = frozenset({"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                      "head"})


def _widen(tree, q8):
    """Float32 copies; the matrices through the lower precision, one scale
    per output channel."""
    def one(path, v):
        v = v.astype(jnp.float32)
        return q8(v, 0) if q8 is not None and path[-1].key in MATRICES else v
    return jax.tree_util.tree_map_with_path(one, tree)


def block(x, w, sz: Sizes, quant: str = "none"):
    """One sandwich-norm layer over one sequence: x (S, D) float32."""
    q8 = QUANT[quant]
    w = _widen(w, q8)
    x = x + _rms(attention(_rms(x, w["ln1"], sz.eps), w, sz, q8), w["ln2"],
                 sz.eps)
    u, m = _rms(x, w["ln3"], sz.eps), w["mlp"]
    y = (jax.nn.silu(u @ m["w_gate"]) * (u @ m["w_up"])) @ m["w_down"]
    return x + _rms(y, w["ln4"], sz.eps)


def close_pass(x, tables, state, last: bool, sz: Sizes):
    """What ends a pass, for ``x (K, S, D)``: the final norm, the gate, the
    exit rule's running sums. ``state`` = (chosen rows, the pass each row
    left at or 0, ``c``, the running product of ``1 - lambda``, the passes
    closed so far) → ``(h_t, state')``."""
    chosen, left_at, c, survive, t = state
    h = _rms(x, tables["out_norm"].astype(jnp.float32), sz.eps)
    lam = jax.nn.sigmoid(h @ tables["gate_w"].astype(jnp.float32)
                         + tables["gate_b"].astype(jnp.float32))
    c = c + (survive if last else lam * survive)
    leaves = (left_at == 0) & ((c >= sz.threshold) | last)
    return h, (jnp.where(leaves[..., None], h, chosen),
               jnp.where(leaves, t + 1, left_at), c,
               survive * (1.0 - lam), t + 1)


def forward(key, sz: Sizes, tokens, quants=("none",)) -> dict:
    """The full forward of a batch of sequences ``tokens (K, S)``: ``{quant:
    (each row's hidden state at the pass it left (K, S, D), that pass
    (K, S) int32, 1-based)}``, on the device. Pass by pass, layer by layer,
    so that one layer's weights are resident at a time."""
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        tables = _tables(key, sz, jnp.bfloat16)
        x = tables["embed"][tokens].astype(jnp.float32)
        xs = dict.fromkeys(quants, x)
        states = dict.fromkeys(quants, (
            jnp.zeros_like(x), jnp.zeros(x.shape[:2], jnp.int32),
            jnp.zeros(x.shape[:2], jnp.float32),
            jnp.ones(x.shape[:2], jnp.float32), jnp.int32(0)))
        for t in range(sz.passes):
            for li in range(sz.layers):
                # made once a pass-layer, whatever the precisions asked for
                w = _layer(key, jnp.int32(li), sz, jnp.bfloat16)
                for q in quants:
                    xs[q] = _block(xs[q], w, sz, q)
            for q in quants:
                xs[q], states[q] = _close(xs[q], tables, states[q],
                                          t == sz.passes - 1, sz)
        return {q: (states[q][0], states[q][1]) for q in quants}


def head_logits(x_rows, head, sz: Sizes, quant: str = "none"):
    """The untied head on chosen rows (the final norm is in them): (n, D)
    -> (n, V)."""
    w = head.astype(jnp.float32)
    if QUANT[quant] is not None:
        w = QUANT[quant](w, 0)
    return x_rows @ w


def logits_for(key, sz: Sizes, tokens, rows, quants=("none",)) -> dict:
    """Reference logits of a batch of sequences at chosen rows. ``tokens``
    (K, S) int32 and ``rows`` (K, n) int32 are padded to fixed lengths by
    the caller (padding follows the real tokens, and every layer is
    causal), so every call reuses one compiled program per function.
    Returns ``{quant: (K, n, V) float32}`` on the host: the device holds
    one sequence's logits at a time."""
    rows = jnp.asarray(rows)
    hidden = forward(key, sz, tokens, quants)
    with jax.default_matmul_precision("highest"):
        head = _tables(key, sz, jnp.bfloat16)["head"]
        out = {}
        for q in quants:
            picked = jnp.take_along_axis(hidden[q][0], rows[:, :, None],
                                         axis=1)
            out[q] = np.stack([np.asarray(_head(one, head, sz, q))
                               for one in picked])
        return out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(xs, w, sz, quant):
    return jax.lax.map(lambda one: block(one, w, sz, quant), xs)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _close(x, tables, state, last, sz):
    return close_pass(x, tables, state, last, sz)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(x_rows, head, sz, quant):
    return head_logits(x_rows, head, sz, quant)
