"""Plain reference of a Jamba-shaped language model (``model_type: jamba``;
here AI21-Jamba2-3B), and its weights.

Pre-norm residual blocks with RMS norms (gain, eps from the config), a tied
head. Layer ``i`` is an attention layer where ``i % attn_layer_period ==
attn_layer_offset`` and a state-space (Mamba-1) layer otherwise; every layer
is ``x += mixer(RMSNorm(x))`` then ``x += W_down(silu(W_gate h) * W_up h)``
with ``h = RMSNorm(x)`` (``num_experts`` is 1: every MLP is dense).

* attention layer: ``q = h W_q`` (``heads`` of ``head_dim = hidden /
  heads``), ``k = h W_k``, ``v = h W_v`` (``kv_heads`` of ``head_dim``), no
  bias, no rotary or learned positions; query head ``n`` reads key/value
  head ``n // (heads / kv_heads)``; scores ``q . k / sqrt(head_dim)``; the
  query at position ``i`` sees key ``j`` iff ``j <= i``; softmax; ``(A v)
  W_o``;
* state-space layer (``Di = mamba_expand * hidden``, ``N = mamba_d_state``,
  ``K = mamba_d_conv``, ``R = mamba_dt_rank``): ``[u, z] = h W_in``; ``u_t
  <- silu(sum_k w_k u_(t-K+1+k) + b_conv)`` per channel (inputs before the
  sequence are zero); ``[d, B, C] = u W_x`` (``R``, ``N``, ``N`` values),
  each RMS-normalised with its own gain; ``dt = softplus(d W_dt + b_dt)``;
  ``A = -exp(A_log)`` (``Di x N``); per position ``h_t = exp(dt_t A) *
  h_(t-1) + (dt_t u_t) (x) B_t`` from ``h = 0``, ``y_t = h_t C_t + D *
  u_t``; the mixer's output is ``(y * silu(z)) W_out``. The recurrence is a
  ``lax.scan`` over the positions of one sequence.

Departures from the published code, each also in the configuration's
``assumed``:

* the norms of ``dt``, ``B`` and ``C`` are the published jamba code's; the
  config has no key for them;
* no positions in the attention layers: the config has no rope key;
* attention is computed a block of queries at a time (all keys at once), so
  that a context of thousands of positions fits: the same sums. A sequence's
  ``(S, Di)`` rows of a state layer fit whole at the serving limit, so the
  scan runs over the sequence in one piece.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, no cache,
no kernel, nothing imported from the program. Weights are the bfloat16
values the program was handed, widened, made layer by layer from the seed so
that one layer is resident at a time: matrices by ``lib/weights.py`` (odd
8-bit integers times powers of two, std 0.02; the conv's taps std 0.33);
the state layers' constants by the published initialisation, rounded to
bfloat16 once: ``A_log = log(1..N)`` a channel, ``b_dt`` such that
``softplus(b_dt)`` is log-uniform in 0.001-0.1 from the seed, ``D`` one (a
seeded Gaussian ``A_log`` would make states that vanish or blow up over
thousands of steps). ``quant`` puts the reference into a lower precision for
the control runs: the matrices, the attention layers' cached lines and the
conv's cached inputs pass through int8 or fp8 and back.
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
CONV_STD = 0.33    # the conv's taps: uniform in +-1/sqrt(K) has std 0.29
DT_RANGE = (1e-3, 1e-1)  # softplus(b_dt), log-uniform

Sizes = collections.namedtuple(
    "Sizes", "vocab hidden layers heads kv_heads head_dim ffn d_inner "
             "d_state d_conv dt_rank eps positions attention std")


def sizes(config: dict) -> Sizes:
    """The sizes this reference needs, under the source's own key names."""
    layers = config["num_hidden_layers"]
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return Sizes(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=layers, heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        ffn=config["intermediate_size"],
        d_inner=config["mamba_expand"] * config["hidden_size"],
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        dt_rank=config["mamba_dt_rank"], eps=config["rms_norm_eps"],
        positions=config["max_position_embeddings"],
        attention=tuple(i % period == offset for i in range(layers)),
        # a rehearsal's widths are a hundredth of the published ones: at std
        # 0.02 its layers would add nothing to the embedding, and the tied
        # head would repeat the last token whatever the layers did
        std=config.get("weight_std", WEIGHT_STD))


def _e0(std: float) -> int:
    return std_exponent(std)


def table_weights(key, sz: Sizes, dtype):
    """The embedding ``(V, D)``: the head is tied to it."""
    return exact_normalish(jax.random.fold_in(key, 0),
                           (sz.vocab, sz.hidden), _e0(sz.std), dtype)


def _mlp(keys, sz: Sizes, dtype):
    d, f, e0 = sz.hidden, sz.ffn, _e0(sz.std)
    return {"w_gate": exact_normalish(keys[0], (d, f), e0, dtype),
            "w_up": exact_normalish(keys[1], (d, f), e0, dtype),
            "w_down": exact_normalish(keys[2], (f, d), e0, dtype)}


def attention_weights(key, layer, sz: Sizes, dtype):
    """An attention layer's matrices, by the program's names. ``layer`` may
    be traced."""
    d, e0 = sz.hidden, _e0(sz.std)
    wide, narrow = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    k = jax.random.split(jax.random.fold_in(key, layer + 1), 8)
    ones = jnp.ones((d,), dtype)
    return {"ln1": ones, "ln2": ones, "mlp": _mlp(k[4:7], sz, dtype),
            "wq": exact_normalish(k[0], (d, wide), e0, dtype),
            "wk": exact_normalish(k[1], (d, narrow), e0, dtype),
            "wv": exact_normalish(k[2], (d, narrow), e0, dtype),
            "wo": exact_normalish(k[3], (wide, d), e0, dtype)}


def state_weights(key, layer, sz: Sizes, dtype):
    """A state-space layer's parameters, by the program's names and in its
    layout (``a_log (N, Di)``: channels last, the published ``(Di, N)``
    transposed). ``layer`` may be traced."""
    d, di, n, r = sz.hidden, sz.d_inner, sz.d_state, sz.dt_rank
    e0 = _e0(sz.std)
    k = jax.random.split(jax.random.fold_in(key, layer + 1), 12)
    ones = jnp.ones((d,), dtype)
    lo, hi = (math.log(v) for v in DT_RANGE)
    step = jnp.exp(jax.random.uniform(k[7], (di,), jnp.float32, lo, hi))
    mixer = {
        "w_in": exact_normalish(k[0], (d, 2 * di), e0, dtype),
        "conv_w": exact_normalish(k[1], (sz.d_conv, di), _e0(CONV_STD),
                                  dtype),
        "conv_b": exact_normalish(k[2], (di,), e0, dtype),
        "w_x": exact_normalish(k[3], (di, r + 2 * n), e0, dtype),
        "dt_norm": jnp.ones((r,), dtype), "b_norm": jnp.ones((n,), dtype),
        "c_norm": jnp.ones((n,), dtype),
        "w_dt": exact_normalish(k[4], (r, di), e0, dtype),
        # softplus's inverse; both rounded to bfloat16 once, whatever the
        # serving type: the numbers the reference widens
        "b_dt": jnp.log(jnp.expm1(step)).astype(jnp.bfloat16).astype(dtype),
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[:, None], (n, di)).astype(
                jnp.bfloat16).astype(dtype),
        "d": jnp.ones((di,), dtype),
        "w_out": exact_normalish(k[5], (di, d), e0, dtype)}
    return {"ln1": ones, "ln2": ones, "mlp": _mlp(k[8:11], sz, dtype),
            "mixer": mixer}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _tables(key, sz, dtype):
    return table_weights(key, sz, dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(key, li, attention, sz, dtype):
    make = attention_weights if attention else state_weights
    return make(key, li, sz, dtype)


def program_params(key, sz: Sizes, dtype):
    """The whole parameter tree in the program's layout and serving type:
    one compiled call per layer, so that the temporaries of one layer's
    making (not of all) lie beside the weights."""
    blocks = [_layer(key, jnp.int32(li), sz.attention[li], sz, dtype)
              for li in range(sz.layers)]
    return {"embed": _tables(key, sz, dtype), "blocks": blocks,
            "out_norm": jnp.ones((sz.hidden,), dtype)}


def _rms(x, gain, eps):
    return x * gain / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def attention(h, w, sz: Sizes, q8=None):
    """Grouped-query attention without positions over one sequence's normed
    rows ``h (S, D)``, a block of queries at a time."""
    s = h.shape[0]
    q = (h @ w["wq"]).reshape(s, sz.heads, sz.head_dim)
    k, v = h @ w["wk"], h @ w["wv"]
    if q8 is not None:  # the cache: one scale per position and line
        k, v = q8(k, -1), q8(v, -1)
    k = k.reshape(s, sz.kv_heads, sz.head_dim)
    v = v.reshape(s, sz.kv_heads, sz.head_dim)
    group = sz.heads // sz.kv_heads
    rows = math.gcd(s, 256)  # queries a block
    q = q.reshape(s // rows, rows, sz.kv_heads, group, sz.head_dim)
    keys = jnp.arange(s)

    def one(args):
        qb, first = args
        at = first + jnp.arange(rows)
        att = jnp.einsum("qkgd,ckd->kgqc", qb, k) / math.sqrt(sz.head_dim)
        seen = keys[None, :] <= at[:, None]
        att = jax.nn.softmax(jnp.where(seen[None, None], att, -1e30), -1)
        return jnp.einsum("kgqc,ckd->qkgd", att, v)

    o = jax.lax.map(one, (q, jnp.arange(s // rows) * rows))
    return o.reshape(s, sz.heads * sz.head_dim) @ w["wo"]


def state_space(h, m, sz: Sizes, q8=None):
    """The Mamba-1 mixer over one sequence's normed rows ``h (S, D)``."""
    s, di, n, r = h.shape[0], sz.d_inner, sz.d_state, sz.dt_rank
    u, z = jnp.split(h @ m["w_in"], 2, axis=-1)
    if q8 is not None:  # the conv's cached inputs: one scale a position
        u = q8(u, -1)
    padded = jnp.concatenate([jnp.zeros((sz.d_conv - 1, di)), u], axis=0)
    u = jax.nn.silu(sum(m["conv_w"][k] * padded[k:k + s]
                        for k in range(sz.d_conv)) + m["conv_b"])
    dbc = u @ m["w_x"]
    dt = _rms(dbc[:, :r], m["dt_norm"], sz.eps)
    b = _rms(dbc[:, r:r + n], m["b_norm"], sz.eps)
    c = _rms(dbc[:, r + n:], m["c_norm"], sz.eps)
    dt = jax.nn.softplus(dt @ m["w_dt"] + m["b_dt"])
    a = -jnp.exp(m["a_log"].T)  # (Di, N), as published

    def one(state, row):
        dt_t, u_t, b_t, c_t = row
        state = (jnp.exp(dt_t[:, None] * a) * state
                 + (dt_t * u_t)[:, None] * b_t[None, :])
        return state, state @ c_t + m["d"] * u_t

    _, y = jax.lax.scan(one, jnp.zeros((di, n)), (dt, u, b, c))
    return (y * jax.nn.silu(z)) @ m["w_out"]


MATRICES = frozenset({"wq", "wk", "wv", "wo", "w_in", "w_x", "w_dt",
                      "w_out", "w_gate", "w_up", "w_down"})


def _widen(tree, q8):
    """Float32 copies; the matrices through the lower precision, one scale
    per output channel."""
    def one(path, v):
        v = v.astype(jnp.float32)
        return q8(v, 0) if q8 is not None and path[-1].key in MATRICES else v
    return jax.tree_util.tree_map_with_path(one, tree)


def block(x, w, attends: bool, sz: Sizes, quant: str = "none"):
    """One pre-norm block over one sequence: x (S, D) float32."""
    q8 = QUANT[quant]
    w = _widen(w, q8)
    h = _rms(x, w["ln1"], sz.eps)
    x = x + (attention(h, w, sz, q8) if attends
             else state_space(h, w["mixer"], sz, q8))
    h, m = _rms(x, w["ln2"], sz.eps), w["mlp"]
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def head_logits(x_rows, out_gain, embed, sz: Sizes, quant: str = "none"):
    """Final norm and the tied head on chosen rows: (n, D) -> (n, V)."""
    e = embed.astype(jnp.float32)
    if QUANT[quant] is not None:
        e = QUANT[quant](e, -1)
    return _rms(x_rows, out_gain, sz.eps) @ e.T


def logits_for(key, sz: Sizes, tokens, rows, quants=("none",)) -> dict:
    """Reference logits of a batch of sequences at chosen rows, layer by
    layer so that one layer's weights are resident at a time, one sequence
    at a time inside a layer. ``tokens`` (K, S) int32 and ``rows`` (K, n)
    int32 are padded to fixed lengths by the caller (padding follows the
    real tokens, and every layer is causal), so every call reuses one
    compiled program per function. Returns ``{quant: (K, n, V) float32}``
    on the host: at this vocabulary a batch's logits are gigabytes, and the
    device holds one sequence's at a time."""
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        embed = _tables(key, sz, jnp.bfloat16)
        xs = {q: embed[tokens].astype(jnp.float32) for q in quants}
        for li in range(sz.layers):
            w = _layer(key, jnp.int32(li), sz.attention[li], sz,
                       jnp.bfloat16)
            for q in quants:
                xs[q] = _block(xs[q], w, sz.attention[li], sz, q)
        del w
        gain = jnp.ones((sz.hidden,), jnp.float32)
        out = {}
        for q in quants:
            picked = jnp.take_along_axis(xs[q], rows[:, :, None], axis=1)
            out[q] = np.stack([np.asarray(_head(one, gain, embed, sz, q))
                               for one in picked])
        return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block(xs, w, attends, sz, quant):
    return jax.lax.map(lambda one: block(one, w, attends, sz, quant), xs)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x_rows, gain, embed, sz, quant):
    return head_logits(x_rows, gain, embed, sz, quant)
