"""Plain reference of a Mellum-shaped language model (``model_type: mellum``;
here Mellum2-12B-A2.5B), and its weights.

Pre-norm residual blocks with RMS norms (gain, eps from the config), no
biases, an untied head. Every block is the same:

* ``h = RMSNorm(x)``; ``q = h W_q`` (``heads`` of ``head_dim``), ``k = h W_k``,
  ``v = h W_v`` (``kv_heads`` of ``head_dim``); rotary positions on ``q`` and
  ``k`` over all of a head's dimensions, the frequencies by the layer's type
  (``rope_parameters``): ``sliding_attention`` layers ``f_i = theta^(-2i/d)``;
  ``full_attention`` layers YaRN, ``f_i / factor * ramp_i + f_i * (1 -
  ramp_i)`` with ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``low =
  max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)), d - 1)``,
  ``dim(r) = d ln(original / (2 pi r)) / (2 ln theta)``, and cosine and sine
  times ``attention_factor``;
* query head ``n`` reads key/value head ``n // (heads / kv_heads)``; scores
  ``q . k / sqrt(head_dim)``; the query at position ``i`` sees key ``j`` iff
  ``j <= i`` and, in a sliding layer, ``i - j < sliding_window``; softmax;
  ``x += (A v) W_o``;
* ``h = RMSNorm(x)``; ``p = softmax(h W_g)`` over all experts in float32; the
  ``num_experts_per_tok`` largest; their ``p`` renormalised to sum 1
  (``norm_topk_prob``); ``x += sum_e w_e W_down,e (silu(W_gate,e h) * W_up,e
  h)``, here a loop over the experts held (``experts_held``), every token
  through each with its weight for it (zero where it was not chosen). What
  experts held elsewhere would add is left out, as in the program.

Departures from the published code, each also in the configuration's
``assumed``:

* the head lies on this (first) pipeline stage so that tokens come out; in
  the deployment it lies on the last;
* softmax scoring, no q/k norm, no shared expert, no router bias or scale:
  the config has no key for any;
* attention is computed a block of queries at a time (all keys at once), so
  that a context of thousands of positions fits: the same sums.

Rotary positions rotate the half-split pairs ``(i, i + d/2)`` as the
published code does (``rotate_half``).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, no cache,
no batching tricks, nothing imported from the program. Weights are the
bfloat16 values the program was handed (``lib/weights.py``), widened, made
layer by layer from the seed so that one layer is resident at a time.
``quant`` puts the reference into a lower precision for the control runs:
weights and the cached lines pass through int8 or fp8 and back.
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

WEIGHT_STD = 0.02  # every matrix

Sizes = collections.namedtuple(
    "Sizes", "vocab hidden layers heads kv_heads head_dim expert_ffn "
             "experts top_k held eps norm_topk positions window sliding "
             "rope_full rope_sliding")


def rope_table(head_dim: int, rope: dict) -> tuple:
    """``(frequencies of the head_dim/2 pairs, attention factor)`` of one
    ``rope_parameters`` entry, as tuples of floats (float64 arithmetic)."""
    theta = float(rope["rope_theta"])
    freq = [theta ** (-2.0 * i / head_dim) for i in range(head_dim // 2)]
    if rope.get("rope_type", "default") == "default":
        return tuple(freq), 1.0
    original = rope["original_max_position_embeddings"]

    def dim(rotations):
        return (head_dim * math.log(original / (2 * math.pi * rotations))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), head_dim - 1)
    out = []
    for i, f in enumerate(freq):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / rope["factor"] * ramp + f * (1.0 - ramp))
    return tuple(out), float(rope["attention_factor"])


def sizes(config: dict) -> Sizes:
    """The sizes this reference needs, under the source's own key names."""
    layers = config["num_hidden_layers"]
    types = config["layer_types"][:layers]
    ropes = config["rope_parameters"]
    return Sizes(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=layers, heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        expert_ffn=config["moe_intermediate_size"],
        experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        held=tuple(config.get("experts_held")
                   or (0, config["num_experts"])),
        eps=config["rms_norm_eps"],
        norm_topk=bool(config["norm_topk_prob"]),
        positions=config["max_position_embeddings"],
        window=config["sliding_window"],
        sliding=tuple(t == "sliding_attention" for t in types),
        rope_full=rope_table(config["head_dim"], ropes["full_attention"]),
        rope_sliding=rope_table(config["head_dim"],
                                ropes["sliding_attention"]))


def _e0() -> int:
    return std_exponent(WEIGHT_STD)


def table_weights(key, sz: Sizes, dtype):
    """``(embed (V, D), head (D, V))``: the head is not tied."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    return (exact_normalish(k_embed, (sz.vocab, sz.hidden), _e0(), dtype),
            exact_normalish(k_head, (sz.hidden, sz.vocab), _e0(), dtype))


def layer_weights(key, layer, sz: Sizes, dtype):
    """One block's matrices, by the program's names. ``layer`` may be
    traced. The experts are the ones held (``sz.held``)."""
    d, e0 = sz.hidden, _e0()
    wide, narrow = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    held, f = sz.held[1], sz.expert_ffn
    k = jax.random.split(jax.random.fold_in(key, layer + 1), 8)
    ones = jnp.ones((d,), dtype)
    return {"ln1": ones, "ln2": ones,
            "wq": exact_normalish(k[0], (d, wide), e0, dtype),
            "wk": exact_normalish(k[1], (d, narrow), e0, dtype),
            "wv": exact_normalish(k[2], (d, narrow), e0, dtype),
            "wo": exact_normalish(k[3], (wide, d), e0, dtype),
            "router": exact_normalish(k[4], (d, sz.experts), e0, dtype),
            "experts": {
                "w_gate": exact_normalish(k[5], (held, d, f), e0, dtype),
                "w_up": exact_normalish(k[6], (held, d, f), e0, dtype),
                "w_down": exact_normalish(k[7], (held, f, d), e0, dtype)}}


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
    embed, head = _tables(key, sz, dtype)
    blocks = [_layer(key, jnp.int32(li), sz, dtype)
              for li in range(sz.layers)]
    return {"embed": embed, "blocks": blocks,
            "out_norm": jnp.ones((sz.hidden,), dtype), "head": head}


def _rms(x, gain, eps):
    return x * gain / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, table):
    """``x (S, heads, d)``, row ``s`` at position ``s``: pair ``(i, i +
    d/2)`` rotated by ``s * freq_i``, cosine and sine times the factor."""
    freq, factor = table
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32))[:, None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, w, sliding: bool, sz: Sizes, q8=None):
    """Grouped-query attention over one sequence ``x (S, D)``, a block of
    queries at a time."""
    s = x.shape[0]
    table = sz.rope_sliding if sliding else sz.rope_full
    h = _rms(x, w["ln1"], sz.eps)
    q = rope((h @ w["wq"]).reshape(s, sz.heads, sz.head_dim), table)
    k = rope((h @ w["wk"]).reshape(s, sz.kv_heads, sz.head_dim), table)
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
        att = jnp.einsum("qkgd,ckd->kgqc", qb, k) / math.sqrt(sz.head_dim)
        seen = keys[None, :] <= at[:, None]
        if sliding:
            seen &= at[:, None] - keys[None, :] < sz.window
        att = jax.nn.softmax(jnp.where(seen[None, None], att, -1e30), -1)
        return jnp.einsum("kgqc,ckd->qkgd", att, v)

    o = jax.lax.map(one, (q, jnp.arange(s // rows) * rows))
    return o.reshape(s, sz.heads * sz.head_dim) @ w["wo"]


def combine_weights(h, router, sz: Sizes):
    """``(T, E)``: each token's weight for each expert, zero where the
    expert was not among its ``top_k`` by probability."""
    p = jax.nn.softmax(h @ router, axis=-1)
    chosen = jnp.argsort(-p, axis=-1)[:, :sz.top_k]
    picked = jnp.zeros(p.shape, bool).at[
        jnp.arange(p.shape[0])[:, None], chosen].set(True)
    kept = jnp.where(picked, p, 0.0)
    if sz.norm_topk:
        kept = kept / kept.sum(-1, keepdims=True)
    return kept


def experts_sum(h, combine, experts, q8=None):
    """Loop over the experts held: every token through each, weighted."""
    def one(acc, xs):
        m, col = xs
        m = {k: v.astype(jnp.float32) for k, v in m.items()}
        if q8 is not None:  # one scale per output channel
            m = {k: q8(v, 0) for k, v in m.items()}
        out = (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
        return acc + out * col[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h), (experts, combine.T))[0]


def block(x, w, sliding: bool, sz: Sizes, quant: str = "none"):
    """One pre-norm block over one sequence: x (S, D) float32."""
    q8 = QUANT[quant]
    experts = w["experts"]  # widened one at a time
    w = {k: v.astype(jnp.float32) for k, v in w.items() if k != "experts"}
    if q8 is not None:  # one scale per output channel
        for name in ("wq", "wk", "wv", "wo"):
            w[name] = q8(w[name], 0)
    x = x + attention(x, w, sliding, sz, q8)
    h = _rms(x, w["ln2"], sz.eps)
    first, count = sz.held
    combine = combine_weights(h, w["router"], sz)[:, first:first + count]
    return x + experts_sum(h, combine, experts, q8)


def head_logits(x_rows, out_gain, head, sz: Sizes, quant: str = "none"):
    """Final norm and the untied head on chosen rows: (n, D) -> (n, V)."""
    e = head.astype(jnp.float32)
    if QUANT[quant] is not None:
        e = QUANT[quant](e, 0)
    return _rms(x_rows, out_gain, sz.eps) @ e


def logits_for(key, sz: Sizes, tokens, rows, quants=("none",)) -> dict:
    """Reference logits of a batch of sequences at chosen rows, layer by
    layer so that one layer's weights are resident at a time, one sequence
    at a time inside a layer. ``tokens`` (K, S) int32 and ``rows`` (K, n)
    int32 are padded to fixed lengths by the caller (padding follows the
    real tokens, and attention is causal), so every call reuses one compiled
    program per function. Returns ``{quant: (K, n, V) float32}`` on the
    host: at this vocabulary a batch's logits are gigabytes, and the
    device holds one sequence's at a time."""
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        embed, head = _tables(key, sz, jnp.bfloat16)
        xs = {q: embed[tokens].astype(jnp.float32) for q in quants}
        for li in range(sz.layers):
            w = _layer(key, jnp.int32(li), sz, jnp.bfloat16)
            for q in quants:
                xs[q] = _block(xs[q], w, sz.sliding[li], sz, q)
        del w
        gain = jnp.ones((sz.hidden,), jnp.float32)
        out = {}
        for q in quants:
            picked = jnp.take_along_axis(xs[q], rows[:, :, None], axis=1)
            out[q] = np.stack([np.asarray(_head(one, gain, head, sz, q))
                               for one in picked])
        return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block(xs, w, sliding, sz, quant):
    return jax.lax.map(lambda one: block(one, w, sliding, sz, quant), xs)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x_rows, gain, head, sz, quant):
    return head_logits(x_rows, gain, head, sz, quant)
