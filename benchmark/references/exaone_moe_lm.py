"""Plain reference of an EXAONE-MoE-shaped language model (``model_type:
exaone_moe``; here K-EXAONE-236B-A23B) with its multi-token-prediction
layer, and its weights.

Pre-norm residual blocks with RMS norms (gain, eps from the config), no
biases, an untied head. A block:

* ``h = RMSNorm(x)``; ``q = h W_q`` (``heads`` of ``head_dim``), ``k = h W_k``,
  ``v = h W_v`` (``kv_heads`` of ``head_dim``); ``q`` and ``k`` RMS-normed
  over each head's ``head_dim`` values with a learned gain; on a
  ``sliding_attention`` layer ``q`` and ``k`` rotated by ``f_i = theta^(-2i/d)``,
  half-split pairs ``(i, i + d/2)``; on a ``full_attention`` layer not rotated;
* query head ``n`` reads key/value head ``n // (heads / kv_heads)``; scores
  ``q . k / sqrt(head_dim)``; the query at position ``i`` sees key ``j`` iff
  ``j <= i`` and, in a sliding layer, ``i - j < sliding_window``; softmax;
  ``x += (A v) W_o``;
* ``h = RMSNorm(x)``; a ``dense`` layer: ``x += W_down(silu(W_gate h) * W_up
  h)`` of width ``intermediate_size``; a ``sparse`` layer: ``s = sigmoid(h
  W_r)`` over all experts; the ``num_experts_per_tok`` largest of ``s + bias``;
  their ``s`` renormalised to sum 1 (``norm_topk_prob``) and times
  ``routed_scaling_factor``; ``x += sum_e w_e FFN_e(h) + FFN_shared(h)``, here
  a loop over the experts held (``experts_held``), every token through each
  with its weight for it (zero where it was not chosen). What experts held
  elsewhere would add is left out, as in the program.

``logits = RMSNorm(x_L) W_head`` over the rows of the vocabulary held
(``vocab_held``): a smaller vocabulary, ids ``0 .. count - 1``.

The MTP layer: ``u_i = [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_{L,i})] W_eh``, one
``full_attention`` block with a sparse feed-forward over ``u``, its own
output norm, the main embedding and head: ``logits_mtp_i`` scores token
``i + 2``. The last row of a sequence has no next token and is paired with
token 0; attention is causal, so no earlier row sees it.

Departures from the published code, each also in the configuration's
``assumed``: the head and the MTP layer lie on this stage so that tokens come
out; the q/k norm, the unrotated full layers, the selection bias and the MTP
block's feed-forward are the EXAONE-4.0 family's and the ``deepseek_v3``
router's conventions where the config has no key; attention is computed a
block of queries at a time (all keys at once): the same sums.

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
# The router's selection bias. A trained bias evens the experts' load (it is
# what the published router learns it for); a seeded one the size of the
# program's test initialiser (0.05: 0.7 of a logit's 1.57 where the top
# 8 of 128 are chosen) does the opposite: measured on the chip, PR 47, 90.5%
# of the held experts reached a round and the largest load 2.66 times the
# mean, the share reached swinging by 3% from seed to seed and the round's
# length with it. At 0.01 the choice still differs from the weights' order
# at near ties and the load is a random router's (99.9% reached, 1.7)
BIAS_STD = 0.01

Sizes = collections.namedtuple(
    "Sizes", "vocab hidden layers heads kv_heads head_dim dense_ffn "
             "expert_ffn experts top_k held shared scale eps norm_topk "
             "positions window sliding dense rope mtp")


def sizes(config: dict) -> Sizes:
    """The sizes this reference needs, under the source's own key names.
    ``num_experts`` and ``vocab_size`` count what is held here where the
    file lists them in ``reduced``; the router's width is the published
    count (``published.num_experts``)."""
    layers = config["num_hidden_layers"]
    published = config.get("published", {})
    experts = published.get("num_experts", config["num_experts"])
    theta = float(config["rope_parameters"]["rope_theta"])
    d = config["head_dim"]
    return Sizes(
        vocab=(config.get("vocab_held") or (0, config["vocab_size"]))[1],
        hidden=config["hidden_size"], layers=layers,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=d,
        dense_ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"], experts=experts,
        top_k=config["num_experts_per_tok"],
        held=tuple(config.get("experts_held") or (0, experts)),
        shared=config["num_shared_experts"],
        scale=float(config["routed_scaling_factor"]),
        eps=config["rms_norm_eps"],
        norm_topk=bool(config["norm_topk_prob"]),
        positions=config["max_position_embeddings"],
        window=config["sliding_window"],
        sliding=tuple(t == "sliding_attention"
                      for t in config["layer_types"][:layers]),
        dense=tuple(t == "dense"
                    for t in config["mlp_layer_types"][:layers]),
        rope=tuple(theta ** (-2.0 * i / d) for i in range(d // 2)),
        mtp=bool(config["num_nextn_predict_layers"]))


def model_config(config: dict) -> dict:
    """The file's keys as the program's configuration type takes them:
    the published counts where the file holds this chip's share."""
    published = config.get("published", {})
    return {**config, **{k: published[k] for k in ("num_experts",
                                                   "vocab_size")
                         if k in published}}


def _e0() -> int:
    return std_exponent(WEIGHT_STD)


def table_weights(key, sz: Sizes, dtype):
    """``(embed (V, D), head (D, V))`` of the rows held: not tied."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    return (exact_normalish(k_embed, (sz.vocab, sz.hidden), _e0(), dtype),
            exact_normalish(k_head, (sz.hidden, sz.vocab), _e0(), dtype))


def _mlp(keys, d, width, dtype, lead=()):
    e0 = _e0()
    return {"w_gate": exact_normalish(keys[0], (*lead, d, width), e0, dtype),
            "w_up": exact_normalish(keys[1], (*lead, d, width), e0, dtype),
            "w_down": exact_normalish(keys[2], (*lead, width, d), e0, dtype)}


def layer_weights(key, layer, sz: Sizes, dtype, dense: bool):
    """One block's matrices, by the program's names. ``layer`` may be
    traced. The experts are the ones held (``sz.held``)."""
    d, e0 = sz.hidden, _e0()
    wide, narrow = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    k = jax.random.split(jax.random.fold_in(key, layer + 1), 12)
    ones = jnp.ones((d,), dtype)
    w = {"ln1": ones, "ln2": ones,
         "q_norm": jnp.ones((sz.head_dim,), dtype),
         "k_norm": jnp.ones((sz.head_dim,), dtype),
         "wq": exact_normalish(k[0], (d, wide), e0, dtype),
         "wk": exact_normalish(k[1], (d, narrow), e0, dtype),
         "wv": exact_normalish(k[2], (d, narrow), e0, dtype),
         "wo": exact_normalish(k[3], (wide, d), e0, dtype)}
    if dense:
        w["mlp"] = _mlp(k[4:7], d, sz.dense_ffn, dtype)
    else:
        w["router"] = exact_normalish(k[4], (d, sz.experts), e0, dtype)
        w["router_bias"] = exact_normalish(
            k[5], (sz.experts,), std_exponent(BIAS_STD), dtype)
        w["experts"] = _mlp(k[6:9], d, sz.expert_ffn, dtype, (sz.held[1],))
        w["shared"] = _mlp(k[9:12], d, sz.shared * sz.expert_ffn, dtype)
    return w


def mtp_weights(key, sz: Sizes, dtype):
    """The MTP layer's parameters: two norms, ``eh_proj``, one sparse block
    (layer index ``sz.layers``), its output norm."""
    ones = jnp.ones((sz.hidden,), dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, 0), 7)
    return {"enorm": ones, "hnorm": ones, "out_norm": ones,
            "eh_proj": exact_normalish(k, (2 * sz.hidden, sz.hidden), _e0(),
                                       dtype),
            "block": layer_weights(key, sz.layers, sz, dtype, False)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _tables(key, sz, dtype):
    return table_weights(key, sz, dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(key, li, sz, dtype, dense):
    return layer_weights(key, li, sz, dtype, dense)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _mtp(key, sz, dtype):
    return mtp_weights(key, sz, dtype)


def program_params(key, sz: Sizes, dtype):
    """The whole parameter tree in the program's layout and serving type:
    one compiled call per layer, so that the temporaries of one layer's
    making (not of all) lie beside the weights."""
    embed, head = _tables(key, sz, dtype)
    blocks = [_layer(key, jnp.int32(li), sz, dtype, sz.dense[li])
              for li in range(sz.layers)]
    params = {"embed": embed, "blocks": blocks,
              "out_norm": jnp.ones((sz.hidden,), dtype), "head": head}
    if sz.mtp:
        params["mtp"] = _mtp(key, sz, dtype)
    return params


def _rms(x, gain, eps):
    return x * gain / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, freq):
    """``x (S, heads, d)``, row ``s`` at position ``s``: pair ``(i, i +
    d/2)`` rotated by ``s * freq_i``."""
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32))[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, w, sliding: bool, sz: Sizes, q8=None):
    """Grouped-query attention over one sequence ``x (S, D)``, a block of
    queries at a time."""
    s = x.shape[0]
    h = _rms(x, w["ln1"], sz.eps)
    q = _rms((h @ w["wq"]).reshape(s, sz.heads, sz.head_dim), w["q_norm"],
             sz.eps)
    k = _rms((h @ w["wk"]).reshape(s, sz.kv_heads, sz.head_dim), w["k_norm"],
             sz.eps)
    if sliding:  # positions live on the local layers only
        q, k = rope(q, sz.rope), rope(k, sz.rope)
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


def combine_weights(h, router, bias, sz: Sizes):
    """``(T, E)``: each token's weight for each expert, zero where the
    expert was not among its ``top_k`` by score plus selection bias."""
    s = jax.nn.sigmoid(h @ router)
    chosen = jnp.argsort(-(s + bias), axis=-1)[:, :sz.top_k]
    picked = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(True)
    kept = jnp.where(picked, s, 0.0)
    if sz.norm_topk:
        kept = kept / kept.sum(-1, keepdims=True)
    return kept * sz.scale


def gated(h, m, q8=None):
    m = {k: v.astype(jnp.float32) for k, v in m.items()}
    if q8 is not None:  # one scale per output channel
        m = {k: q8(v, 0) for k, v in m.items()}
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def experts_sum(h, combine, experts, q8=None):
    """Loop over the experts held: every token through each, weighted."""
    def one(acc, xs):
        m, col = xs
        return acc + gated(h, m, q8) * col[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h), (experts, combine.T))[0]


def feed_forward(h, w, sz: Sizes, q8=None):
    """What the residual adds after the second norm: the dense MLP, or the
    held experts' part plus the shared expert."""
    if "mlp" in w:
        return gated(h, w["mlp"], q8)
    first, count = sz.held
    combine = combine_weights(
        h, w["router"].astype(jnp.float32),
        w["router_bias"].astype(jnp.float32), sz)[:, first:first + count]
    return experts_sum(h, combine, w["experts"], q8) + gated(h, w["shared"],
                                                            q8)


def block(x, w, sliding: bool, sz: Sizes, quant: str = "none"):
    """One pre-norm block over one sequence: x (S, D) float32."""
    q8 = QUANT[quant]
    a = {k: w[k].astype(jnp.float32)
         for k in ("ln1", "ln2", "q_norm", "k_norm", "wq", "wk", "wv", "wo")}
    if q8 is not None:  # one scale per output channel
        for name in ("wq", "wk", "wv", "wo"):
            a[name] = q8(a[name], 0)
    x = x + attention(x, a, sliding, sz, q8)
    return x + feed_forward(_rms(x, a["ln2"], sz.eps), w, sz, q8)


def mtp_block(x, toks, embed, m, sz: Sizes, quant: str = "none"):
    """The MTP layer over one sequence: the stack's output ``x (S, D)`` and
    the sequence's tokens ``toks (S,)`` → the MTP block's output rows."""
    f32 = jnp.float32
    after = jnp.concatenate([toks[1:], jnp.zeros((1,), toks.dtype)])
    e = _rms(embed[after].astype(f32), m["enorm"].astype(f32), sz.eps)
    h = _rms(x, m["hnorm"].astype(f32), sz.eps)
    eh = m["eh_proj"].astype(f32)
    if QUANT[quant] is not None:
        eh = QUANT[quant](eh, 0)
    u = jnp.concatenate([e, h], axis=-1) @ eh
    return block(u, m["block"], False, sz, quant)


def head_logits(x_rows, out_gain, head, sz: Sizes, quant: str = "none"):
    """Final norm and the untied head on chosen rows: (n, D) -> (n, V)."""
    e = head.astype(jnp.float32)
    if QUANT[quant] is not None:
        e = QUANT[quant](e, 0)
    return _rms(x_rows, out_gain, sz.eps) @ e


def _forward(key, sz: Sizes, tokens, rows, mtp_rows, quants):
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    gain = jnp.ones((sz.hidden,), jnp.float32)

    def picked_logits(xs, at):
        out = {}
        for q in quants:
            picked = jnp.take_along_axis(xs[q], at[:, :, None], axis=1)
            out[q] = np.stack([np.asarray(_head(one, gain, head, sz, q))
                               for one in picked])
        return out

    with jax.default_matmul_precision("highest"):
        embed, head = _tables(key, sz, jnp.bfloat16)
        xs = {q: embed[tokens].astype(jnp.float32) for q in quants}
        for li in range(sz.layers):
            w = _layer(key, jnp.int32(li), sz, jnp.bfloat16, sz.dense[li])
            for q in quants:
                xs[q] = _block(xs[q], w, sz.sliding[li], sz, q)
        del w
        main = picked_logits(xs, rows)
        if mtp_rows is None:
            return main, None
        m = _mtp(key, sz, jnp.bfloat16)
        for q in quants:
            xs[q] = _mtp_block(xs[q], tokens, embed, m, sz, q)
        return main, picked_logits(xs, jnp.asarray(mtp_rows))


def logits_for(key, sz: Sizes, tokens, rows, quants=("none",)) -> dict:
    """Reference logits of a batch of sequences at chosen rows, layer by
    layer so that one layer's weights are resident at a time, one sequence
    at a time inside a layer. ``tokens`` (K, S) int32 and ``rows`` (K, n)
    int32 are padded to fixed lengths by the caller (padding follows the
    real tokens, and attention is causal), so every call reuses one compiled
    program per function. Returns ``{quant: (K, n, V) float32}`` on the
    host."""
    return _forward(key, sz, tokens, rows, None, quants)[0]


def both_logits_for(key, sz: Sizes, tokens, rows, mtp_rows,
                    quants=("none",)) -> tuple:
    """``(main logits at rows, MTP logits at mtp_rows)``, each ``{quant: (K,
    n, V)}``: the stack once, then the MTP layer on its output. The MTP
    logits at row ``i`` score token ``i + 2``."""
    return _forward(key, sz, tokens, rows, mtp_rows, quants)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block(xs, w, sliding, sz, quant):
    return jax.lax.map(lambda one: block(one, w, sliding, sz, quant), xs)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _mtp_block(xs, tokens, embed, m, sz, quant):
    return jax.lax.map(
        lambda one: mtp_block(one[0], one[1], embed, m, sz, quant),
        (xs, tokens))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x_rows, gain, head, sz, quant):
    return head_logits(x_rows, gain, head, sz, quant)
