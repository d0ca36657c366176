"""Plain reference of a DeepSeek-V3-shaped language model
(``model_type: deepseek_v3``; here Kanana-2-30B-A3B), and its weights.

Pre-norm residual blocks with RMS norms (gain, eps from the config), no
biases, an untied head. Attention is multi-head latent attention without a
query bottleneck, written in its **expanded** form: from the normalised
latent ``c`` every head's keys and values are made (``W_kvb c``), the one
rotary key ``kr`` is shared by all heads, and scores are
``[q_nope, q_rope] . [k_nope, kr] / sqrt(qk_head_dim)`` under a causal
softmax. The leading ``first_k_dense_replace`` layers have a gated (SiLU)
MLP; the others add the shared experts' gated MLP to a sum over the routed
experts, here a loop over all of them with the weight of each token for each
expert (zero where it was not chosen): ``s = sigmoid(W_g h)`` in float32,
the ``num_experts_per_tok`` largest of ``s + b`` chosen (``b``:
``e_score_correction_bias``, for the choice only), the chosen ``s``
renormalised and scaled by ``routed_scaling_factor``. Nothing is dropped.

Departures from the published code, each also in the configuration's
``assumed``:

* rotary positions rotate adjacent pairs ``(2i, 2i+1)``. The published code
  (``rope_interleave``) first de-interleaves ``q_rope`` and ``kr`` by one and
  the same permutation and then rotates half-split; a permutation applied to
  both sides of ``q . k`` cancels, so the scores are the same;
* the head lies on this (first) pipeline stage so that tokens come out; in
  the deployment it lies on the last;
* ``W_kvb`` is held split per head into ``wuk`` and ``wuv`` (the same numbers).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, no cache,
no batching tricks, nothing imported from the program. Weights are the
bfloat16 values the program was handed (``lib/weights.py``), widened, made
layer by layer from the seed so that one layer is resident at a time.
``quant`` puts the reference into a lower precision for the control runs:
weights and the cached line pass through int8 or fp8 and back.
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

WEIGHT_STD = 0.02  # every matrix, and e_score_correction_bias: small and
# non-zero, so that the choice (s + b) and the weight (s) really differ. A
# wider bias (0.05 was tried) skews every seed's routing its own way: the
# experts a step reaches then differ from seed to seed by 0.7%, and the
# token gap with them (PERF.md, PR 27)

Sizes = collections.namedtuple(
    "Sizes", "vocab hidden layers heads dense_ffn expert_ffn experts top_k "
             "shared dense_layers latent rope nope vdim eps theta scale "
             "norm_topk positions")


def sizes(config: dict) -> Sizes:
    """The sizes this reference needs, under the source's own key names."""
    return Sizes(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        dense_ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        shared=config["n_shared_experts"],
        dense_layers=config["first_k_dense_replace"],
        latent=config["kv_lora_rank"], rope=config["qk_rope_head_dim"],
        nope=config["qk_nope_head_dim"], vdim=config["v_head_dim"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        scale=config["routed_scaling_factor"],
        norm_topk=bool(config["norm_topk_prob"]),
        positions=config["max_position_embeddings"])


def _e0() -> int:
    return std_exponent(WEIGHT_STD)


def table_weights(key, sz: Sizes, dtype):
    """``(embed (V, D), head (D, V))``: the head is not tied."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    return (exact_normalish(k_embed, (sz.vocab, sz.hidden), _e0(), dtype),
            exact_normalish(k_head, (sz.hidden, sz.vocab), _e0(), dtype))


def _mlp(keys, d, width, dtype, lead=()):
    e0 = _e0()
    return {"w_gate": exact_normalish(keys[0], (*lead, d, width), e0, dtype),
            "w_up": exact_normalish(keys[1], (*lead, d, width), e0, dtype),
            "w_down": exact_normalish(keys[2], (*lead, width, d), e0, dtype)}


def layer_weights(key, layer, dense: bool, sz: Sizes, dtype):
    """One block's matrices, by the program's names. ``layer`` may be
    traced; ``dense`` (a leading dense layer or an expert layer) is not."""
    d, h, e0 = sz.hidden, sz.heads, _e0()
    k = jax.random.split(jax.random.fold_in(key, layer + 1), 16)
    ones = jnp.ones((d,), dtype)
    w = {"ln1": ones, "ln2": ones,
         "wq": exact_normalish(k[0], (d, h * (sz.nope + sz.rope)), e0, dtype),
         "wkva": exact_normalish(k[1], (d, sz.latent + sz.rope), e0, dtype),
         "kv_norm": jnp.ones((sz.latent,), dtype),
         "wuk": exact_normalish(k[2], (h, sz.latent, sz.nope), e0, dtype),
         "wuv": exact_normalish(k[3], (h, sz.latent, sz.vdim), e0, dtype),
         "wo": exact_normalish(k[4], (h * sz.vdim, d), e0, dtype)}
    if dense:
        w["mlp"] = _mlp(k[5:8], d, sz.dense_ffn, dtype)
    else:
        w["router"] = exact_normalish(k[5], (d, sz.experts), e0, dtype)
        w["router_bias"] = exact_normalish(
            k[6], (sz.experts,), e0, jnp.float32)
        w["experts"] = _mlp(k[7:10], d, sz.expert_ffn, dtype, (sz.experts,))
        w["shared"] = _mlp(k[10:13], d, sz.shared * sz.expert_ffn, dtype)
    return w


@functools.partial(jax.jit, static_argnums=(1, 2))
def _tables(key, sz, dtype):
    return table_weights(key, sz, dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(key, li, dense, sz, dtype):
    return layer_weights(key, li, dense, sz, dtype)


def program_params(key, sz: Sizes, dtype):
    """The whole parameter tree in the program's layout and serving type:
    one compiled call per kind of layer, so that the temporaries of one
    layer's making (not of all) lie beside the weights."""
    embed, head = _tables(key, sz, dtype)
    blocks = [_layer(key, jnp.int32(li), li < sz.dense_layers, sz, dtype)
              for li in range(sz.layers)]
    return {"embed": embed, "blocks": blocks,
            "out_norm": jnp.ones((sz.hidden,), dtype), "head": head}


def _rms(x, gain, eps):
    return x * gain / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta: float):
    """``x (S, ..., R)``, row ``s`` at position ``s``: pair ``(2i, 2i+1)``
    rotated by ``s * theta**(-2i/R)``."""
    r = x.shape[-1]
    freq = jnp.exp(jnp.arange(r // 2, dtype=jnp.float32)
                   * (-2.0 * math.log(theta) / r))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), r // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(x, w, sz: Sizes, q8=None):
    """Expanded multi-head latent attention over one sequence ``x (S, D)``."""
    s = x.shape[0]
    h = _rms(x, w["ln1"], sz.eps)
    q = (h @ w["wq"]).reshape(s, sz.heads, sz.nope + sz.rope)
    q = jnp.concatenate([q[..., :sz.nope], rope(q[..., sz.nope:], sz.theta)],
                        axis=-1)
    kva = h @ w["wkva"]
    line = jnp.concatenate(
        [_rms(kva[:, :sz.latent], w["kv_norm"], sz.eps),
         rope(kva[:, sz.latent:], sz.theta)], axis=-1)
    if q8 is not None:  # the cache: one scale per position
        line = q8(line, -1)
    c, kr = line[:, :sz.latent], line[:, sz.latent:]
    k_nope = jnp.einsum("sl,hln->shn", c, w["wuk"])
    v = jnp.einsum("sl,hlv->shv", c, w["wuv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, None], (s, sz.heads, sz.rope))], -1)
    att = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(sz.nope + sz.rope)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal[None], att, -1e30), axis=-1)
    o = jnp.einsum("hqk,khv->qhv", att, v).reshape(s, sz.heads * sz.vdim)
    return o @ w["wo"]


def gated(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def combine_weights(h, router, bias, sz: Sizes):
    """``(T, E)``: each token's weight for each expert, zero where the
    expert was not among its ``top_k`` by ``s + b``."""
    s = jax.nn.sigmoid(h @ router)
    chosen = jnp.argsort(-(s + bias), axis=-1)[:, :sz.top_k]
    picked = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(True)
    kept = jnp.where(picked, s, 0.0)
    if sz.norm_topk:
        kept = kept / (kept.sum(-1, keepdims=True) + 1e-20)
    return kept * sz.scale


def experts_sum(h, combine, experts, q8=None):
    """Loop over all experts: every token through every expert, weighted."""
    def one(acc, xs):
        m, col = xs
        m = {k: v.astype(jnp.float32) for k, v in m.items()}
        if q8 is not None:  # one scale per output channel
            m = {k: q8(v, 0) for k, v in m.items()}
        return acc + gated(h, m) * col[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h), (experts, combine.T))[0]


def block(x, w, dense: bool, sz: Sizes, quant: str = "none"):
    """One pre-norm block over one sequence: x (S, D) float32."""
    q8 = QUANT[quant]
    experts = None if dense else w["experts"]  # widened one at a time
    w = {k: (v if k == "experts" else jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), v)) for k, v in w.items()}
    if q8 is not None:  # one scale per output channel
        for name in ("wq", "wkva", "wo"):
            w[name] = q8(w[name], 0)
        for name in ("wuk", "wuv"):   # W_kvb's channels: (head, out) over l
            w[name] = q8(w[name], 1)
        for name in ("mlp", "shared"):
            if name in w:
                w[name] = {k: q8(v, 0) for k, v in w[name].items()}
    x = x + attention(x, w, sz, q8)
    h = _rms(x, w["ln2"], sz.eps)
    if dense:
        return x + gated(h, w["mlp"])
    combine = combine_weights(h, w["router"], w["router_bias"], sz)
    return x + gated(h, w["shared"]) + experts_sum(h, combine, experts, q8)


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
            dense = li < sz.dense_layers
            w = _layer(key, jnp.int32(li), dense, sz, jnp.bfloat16)
            for q in quants:
                xs[q] = _block(xs[q], w, dense, sz, q)
        del w
        gain = jnp.ones((sz.hidden,), jnp.float32)
        out = {}
        for q in quants:
            picked = jnp.take_along_axis(xs[q], rows[:, :, None], axis=1)
            out[q] = np.stack([np.asarray(_head(one, gain, head, sz, q))
                               for one in picked])
        return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block(xs, w, dense, sz, quant):
    return jax.lax.map(lambda one: block(one, w, dense, sz, quant), xs)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x_rows, gain, head, sz, quant):
    return head_logits(x_rows, gain, head, sz, quant)
