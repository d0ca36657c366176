"""Plain reference of a ZAYA-shaped language model (``model_type: zaya``; here
ZAYA1-8B), and its weights.

Every layer is the same (``layer_types``: ``hybrid``): a CCA attention part,
then a top-1 expert part, each merged into the residual stream ``x`` by
learned per-channel scales and shifts. RMS norms with a gain (eps from the
config), no bias in attention (``attention_bias false``), the head tied to
the embedding. With ``E`` the hidden size, ``d`` the head size, ``H`` query
heads and ``G`` key heads:

* **CCA** (compressed convolutional attention, arXiv:2510.04476), ``h =
  RMSNorm(x)``:

  1. ``q~ = h W_q`` (``H d``), ``k~ = h W_k`` (``G d``): the latent;
  2. over the packed row ``p = [q~ ; k~]``, causal, rows before the first
     zero: ``a_t = w0[0] p_{t-1} + w0[1] p_t + b0`` (depthwise,
     ``cca_time0`` 2), ``c_t = a_{t-1} W1[0] + a_t W1[1] + b1`` with ``W1``
     block-diagonal over the ``H + G`` heads (``cca_time1`` 2);
  3. the q-k mean from the rows before the convolutions: query head ``n`` of
     key head ``g = n // (H / G)``: ``q_n = c^q_n + (q~_n + k~_g) / 2``,
     ``k_g = c^k_g + (mean_{n in g} q~_n + k~_g) / 2``;
  4. ``v_t = [h_t W_v1 ; h_{t-1} W_v2]``: key head 0's values are the
     token's own, key head 1's the token's before (``h_{-1} = 0``);
  5. ``q_n <- sqrt(d) q_n / |q_n|``, ``k_g <- tau_g sqrt(d) k_g / |k_g|``;
  6. the first ``partial_rotary_factor`` of each head rotated by the
     position, half-split pairs (``rotate_half``), ``rope_parameters.hybrid``;
  7. causal softmax of ``q_n . k_g / sqrt(d)``, values ``v_g``; ``y = concat_n
     W_o``.

* **merge** (``scale_residual_merge``): ``x <- (x + b_x) s_x + (y + b_y)
  s_y``; layer 0: ``x <- x + (y + b_y) s_y``.

* **experts**, ``u = RMSNorm(x)``: ``r = u D + b_D`` (``router_hidden_size``);
  from layer 1 on ``r += gamma r'``, ``r'`` the layer before's ``r`` after
  its own addition (exponential depth averaging); ``s = softmax(W_3 gelu(W_2
  gelu(W_1 RMSNorm(r) + b_1) + b_2))``; the one expert ``e = argmax(s +
  beta)``; ``y = s_e W_down,e (silu(W_gate,e u) * W_up,e u)``, here a loop
  over the experts, every token through each with its weight for it (zero
  where it was not chosen).

Departures from the published description, each also in the configuration's
``assumed`` (the ``config.json`` has no key for any of them, and
``transformers`` here has no ``zaya`` model to read):

* the order and form of the q-k mean, the two convolutions' grouping and
  biases, ``tau`` on the keys only, which half of the values is shifted;
* the L2 norms carry ``rms_norm_eps`` under the root, as the RMS norms do;
* the merge's form and its missing ``(b_x, s_x)`` in layer 0;
* the router's MLP (two hidden layers of ``router_hidden_size``, exact
  GELU, an RMS norm with a gain before it) and the depth average
  (``zaya_use_eda``, ``zaya_mlp_expansion`` of the sibling ``ZAYA1-base``);
* **no skip choice**: the router has ``num_experts`` outputs and every token
  runs one expert (``num_experts 16``, ``num_experts_per_tok 1``); the
  family's "residual-scaled MoD" has no key in this config and no equation
  in the public descriptions. The costlier reading: nothing a deployment
  computes is left out;
* the head lies on this (first) pipeline stage so that tokens come out; in
  the deployment it lies on the last, which would also receive the
  router's activations ``r`` with the residual stream;
* attention is computed a block of queries at a time (all keys at once), so
  that a context of thousands of positions fits: the same sums.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, full
sequences, no cache, no state a sequence (the convolutions and the value
shift are shifted copies of the whole sequence), nothing imported from the
program. Weights are the bfloat16 values the program was handed
(``lib/weights.py``), widened, made layer by layer from the seed so that one
layer is resident at a time. ``quant`` puts the reference into a lower
precision for the control runs: weights and the cached lines pass through
int8 or fp8 and back.
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

WEIGHT_STD = 0.02      # every projection, table and bias, unless the sizes say
SHIFT_SHARE = 2.0 ** -5  # a merge's shift over a weight: the same for every
#                          token, so a shift of a weight's size (an element of
#                          the stream is no larger) buries the tokens in one
#                          common vector within two layers, and every router
#                          then sends them all to the same few experts
GAMMA_STD = 0.1        # the depth average's multiple
BETA_STD = 0.01        # the router's selection bias
OUT_SHARE = 2.0 ** -3  # the attention's output projection over a weight. A
#                        seeded attention at temperature 1 is nearly the
#                        average of its context, one vector common to every
#                        token of a sequence; layer on layer it adds up in step
#                        where the experts' outputs, a token's own, add up at
#                        random: at a weight's size it is 90% of the stream
#                        within two layers, the next layer's queries share it,
#                        and every router sends all tokens to the same few
#                        experts. Simulated on the CPU at the published widths
#                        (experts of width 512, 1,024 tokens), the experts of
#                        16 that 32 rows reach at layers 0, 9 and 19, by share:
#                        1: 10.5, 2.9, -; 1/4: 12.8, 12.1, 11.3; 1/8: 13.4,
#                        13.6, 13.3; 1/16: 13.6, 13.7, - (even: 13.9). A higher
#                        temperature keeps tokens apart too (8: 13.5, 12.8,
#                        13.0) but makes the model chaotic: at 4 the cache's
#                        bfloat16 lines alone move 143 of 256 best tokens
CONV0_STD = 0.5        # two depthwise taps: a unit row stays a unit row

Sizes = collections.namedtuple(
    "Sizes", "vocab hidden layers heads kv_heads head_dim expert_ffn "
             "experts router eps positions rotary rope std")


def rope_table(rotary: int, rope: dict) -> tuple:
    """The frequencies of the ``rotary / 2`` pairs (float64 arithmetic)."""
    theta = float(rope["rope_theta"])
    return tuple(theta ** (-2.0 * i / rotary) for i in range(rotary // 2))


def sizes(config: dict) -> Sizes:
    """The sizes this reference needs, under the source's own key names."""
    rope = config["rope_parameters"]["hybrid"]
    rotary = int(config["head_dim"] * config["partial_rotary_factor"])
    return Sizes(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        expert_ffn=config["moe_intermediate_size"],
        experts=config["num_experts"], router=config["router_hidden_size"],
        eps=config["rms_norm_eps"],
        positions=config["max_position_embeddings"],
        rotary=rotary, rope=rope_table(rotary, rope),
        std=config.get("weight_std", WEIGHT_STD))


def embed_weights(key, sz: Sizes, dtype):
    """``(V, D)``: the embedding, which is also the head."""
    return exact_normalish(jax.random.fold_in(key, 0), (sz.vocab, sz.hidden),
                           std_exponent(sz.std), dtype)


def layer_weights(key, layer, first: bool, sz: Sizes, dtype):
    """One block's parameters, by the program's names. ``layer`` may be
    traced; ``first`` says whether it is layer 0, which has no ``(b_x,
    s_x)`` and no ``gamma``. Gains, temperatures and merge scales are one."""
    d, dh, r, f, n = (sz.hidden, sz.head_dim, sz.router, sz.expert_ffn,
                      sz.experts)
    heads = sz.heads + sz.kv_heads
    wide = heads * dh
    k = iter(jax.random.split(jax.random.fold_in(key, layer + 1), 32))

    def w(*shape, std=sz.std):
        return exact_normalish(next(k), shape, std_exponent(std), dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def merge():
        shift = sz.std * SHIFT_SHARE
        out = {"by": w(d, std=shift), "sy": ones(d)}
        if not first:
            out.update(bx=w(d, std=shift), sx=ones(d))
        return out

    def paired(rows, cols, std):
        # row i + rows / 2 is minus row i: what every input channel has in
        # common (a GELU's output has a mean) reaches no output, so no
        # expert is favoured whatever the token, as a trained router's
        # balance would see to; a row's own part goes through as it would
        top = w(rows // 2, cols, std=std)
        return jnp.concatenate([top, -top], axis=0)

    unit = r ** -0.5  # a unit input stays a unit output
    router = {"down": w(d, r), "down_b": w(r), "norm": ones(r),
              "w1": w(r, r, std=unit), "b1": w(r),
              "w2": paired(r, r, unit), "b2": w(r),
              "w3": paired(r, n, 2 * unit), "bias": w(n, std=BETA_STD)}
    if not first:
        router["gamma"] = w(r, std=GAMMA_STD)
    return {"ln1": ones(d),
            "wq": w(d, sz.heads * dh), "wk": w(d, sz.kv_heads * dh),
            "wv1": w(d, dh), "wv2": w(d, dh),
            "wo": w(sz.heads * dh, d, std=sz.std * OUT_SHARE),
            "conv0_w": w(2, wide, std=CONV0_STD), "conv0_b": w(wide),
            "conv1_w": w(2, heads, dh, dh, std=(2 * dh) ** -0.5),
            "conv1_b": w(wide),
            "tau": ones(sz.kv_heads),
            "res_attn": merge(),
            "ln2": ones(d),
            "router": router,
            "experts": {"w_gate": w(n, d, f), "w_up": w(n, d, f),
                        "w_down": w(n, f, d)},
            "res_ffn": merge()}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _embed(key, sz, dtype):
    return embed_weights(key, sz, dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(key, li, first, sz, dtype):
    return layer_weights(key, li, first, sz, dtype)


def program_params(key, sz: Sizes, dtype):
    """The whole parameter tree in the program's layout and serving type:
    one compiled call per layer, so that the temporaries of one layer's
    making (not of all) lie beside the weights."""
    blocks = [_layer(key, jnp.int32(li), li == 0, sz, dtype)
              for li in range(sz.layers)]
    return {"embed": _embed(key, sz, dtype), "blocks": blocks,
            "out_norm": jnp.ones((sz.hidden,), dtype)}


def _rms(x, gain, eps):
    return x * gain / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def before(x):
    """Row ``t - 1`` at row ``t`` of ``x (S, ...)``, zero at the first."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def rope(x, sz: Sizes):
    """``x (S, heads, d)``, row ``s`` at position ``s``: of the first
    ``rotary`` values of each head, pair ``(i, i + rotary/2)`` rotated by
    ``s * freq_i``; the other values as they are."""
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(sz.rope, jnp.float32))[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = sz.rotary // 2
    a, b, rest = x[..., :half], x[..., half:sz.rotary], x[..., sz.rotary:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def cca(x, w, sz: Sizes, q8=None):
    """The attention part over one sequence ``x (S, D)``: its output
    before the merge, a block of queries at a time."""
    s, dh, H, G = x.shape[0], sz.head_dim, sz.heads, sz.kv_heads
    group = H // G
    h = _rms(x, w["ln1"], sz.eps)
    p = jnp.concatenate([h @ w["wq"], h @ w["wk"]], axis=-1)
    a = w["conv0_w"][0] * before(p) + w["conv0_w"][1] * p + w["conv0_b"]
    heads = a.reshape(s, H + G, dh)
    c = (jnp.einsum("sni,nio->sno", before(heads), w["conv1_w"][0])
         + jnp.einsum("sni,nio->sno", heads, w["conv1_w"][1])
         + w["conv1_b"].reshape(H + G, dh))
    lat = p.reshape(s, H + G, dh)
    ql, kl = lat[:, :H], lat[:, H:]
    q = c[:, :H] + (ql + jnp.repeat(kl, group, axis=1)) / 2
    k = c[:, H:] + (ql.reshape(s, G, group, dh).mean(axis=2) + kl) / 2
    v = jnp.concatenate([h @ w["wv1"], before(h) @ w["wv2"]], axis=-1)

    def unit(t):  # to the length sqrt(d)
        return t / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + sz.eps)

    q = rope(unit(q), sz)
    k = rope(unit(k) * w["tau"][:, None], sz)
    if q8 is not None:  # the cache: one scale per position and line
        k = q8(k.reshape(s, -1), -1).reshape(k.shape)
        v = q8(v, -1)
    v = v.reshape(s, G, dh)
    rows = math.gcd(s, 256)  # queries a block
    q = q.reshape(s // rows, rows, G, group, dh)
    keys = jnp.arange(s)

    def one(args):
        qb, first = args
        at = first + jnp.arange(rows)
        att = jnp.einsum("qkgd,ckd->kgqc", qb, k) / math.sqrt(dh)
        seen = keys[None, :] <= at[:, None]
        att = jax.nn.softmax(jnp.where(seen[None, None], att, -1e30), -1)
        return jnp.einsum("kgqc,ckd->qkgd", att, v)

    o = jax.lax.map(one, (q, jnp.arange(s // rows) * rows))
    return o.reshape(s, H * dh) @ w["wo"]


def merge(x, y, r):
    """``(x + b_x) s_x + (y + b_y) s_y``; layer 0 keeps ``x`` as it is."""
    if "bx" in r:
        x = (x + r["bx"]) * r["sx"]
    return x + (y + r["by"]) * r["sy"]


def route(u, r, carried, sz: Sizes):
    """``(combine (T, E), activations (T, R))``: each token's weight for
    each expert, zero but for the one chosen, and the router's activations
    after the depth average, which the next layer adds to its own."""
    act = u @ r["down"] + r["down_b"]
    if carried is not None:
        act = act + r["gamma"] * carried
    z = _rms(act, r["norm"], sz.eps)
    z = jax.nn.gelu(z @ r["w1"] + r["b1"], approximate=False)
    z = jax.nn.gelu(z @ r["w2"] + r["b2"], approximate=False)
    s = jax.nn.softmax(z @ r["w3"], axis=-1)
    chosen = jnp.argmax(s + r["bias"], axis=-1)
    picked = jnp.arange(sz.experts)[None, :] == chosen[:, None]
    return jnp.where(picked, s, 0.0), act


def experts_sum(h, combine, experts, q8=None):
    """Loop over the experts: every token through each, weighted."""
    def one(acc, xs):
        m, col = xs
        m = {k: v.astype(jnp.float32) for k, v in m.items()}
        if q8 is not None:  # one scale per output channel
            m = {k: q8(v, 0) for k, v in m.items()}
        out = (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
        return acc + out * col[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h), (experts, combine.T))[0]


def block(x, carried, w, sz: Sizes, quant: str = "none"):
    """One layer over one sequence: ``x (S, D)`` float32 and the layer
    before's router activations (``None`` in layer 0) → ``(x', this
    layer's router activations)``."""
    q8 = QUANT[quant]
    experts = w["experts"]  # widened one at a time
    w = jax.tree_util.tree_map(
        lambda v: v.astype(jnp.float32),
        {k: v for k, v in w.items() if k != "experts"})
    if q8 is not None:  # one scale per output channel
        for name in ("wq", "wk", "wv1", "wv2", "wo"):
            w[name] = q8(w[name], 0)
    x = merge(x, cca(x, w, sz, q8), w["res_attn"])
    u = _rms(x, w["ln2"], sz.eps)
    combine, act = route(u, w["router"], carried, sz)
    return merge(x, experts_sum(u, combine, experts, q8), w["res_ffn"]), act


def head_logits(x_rows, out_gain, embed, sz: Sizes, quant: str = "none"):
    """Final norm and the tied head on chosen rows: (n, D) -> (n, V)."""
    e = embed.astype(jnp.float32)
    if QUANT[quant] is not None:
        e = QUANT[quant](e, 1)  # one scale per output channel
    return _rms(x_rows, out_gain, sz.eps) @ e.T


def hidden_for(key, sz: Sizes, tokens, quants=("none",), edit=None) -> dict:
    """``{quant: (K, S, D)}``: the residual stream after the last layer for
    a batch of sequences ``tokens (K, S)``, layer by layer so that one
    layer's weights are resident at a time, one sequence at a time inside
    a layer. ``edit(li, weights) -> weights`` changes a layer's parameters
    on the way (the tests' way to drop a mechanism)."""
    tokens = jnp.asarray(tokens)
    embed = _embed(key, sz, jnp.bfloat16)
    xs = {q: embed[tokens].astype(jnp.float32) for q in quants}
    acts = dict.fromkeys(quants)
    for li in range(sz.layers):
        w = _layer(key, jnp.int32(li), li == 0, sz, jnp.bfloat16)
        if edit is not None:
            w = edit(li, w)
        for q in quants:
            xs[q], acts[q] = _block(xs[q], acts[q], w, sz, q)
    return xs


def logits_for(key, sz: Sizes, tokens, rows, quants=("none",),
               edit=None) -> dict:
    """Reference logits of a batch of sequences at chosen rows. ``tokens``
    (K, S) int32 and ``rows`` (K, n) int32 are padded to fixed lengths by
    the caller (padding follows the real tokens, and every mixing of rows
    is causal), so every call reuses one compiled program per function.
    Returns ``{quant: (K, n, V) float32}`` on the host: at this vocabulary
    a batch's logits are gigabytes, and the device holds one sequence's at
    a time."""
    rows = jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        xs = hidden_for(key, sz, tokens, quants, edit)
        embed = _embed(key, sz, jnp.bfloat16)
        gain = jnp.ones((sz.hidden,), jnp.float32)
        out = {}
        for q in quants:
            picked = jnp.take_along_axis(xs[q], rows[:, :, None], axis=1)
            out[q] = np.stack([np.asarray(_head(one, gain, embed, sz, q))
                               for one in picked])
        return out


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block(xs, acts, w, sz, quant):
    if acts is None:  # layer 0: nothing comes down the stack yet
        return jax.lax.map(lambda x: block(x, None, w, sz, quant), xs)
    return jax.lax.map(lambda xa: block(*xa, w, sz, quant), (xs, acts))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x_rows, gain, embed, sz, quant):
    return head_logits(x_rows, gain, embed, sz, quant)
