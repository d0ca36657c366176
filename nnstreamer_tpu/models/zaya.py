"""The ZAYA-shaped block for the paged serving engine (``model_type: zaya``;
ZAYA1-8B is one such model): attention in a compressed latent whose
projection mixes each token with the tokens before it (compressed
convolutional attention, CCA), a top-1 expert layer behind an MLP router
that carries its activations from layer to layer, a residual stream that
scales and shifts both of a merge's operands, RMS norms with a gain, a tied
head.

The configuration carries the published ``config.json`` keys under their
published names. Every layer is of one type (``hybrid``): a CCA part, then
an expert part.

**CCA.** ``h = RMSNorm(x)``. The down-projections give a packed row ``p_t =
[h_t W_q ; h_t W_k]`` (``heads * head_dim + kv_heads * head_dim`` values:
the latent queries and keys). Two causal convolutions over the sequence mix
it: ``a_t = w0[0] * p_{t-1} + w0[1] * p_t + b0`` (depthwise, ``cca_time0``
2) and ``c_t = a_{t-1} W1[0] + a_t W1[1] + b1``, ``W1`` block-diagonal over
the ``heads + kv_heads`` heads (``cca_time1`` 2); rows before the sequence's
first are zero. To ``c`` is added the mean of each query head with its key
head, taken before the convolutions: ``q_n = c^q_n + (q~_n + k~_g) / 2``,
``k_g = c^k_g + (mean_{n in g} q~_n + k~_g) / 2``. Both are L2-normalised
to ``sqrt(head_dim)`` (an RMS norm without gain), the keys times a learned
temperature a key head, and the first ``partial_rotary_factor`` of each head
is rotated (half-split pairs, ``models/mellum.py`` ``rotate_half``). The
values are ``[h_t W_v1 ; h_{t-1} W_v2]``: key head 0's are the current
token's, key head 1's the token's before.

What a token keeps in a layer is two lines, the finished keys and the values
(``kv_heads * head_dim`` each): :class:`~.families.GroupedQueryLines`. What a
*slot* keeps in a layer (``slot_lines``, ``models/families.py``) is what the
next row needs of the past: ``[p_t ; a_t ; h_t W_v2]``, one float32 line.
``project_slot`` takes a batch entry's line and how many of its rows are real,
and leaves the line as it stands after the last real row.

**Experts.** ``u = RMSNorm(x)``; ``r = u D + b_D`` (``router_hidden_size``);
from layer 1 on ``r += gamma * r'`` with ``r'`` the layer before's ``r``
after its own addition (exponential depth averaging: the family's carry
down the stack, ``ffn_carry``); ``s = softmax(W_3 gelu(W_2 gelu(W_1
RMSNorm(r) + b_1) + b_2))``; the one expert ``argmax(s + beta)``, weighted by
its own ``s`` (``parallel/moe_dropless.py`` ``experts_ffn`` at one
assignment a row). The router runs in float32 at ``Precision.HIGHEST``, as
every router here. Every token runs one expert: the published router has
``num_experts`` outputs and no output that skips them.

**Merge** (``scale_residual_merge``): after either part, with branch output
``y``: ``x <- (x + b_x) * s_x + (y + b_y) * s_y``; layer 0 has no ``(b_x,
s_x)``.

What the published ``config.json`` leaves open is written down beside the
benchmark's configuration (``benchmark/configs/zaya1_8b_pp2_l20.json``,
``assumed``) and in the plain reference (``benchmark/references/
zaya_lm.py``), which this file is tested against.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Optional, Tuple

from ..parallel import moe_dropless
from .deepseek_v3 import rms_norm
from .exaone_moe import ExaoneMoeFamily
from .families import GroupedQueryLines
from .mellum import rope_frequencies, rotate_half


@dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    moe_intermediate_size: int = 32       # one expert's MLP
    num_experts: int = 4
    num_experts_per_tok: int = 1
    router_hidden_size: int = 16
    cca_time0: int = 2                    # taps of the depthwise convolution
    cca_time1: int = 2                    # taps of the head-wise one
    partial_rotary_factor: float = 0.5
    rope_parameters: Optional[dict] = None
    layer_types: Tuple[str, ...] = ("hybrid",) * 3
    sliding_window: Optional[int] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 128    # the limit served, not a table
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    lm_head_bias: bool = False
    hidden_act: str = "silu"

    def __post_init__(self):
        n = self.num_hidden_layers
        rope = (self.rope_parameters or {}).get("hybrid", {})
        unsupported = {
            "tie_word_embeddings": not self.tie_word_embeddings,
            "attention_bias": self.attention_bias,
            "lm_head_bias": self.lm_head_bias,
            "hidden_act": self.hidden_act != "silu",
            "layer_types": (len(self.layer_types) < n or any(
                t != "hybrid" for t in self.layer_types[:n])),
            "sliding_window": self.sliding_window is not None,
            "cca_time0/cca_time1": (self.cca_time0, self.cca_time1) != (2, 2),
            "num_experts_per_tok": self.num_experts_per_tok != 1,
            "num_key_value_heads": self.num_key_value_heads != 2 or bool(
                self.num_attention_heads % self.num_key_value_heads),
            "partial_rotary_factor": bool(self.rotary_dim % 2),
            "rope_parameters": rope.get("rope_type",
                                        "default") != "default",
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"zaya family: no support for the given {bad}")

    @classmethod
    def from_published(cls, config: dict) -> "ZayaConfig":
        """From a ``config.json``-shaped dict; keys this block does not
        read are ignored. ``layer_types`` may be the published list: the
        first ``num_hidden_layers`` entries are the layers held here."""
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        if kw.get("layer_types") is not None:
            kw["layer_types"] = tuple(kw["layer_types"])
        return cls(**kw)

    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def line_width(self) -> int:
        """The values a token keeps in each of a layer's two lines."""
        return self.num_key_value_heads * self.head_dim

    @property
    def packed_width(self) -> int:
        """The latent queries and keys of a token side by side: what the
        convolutions mix."""
        return (self.num_attention_heads
                + self.num_key_value_heads) * self.head_dim

    @property
    def rotary_dim(self) -> int:
        """The leading values of each head that positions rotate."""
        return int(self.head_dim * self.partial_rotary_factor)

    def rope(self) -> dict:
        return {"rope_type": "default", "rope_theta": 10000.0,
                **(self.rope_parameters or {}).get("hybrid", {})}


def init_params(cfg: ZayaConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded float32 parameters in the program's layout: matrices normal
    with std 0.02 (the repo's other initialisers' rule), norm gains,
    temperatures and merge scales one, merge shifts far smaller than a
    weight and the attention's output projection an eighth of one (a seeded
    attention is nearly the average of its context, one vector common to
    every token that adds up layer on layer: the reference's ``OUT_SHARE``
    has the numbers); the
    convolutions and the router's MLP at a std that keeps a unit input a
    unit output (a 0.02 there would leave the convolutions' part of a
    query, and every expert's score, at nothing), the router's selection
    bias small and non-zero so that choice and weight really differ."""
    import jax
    import jax.numpy as jnp

    D, H, KV, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    W, F, E, R = (cfg.packed_width, cfg.moe_intermediate_size,
                  cfg.num_experts, cfg.router_hidden_size)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 2 + 32 * cfg.num_hidden_layers))

    def dense(*shape, std=0.02):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def merge(first: bool):
        # a shift is the same for every token: far under a weight's size,
        # or the stream is one common vector within two layers
        out = {"by": dense(D, std=0.02 / 32),
               "sy": jnp.ones((D,), jnp.float32)}
        if not first:
            out.update(bx=dense(D, std=0.02 / 32),
                       sx=jnp.ones((D,), jnp.float32))
        return out

    def block(li: int):
        router = {"down": dense(D, R), "down_b": dense(R),
                  "norm": jnp.ones((R,), jnp.float32),
                  "w1": dense(R, R, std=R ** -0.5), "b1": dense(R),
                  "w2": dense(R, R, std=R ** -0.5), "b2": dense(R),
                  "w3": dense(R, E, std=2 * R ** -0.5),
                  "bias": dense(E, std=0.01)}
        if li:
            router["gamma"] = dense(R, std=0.1)
        return {"ln1": jnp.ones((D,), jnp.float32),
                "wq": dense(D, H * Dh), "wk": dense(D, KV * Dh),
                "wv1": dense(D, Dh), "wv2": dense(D, Dh),
                "wo": dense(H * Dh, D, std=0.02 / 8),
                "conv0_w": dense(2, W, std=0.5), "conv0_b": dense(W),
                "conv1_w": dense(2, H + KV, Dh, Dh, std=(2 * Dh) ** -0.5),
                "conv1_b": dense(W),
                "tau": jnp.ones((KV,), jnp.float32),
                "res_attn": merge(li == 0),
                "ln2": jnp.ones((D,), jnp.float32),
                "router": router,
                "experts": {"w_gate": dense(E, D, F), "w_up": dense(E, D, F),
                            "w_down": dense(E, F, D)},
                "res_ffn": merge(li == 0)}

    return {"embed": dense(cfg.vocab_size, D),
            "blocks": [block(li) for li in range(cfg.num_hidden_layers)],
            "out_norm": jnp.ones((D,), jnp.float32)}


class ZayaFamily(GroupedQueryLines):
    """The block above as the paged engine takes it
    (``models/families.py`` has the contract)."""

    name = "zaya"
    attention_scopes = {"full": "attn.full"}
    counters = moe_dropless.COUNTERS
    state_lines = ()       # no layer without attention
    serves_verify = False  # a state a slot under speculative verify: not yet
    window = None          # every layer sees the whole context

    def __init__(self, cfg: ZayaConfig):
        self.cfg = cfg
        self.vocab = cfg.vocab_size
        self.layers = cfg.num_hidden_layers
        self.max_positions = cfg.max_position_embeddings
        self.layer_kinds = ("full",) * self.layers
        self.expert_slots = self.layers * cfg.num_experts
        self._rope = rope_frequencies(cfg.rotary_dim, cfg.rope())
        # what a slot keeps in every attention layer: the last packed row,
        # the last row between the two convolutions and the last shifted
        # value, one flat float32 line
        self.slot_lines = (((2 * cfg.packed_width + cfg.head_dim,),
                            "float32"),)

    def init_params(self, seed: int):
        return init_params(self.cfg, seed=seed)

    def with_positions(self, positions: int) -> "ZayaFamily":
        from dataclasses import replace

        return ZayaFamily(
            replace(self.cfg, max_position_embeddings=positions))

    def embed(self, p, toks, pos):
        import jax.numpy as jnp

        return p["embed"][toks].astype(jnp.float32)

    def blocks(self, p):
        return p["blocks"]

    # -- the CCA part ----------------------------------------------------------
    def project_slot(self, blk, x, pos, kind, state, rows):
        """``x (B, Q, D)`` at ``pos (B, Q)``, each batch entry's line
        ``state = (line (B, 2 * packed + head_dim),)`` and ``rows (B,)``,
        how many of its ``Q`` rows are real → the finished queries ``(B,
        Q, H, head_dim)``, the two lines to write, finished keys and
        values, ``(B, Q, kv_heads * head_dim)``, and the line as it stands
        after each entry's last real row (as it was, with none)."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        H, KV, Dh, W = (cfg.num_attention_heads, cfg.num_key_value_heads,
                        cfg.head_dim, cfg.packed_width)
        B, Q = x.shape[:2]
        f32 = jnp.float32
        with jax.named_scope("cca.in"):
            h = rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
            p = jnp.concatenate([h @ blk["wq"], h @ blk["wk"]], axis=-1)
            v1, v2 = h @ blk["wv1"], h @ blk["wv2"]
        with jax.named_scope("cca.mix"):
            (line,) = state
            was_p, was_a, was_v = (line[:, None, :W], line[:, None, W:2 * W],
                                   line[:, None, 2 * W:])
            # row t of ``*_all`` is the sequence's row before the launch's
            # row t: the slot's kept row, then the launch's own
            p_all = jnp.concatenate([was_p, p], axis=1)        # (B, Q+1, W)
            w0 = blk["conv0_w"].astype(f32)
            a = (w0[0] * p_all[:, :-1] + w0[1] * p_all[:, 1:]
                 + blk["conv0_b"].astype(f32))
            a_all = jnp.concatenate([was_a, a], axis=1)
            heads = a_all.reshape(B, Q + 1, H + KV, Dh)
            w1 = blk["conv1_w"]
            c = (jnp.einsum("bqni,nio->bqno", heads[:, :-1], w1[0])
                 + jnp.einsum("bqni,nio->bqno", heads[:, 1:], w1[1])
                 + blk["conv1_b"].astype(f32).reshape(H + KV, Dh))
            # the mean of each query head with its key head, from the rows
            # before the convolutions
            lat = p.reshape(B, Q, H + KV, Dh)
            ql, kl = lat[:, :, :H], lat[:, :, H:]
            G = H // KV
            q = c[:, :, :H] + (ql + jnp.repeat(kl, G, axis=2)) / 2
            k = c[:, :, H:] + (ql.reshape(B, Q, KV, G, Dh).mean(3) + kl) / 2
            # key head 0 reads this token's values, key head 1 the token's
            # before
            v_all = jnp.concatenate([was_v, v2], axis=1)
            values = jnp.concatenate([v1, v_all[:, :-1]], axis=-1)
            q = self._unit(q)
            k = self._unit(k) * blk["tau"].astype(f32)[:, None]
            q, k = self._rotate(q, pos), self._rotate(k, pos)
            # the line after each entry's last real row: row ``rows`` of the
            # kept row followed by the launch's
            at = rows[:, None, None]
            line = jnp.concatenate(
                [jnp.take_along_axis(part, at, axis=1)[:, 0]
                 for part in (p_all, a_all, v_all)], axis=-1)
        return q, (k.reshape(B, Q, KV * Dh), values), (line,)

    def _unit(self, x):
        """Each head to the length ``sqrt(head_dim)``: an RMS norm with no
        gain."""
        import jax
        import jax.numpy as jnp

        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                 + self.cfg.rms_norm_eps)

    def _rotate(self, x, pos):
        """The first ``rotary_dim`` values of each head ``x (B, Q, n,
        head_dim)`` rotated by its position, the others as they are."""
        import jax.numpy as jnp

        R = self.cfg.rotary_dim
        freq, factor = self._rope
        return jnp.concatenate(
            [rotate_half(x[..., :R], pos[..., None], freq, factor),
             x[..., R:]], axis=-1)

    # the step's two operand forms, by the kernel's rule at this block's
    # shapes, are the exaone_moe family's (two key heads side by side in a
    # line, ``K`` rows a slot)
    step_by_head = ExaoneMoeFamily.step_by_head
    step_queries = ExaoneMoeFamily.step_queries

    def step_output(self, blk, o):
        import jax

        with jax.named_scope("cca.out"):
            return ExaoneMoeFamily.step_output(self, blk, o)

    def chunk_output(self, blk, o):
        import jax

        with jax.named_scope("cca.out"):
            return super().chunk_output(blk, o)

    # -- the merge --------------------------------------------------------------
    def merge(self, blk, x, y, part):
        """``(x + b_x) * s_x + (y + b_y) * s_y``; layer 0 keeps ``x`` as it
        is."""
        import jax
        import jax.numpy as jnp

        r = {k: v.astype(jnp.float32) for k, v in blk[
            "res_attn" if part == "attention" else "res_ffn"].items()}
        with jax.named_scope("merge"):
            if "bx" in r:
                x = (x + r["bx"]) * r["sx"]
            return x + (y + r["by"]) * r["sy"]

    # -- the expert part ---------------------------------------------------------
    def open_stack(self, p, x):
        return None   # a first pipeline stage receives no router activations

    def route(self, r, h, carry):
        """``h (T, D)`` → the one expert a row ``(T, 1)``, its weight ``(T,
        1)`` and the router's activations ``(T, R)`` that the next layer
        adds to its own. ``carry``: the layer before's, or ``None``."""
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        hi = jax.lax.Precision.HIGHEST

        def dot(a, w):
            return jnp.dot(a, w.astype(f32), precision=hi)

        with jax.named_scope("moe.router"):
            act = dot(h.astype(f32), r["down"]) + r["down_b"].astype(f32)
            if carry is not None:
                act = act + r["gamma"].astype(f32) * carry
            z = rms_norm(act, r["norm"].astype(f32), self.cfg.rms_norm_eps)
            z = jax.nn.gelu(dot(z, r["w1"]) + r["b1"].astype(f32),
                            approximate=False)
            z = jax.nn.gelu(dot(z, r["w2"]) + r["b2"].astype(f32),
                            approximate=False)
            scores = jax.nn.softmax(dot(z, r["w3"]), axis=-1)
            expert = jnp.argmax(scores + r["bias"].astype(f32), axis=-1)
            weight = jnp.take_along_axis(scores, expert[:, None], axis=-1)
            return expert[:, None].astype(jnp.int32), weight, act

    def ffn_carry(self, blk, x, live, carry):
        cfg = self.cfg
        h = rms_norm(x, blk["ln2"], cfg.rms_norm_eps)
        B, Q, D = h.shape
        flat = h.reshape(B * Q, D)
        if carry is not None:
            carry = carry.reshape(B * Q, -1)
        experts, weights, act = self.route(blk["router"], flat, carry)
        e = blk["experts"]
        y, counts = moe_dropless.experts_ffn(
            e["w_gate"], e["w_up"], e["w_down"], flat, experts, weights,
            live=live.reshape(B * Q))
        return y.reshape(B, Q, D), counts, act.reshape(B, Q, -1)

    def head(self, p, x):
        return rms_norm(x, p["out_norm"], self.cfg.rms_norm_eps) @ p[
            "embed"].T
