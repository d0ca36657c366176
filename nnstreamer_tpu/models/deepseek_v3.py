"""The DeepSeek-V3-shaped block for the paged serving engine: multi-head
latent attention (MLA) with rotary positions, a gated (SiLU) MLP in the
leading dense layers and a dropless sigmoid-routed expert layer with shared
experts after them, RMS norms with a gain, an untied head.

The configuration carries the published ``config.json`` keys under their
published names (``model_type: deepseek_v3``; Kanana-2-30B-A3B is one such
model). What the engine keeps per token and layer is ONE line, the
normalised latent ``c`` (``kv_lora_rank`` values) followed by the rotated
shared key ``kr`` (``qk_rope_head_dim`` values): every head reads that line,
as its keys and as its values, in the absorbed form

    q_lat_h = W_uk_h q_nope_h          score = (q_lat_h . c + q_rope_h . kr) / sqrt(qk_head_dim)
    o_lat_h = sum_t p_t c_t            o_h   = W_uv_h^T o_lat_h

so no program expands a context's keys and values per head. The chunked
prefill uses the same absorbed form as the decode step: at a chunk of 128
queries over 3072 lines its products are within a sixth of the expanded
form's, it reads the pool's lines as they lie, and one attention function
serves every program.

Rotary positions rotate adjacent pairs ``(2i, 2i+1)`` (``rope_interleave``);
the published code de-interleaves ``q_rope`` and ``kr`` by one permutation
and rotates half-split, which is the same ``q . k``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, fields
from typing import Any, Dict, Optional, Tuple

from ..parallel import moe_dropless
from .families import PlainStack


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    intermediate_size: int = 128          # the dense layers' MLP
    moe_intermediate_size: int = 32       # one expert's MLP
    n_routed_experts: int = 8
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    kv_lora_rank: int = 16
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    max_position_embeddings: int = 128    # the limit served, not a table
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    # the experts this chip holds of every expert layer: (first, count);
    # None is all of them. The router always scores n_routed_experts
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        unsupported = {
            "q_lora_rank": self.q_lora_rank is not None,
            "rope_scaling": self.rope_scaling is not None,
            "scoring_func": self.scoring_func != "sigmoid",
            "topk_method": self.topk_method != "noaux_tc",
            "n_group/topk_group": (self.n_group, self.topk_group) != (1, 1),
            "moe_layer_freq": self.moe_layer_freq != 1,
            "tie_word_embeddings": self.tie_word_embeddings,
            "attention_bias": self.attention_bias,
            "hidden_act": self.hidden_act != "silu",
            "rope_interleave": not self.rope_interleave,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"deepseek_v3 family: no support for the given {bad}")

    @classmethod
    def from_published(cls, config: dict) -> "DeepseekV3Config":
        """From a ``config.json``-shaped dict; keys this block does not
        read (``head_dim``, ``num_key_value_heads``, ...) are ignored."""
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        if kw.get("experts_held") is not None:
            kw["experts_held"] = tuple(kw["experts_held"])
        return cls(**kw)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def line_width(self) -> int:
        """The values a token keeps in a layer: latent, then rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def line_stored(self) -> int:
        """The line as the pool stores it: zero-padded to whole lane rows
        of 128. Kanana-2's 576 values are four and a half rows; stored as
        576 the compiler re-tiles the whole pool inside the step (two
        copies of it a step) and the gather and both contractions take
        1.62 ms a layer on a v5e, stored as 640 they take 0.86 ms and the
        pool goes in and out untouched (tools/mla_line_layout.py, PR 27):
        10,240 B a token over 8 layers instead of 9,216."""
        return -(-self.line_width // 128) * 128

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def vocab(self) -> int:
        return self.vocab_size

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace


def init_params(cfg: DeepseekV3Config, seed: int = 0) -> Dict[str, Any]:
    """Seeded float32 parameters in the program's layout (the repo's other
    initialiser's rule: normal, std 0.02; norm gains one). The selection
    bias is small and non-zero so that choice and weight really differ."""
    import jax
    import jax.numpy as jnp

    D, H = cfg.hidden_size, cfg.num_attention_heads
    L, R = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    N, V = cfg.qk_nope_head_dim, cfg.v_head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 2 + 16 * cfg.num_hidden_layers))

    def dense(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    def mlp(width, lead=()):
        return {"w_gate": dense(*lead, D, width),
                "w_up": dense(*lead, D, width),
                "w_down": dense(*lead, width, D)}

    blocks = []
    for li in range(cfg.num_hidden_layers):
        blk = {"ln1": jnp.ones((D,), jnp.float32),
               "wq": dense(D, H * cfg.qk_head_dim),
               "wkva": dense(D, L + R),
               "kv_norm": jnp.ones((L,), jnp.float32),
               "wuk": dense(H, L, N), "wuv": dense(H, L, V),
               "wo": dense(H * V, D),
               "ln2": jnp.ones((D,), jnp.float32)}
        if cfg.is_dense(li):
            blk["mlp"] = mlp(cfg.intermediate_size)
        else:
            blk["router"] = dense(D, cfg.n_routed_experts)
            blk["router_bias"] = jax.random.normal(
                next(keys), (cfg.n_routed_experts,), jnp.float32) * 0.05
            blk["experts"] = mlp(cfg.moe_intermediate_size, (cfg.held[1],))
            blk["shared"] = mlp(cfg.n_shared_experts
                                * cfg.moe_intermediate_size)
        blocks.append(blk)
    return {"embed": dense(cfg.vocab_size, D), "blocks": blocks,
            "out_norm": jnp.ones((D,), jnp.float32),
            "head": dense(D, cfg.vocab_size)}


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x * gain / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rotate_pairs(x, pos, theta: float):
    """Rotary position embedding on adjacent pairs: ``x (..., R)`` at
    positions ``pos`` (broadcastable to ``x.shape[:-1]``), pair ``i`` by
    ``pos * theta**(-2i/R)``."""
    import jax.numpy as jnp

    R = x.shape[-1]
    freq = jnp.exp(jnp.arange(R // 2, dtype=jnp.float32)
                   * (-2.0 * math.log(theta) / R))
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], R // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


class DeepseekV3Family(PlainStack):
    """The block above as the paged engine takes it
    (``models/families.py`` has the contract)."""

    name = "deepseek_v3"
    attention_scopes = {"full": "mla"}
    window = None          # every layer sees the whole context
    passes = 1             # the stack once a token
    counters = moe_dropless.COUNTERS
    state_lines = ()       # no layer keeps a state a sequence
    drafts = 0             # no layer of it drafts a token
    serves_verify = False  # speculative verification: not in this family yet
    chunk_precision = "highest"    # f32 queries over a bf16 pool

    def __init__(self, cfg: DeepseekV3Config):
        self.cfg = cfg
        self.vocab = cfg.vocab_size
        self.layers = cfg.num_hidden_layers
        self.max_positions = cfg.max_position_embeddings
        self.layer_kinds = ("full",) * self.layers
        moe_layers = sum(not cfg.is_dense(li) for li in range(self.layers))
        # expert slots of one call: held experts times expert layers
        self.expert_slots = moe_layers * cfg.held[1]

    @property
    def cache_lines(self) -> tuple:
        return (self.cfg.line_stored,)

    def init_params(self, seed: int):
        return init_params(self.cfg, seed=seed)

    def stored(self, params):
        return params              # served as they come

    def with_positions(self, positions: int) -> "DeepseekV3Family":
        from dataclasses import replace

        return DeepseekV3Family(
            replace(self.cfg, max_position_embeddings=positions))

    def embed(self, p, toks, pos):
        import jax.numpy as jnp

        return p["embed"][toks].astype(jnp.float32)

    def blocks(self, p):
        return p["blocks"]

    def project(self, blk, x, pos, kind="full"):
        """``x (B, Q, D)`` at ``pos (B, Q)`` → the absorbed queries
        ``(B, Q, H, line)`` and the one line to write ``(B, Q, line)``."""
        import jax.numpy as jnp

        cfg = self.cfg
        H, N = cfg.num_attention_heads, cfg.qk_nope_head_dim
        h = rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
        q = (h @ blk["wq"]).reshape(*x.shape[:2], H, cfg.qk_head_dim)
        q_rope = rotate_pairs(q[..., N:], pos[..., None], cfg.rope_theta)
        q_lat = jnp.einsum("bqhn,hln->bqhl", q[..., :N], blk["wuk"])
        kva = h @ blk["wkva"]
        c = rms_norm(kva[..., :cfg.kv_lora_rank], blk["kv_norm"],
                     cfg.rms_norm_eps)
        kr = rotate_pairs(kva[..., cfg.kv_lora_rank:], pos, cfg.rope_theta)
        # both sides zero-padded to the stored line: zeros add nothing
        pad = cfg.line_stored - cfg.line_width
        q_pad = jnp.zeros((*q_lat.shape[:-1], pad), q_lat.dtype)
        line_pad = jnp.zeros((*c.shape[:-1], pad), c.dtype)
        return (jnp.concatenate([q_lat, q_rope, q_pad], axis=-1),
                (jnp.concatenate([c, kr, line_pad], axis=-1),))

    @property
    def chunk_heads(self) -> tuple:
        # every head reads the whole line, as keys and as values
        return (1, self.cfg.num_attention_heads)

    def chunk_output(self, blk, o):
        return self._project_out(blk, o)

    @property
    def attention_scale(self) -> float:
        return self.cfg.qk_head_dim ** -0.5

    def step_by_head(self, queries: int) -> bool:
        return False    # one line every head reads whole

    def step_queries(self, q):
        return q[:, 0]  # (S, H, line): the absorbed queries as they are

    def step_output(self, blk, o):
        return self._project_out(blk, o[:, None])

    def _project_out(self, blk, o):
        """Weighted sums of whole lines ``(B, Q, H, line)``: the latent part
        is the output; ``W_uv`` per head, then ``W_o``."""
        import jax.numpy as jnp

        o = jnp.einsum("bqhl,hlv->bqhv", o[..., :self.cfg.kv_lora_rank],
                       blk["wuv"])
        return o.reshape(*o.shape[:2], -1) @ blk["wo"]

    def ffn(self, blk, x, live):
        cfg = self.cfg
        h = rms_norm(x, blk["ln2"], cfg.rms_norm_eps)
        if "mlp" in blk:
            import jax

            m = blk["mlp"]
            with jax.named_scope("mlp"):
                return moe_dropless.gated_mlp(
                    h, m["w_gate"], m["w_up"], m["w_down"]), None
        B, Q, D = h.shape
        flat = h.reshape(B * Q, D)
        experts, weights = moe_dropless.route(
            blk["router"], blk["router_bias"], flat, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob,
            scoring=cfg.scoring_func)
        e, s = blk["experts"], blk["shared"]
        y, counts = moe_dropless.experts_ffn(
            e["w_gate"], e["w_up"], e["w_down"], flat, experts, weights,
            live=live.reshape(B * Q), first_expert=cfg.held[0])
        y = y + moe_dropless.shared_ffn(s["w_gate"], s["w_up"], s["w_down"],
                                        flat)
        return y.reshape(B, Q, D), counts

    def head(self, p, x):
        return rms_norm(x, p["out_norm"], self.cfg.rms_norm_eps) @ p["head"]
