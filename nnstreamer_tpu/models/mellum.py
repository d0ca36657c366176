"""The Mellum-shaped block for the paged serving engine (``model_type:
mellum``; Mellum2-12B-A2.5B is one such model): grouped-query attention
with rotary positions, most layers seeing a sliding window and every
fourth the whole context, a dropless softmax-routed expert layer in every
block, RMS norms with a gain, no bias anywhere, an untied head.

The configuration carries the published ``config.json`` keys under their
published names. What the engine keeps per token and layer is two lines,
the rotated keys and the values of the ``num_key_value_heads`` heads side
by side (``kv_heads * head_dim`` values each); query head ``n`` reads the
block of key head ``n // (heads // kv_heads)``.

Layers are of two kinds (``layer_types``): a ``sliding_attention`` layer's
query at position ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``
and rotates by the default frequencies; a ``full_attention`` layer sees
every ``j <= i`` and rotates by YaRN's (``rope_parameters``): the
frequencies of the slow pairs divided by ``factor``, the fast ones kept,
a linear ramp between, and cosine and sine scaled by ``attention_factor``.
The family says the kinds to the engine (``layer_kinds``, ``window``),
which keeps a block table and a page allocator for each and gives a
window layer's pages back behind the window.

Rotary positions rotate the half-split pairs ``(i, i + head_dim / 2)``, as
the published code does (``rotate_half``). The frequencies are constants
of the program and the angles are made from the positions inside it: no
table the size of the serving limit exists.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, fields
from typing import Any, Dict, Optional, Tuple

from ..parallel import moe_dropless
from .deepseek_v3 import rms_norm
from .families import GroupedQueryLines

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    intermediate_size: int = 128          # a dense layer's MLP: none here
    moe_intermediate_size: int = 32       # one expert's MLP
    num_experts: int = 8
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + (
        "full_attention",)
    mlp_layer_types: Tuple[str, ...] = ("sparse",) * 4
    sliding_window: int = 16
    use_sliding_window: bool = True
    max_window_layers: int = 0            # unread: layer_types is explicit
    rope_parameters: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 128    # the limit served, not a table
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    # the experts this chip holds of every expert layer: (first, count);
    # None is all of them. The router always scores num_experts
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        ropes = self.rope_parameters or {}
        unsupported = {
            "tie_word_embeddings": self.tie_word_embeddings,
            "attention_bias": self.attention_bias,
            "hidden_act": self.hidden_act != "silu",
            "layer_types": (len(self.layer_types) < n or any(
                t not in _KINDS for t in self.layer_types)),
            "mlp_layer_types": (len(self.mlp_layer_types) < n or any(
                t != "sparse" for t in self.mlp_layer_types[:n])),
            "use_sliding_window": (not self.use_sliding_window and
                                   "sliding_attention"
                                   in self.layer_types[:n]),
            "rope_parameters": any(
                ropes.get(t, {}).get("rope_type", "default")
                not in ("default", "yarn") for t in _KINDS),
            "num_key_value_heads": bool(
                self.num_attention_heads % self.num_key_value_heads),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"mellum family: no support for the given {bad}")

    @classmethod
    def from_published(cls, config: dict) -> "MellumConfig":
        """From a ``config.json``-shaped dict; keys this block does not
        read are ignored. ``layer_types`` may be the published list: the
        first ``num_hidden_layers`` entries are the layers held here."""
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        for key in ("layer_types", "mlp_layer_types", "experts_held"):
            if kw.get(key) is not None:
                kw[key] = tuple(kw[key])
        return cls(**kw)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def line_width(self) -> int:
        """The values a token keeps in each of a layer's two lines."""
        return self.num_key_value_heads * self.head_dim

    def rope(self, layer_type: str) -> dict:
        return {"rope_type": "default", "rope_theta": 10000.0,
                **(self.rope_parameters or {}).get(layer_type, {})}


def rope_frequencies(head_dim: int, rope: dict) -> "tuple[Any, float]":
    """``(frequency of each of the head_dim/2 pairs, what cosine and sine
    are multiplied by)`` for one layer type's ``rope_parameters`` entry, in
    float64 on the host: constants of the program."""
    import numpy as np

    theta = float(rope["rope_theta"])
    half = head_dim // 2
    freq = theta ** (-2.0 * np.arange(half) / head_dim)
    if rope.get("rope_type", "default") == "default":
        return freq, 1.0
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def dim_of(rotations):  # the pair that turns this often over `original`
        return head_dim * math.log(original / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim_of(rope.get("beta_slow", 1))), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return freq / factor * ramp + freq * (1 - ramp), float(attention_factor)


def rotate_half(x, pos, freq, factor: float):
    """Rotary position embedding on half-split pairs: ``x (..., R)`` at
    positions ``pos`` (broadcastable to ``x.shape[:-1]``), pair
    ``(i, i + R/2)`` by ``pos * freq[i]``, cosine and sine times
    ``factor``."""
    import jax.numpy as jnp

    ang = pos[..., None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def init_params(cfg: MellumConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded float32 parameters in the program's layout (the repo's other
    initialisers' rule: normal, std 0.02; norm gains one)."""
    import jax
    import jax.numpy as jnp

    D, H, KV, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    F = cfg.moe_intermediate_size
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 2 + 8 * cfg.num_hidden_layers))

    def dense(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    blocks = [{"ln1": jnp.ones((D,), jnp.float32),
               "wq": dense(D, H * Dh), "wk": dense(D, KV * Dh),
               "wv": dense(D, KV * Dh), "wo": dense(H * Dh, D),
               "ln2": jnp.ones((D,), jnp.float32),
               "router": dense(D, cfg.num_experts),
               "experts": {"w_gate": dense(cfg.held[1], D, F),
                           "w_up": dense(cfg.held[1], D, F),
                           "w_down": dense(cfg.held[1], F, D)}}
              for _ in range(cfg.num_hidden_layers)]
    return {"embed": dense(cfg.vocab_size, D), "blocks": blocks,
            "out_norm": jnp.ones((D,), jnp.float32),
            "head": dense(D, cfg.vocab_size)}


class MellumFamily(GroupedQueryLines):
    """The block above as the paged engine takes it
    (``models/families.py`` has the contract)."""

    name = "mellum"
    attention_scopes = {"full": "attn.full", "window": "attn.window"}
    counters = moe_dropless.COUNTERS
    state_lines = ()       # no layer keeps a state a sequence
    serves_verify = False  # window layers under speculative verify: not yet

    def __init__(self, cfg: MellumConfig):
        self.cfg = cfg
        self.vocab = cfg.vocab_size
        self.layers = cfg.num_hidden_layers
        self.max_positions = cfg.max_position_embeddings
        self.layer_kinds = tuple(
            _KINDS[t] for t in cfg.layer_types[:self.layers])
        self.window = cfg.sliding_window
        self.expert_slots = self.layers * cfg.held[1]
        self._rope = {kind: rope_frequencies(cfg.head_dim, cfg.rope(t))
                      for t, kind in _KINDS.items()}

    def init_params(self, seed: int):
        return init_params(self.cfg, seed=seed)

    def with_positions(self, positions: int) -> "MellumFamily":
        from dataclasses import replace

        return MellumFamily(
            replace(self.cfg, max_position_embeddings=positions))

    def embed(self, p, toks, pos):
        import jax.numpy as jnp

        return p["embed"][toks].astype(jnp.float32)

    def blocks(self, p):
        return p["blocks"]

    def project(self, blk, x, pos, kind):
        """``x (B, Q, D)`` at ``pos (B, Q)`` in a layer of ``kind`` → the
        rotated queries ``(B, Q, H, head_dim)`` and the two lines to
        write, rotated keys and values, ``(B, Q, kv_heads * head_dim)``."""
        cfg = self.cfg
        H, KV, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        freq, factor = self._rope[kind]
        h = rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
        q = (h @ blk["wq"]).reshape(*x.shape[:2], H, Dh)
        k = (h @ blk["wk"]).reshape(*x.shape[:2], KV, Dh)
        q = rotate_half(q, pos[..., None], freq, factor)
        k = rotate_half(k, pos[..., None], freq, factor)
        return q, (k.reshape(*x.shape[:2], KV * Dh), h @ blk["wv"])

    def ffn(self, blk, x, live):
        cfg = self.cfg
        h = rms_norm(x, blk["ln2"], cfg.rms_norm_eps)
        B, Q, D = h.shape
        flat = h.reshape(B * Q, D)
        experts, weights = moe_dropless.route(
            blk["router"], None, flat, cfg.num_experts_per_tok, 1.0,
            cfg.norm_topk_prob, scoring="softmax")
        e = blk["experts"]
        y, counts = moe_dropless.experts_ffn(
            e["w_gate"], e["w_up"], e["w_down"], flat, experts, weights,
            live=live.reshape(B * Q), first_expert=cfg.held[0])
        return y.reshape(B, Q, D), counts

    def head(self, p, x):
        return rms_norm(x, p["out_norm"], self.cfg.rms_norm_eps) @ p["head"]
