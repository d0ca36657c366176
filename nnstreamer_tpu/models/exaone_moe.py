"""The EXAONE-MoE-shaped block for the paged serving engine (``model_type:
exaone_moe``; K-EXAONE-236B-A23B is one such model): grouped-query
attention whose queries and keys are RMS-normed a head, most layers seeing
a sliding window and every fourth the whole context, a gated (SiLU) MLP in
the leading dense layers and a dropless sigmoid-routed expert layer with a
shared expert after them, RMS norms with a gain, no bias anywhere, an
untied head, and one multi-token-prediction (MTP) layer that drafts the
token after the next.

The configuration carries the published ``config.json`` keys under their
published names. What the engine keeps per token and layer is two lines,
the normed (and, on a window layer, rotated) keys and the values of the
``num_key_value_heads`` heads side by side; query head ``n`` reads the
block of key head ``n // (heads // kv_heads)``.

Layers are of two kinds (``layer_types``): a ``sliding_attention`` layer's
query at position ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``
and rotates queries and keys by the default frequencies, half-split pairs;
a ``full_attention`` layer sees every ``j <= i`` and rotates nothing (the
family's hybrid rule: positions live on the local layers only). The
feed-forward of a layer is by ``mlp_layer_types``: ``dense`` a gated MLP
of ``intermediate_size``, ``sparse`` the router's ``num_experts_per_tok``
of ``num_experts`` by ``sigmoid`` scores plus a selection bias, their
scores renormalised and scaled by ``routed_scaling_factor``, plus the
shared expert (``parallel/moe_dropless.py`` as it stands).

Two shares of a layer may be held here instead of the whole
(``models/families.py``; the deployment divides every layer over chips):

* ``experts_held (first, count)`` — the routed experts this chip holds of
  every expert layer; the router scores all ``num_experts`` and what the
  absent experts would add is left out;
* ``vocab_held (first, count)`` — the rows of the embedding and the
  columns of the head this chip holds. The vocabulary served is then the
  slice: ``count`` ids, ``0 .. count - 1`` for the rows ``first ..``;
  logits, argmax and the served ids are over it and nothing stands in for
  the absent rows.

The MTP layer (``num_nextn_predict_layers`` 1, ``mtp_layer_types``
``["full_attention"]``): ``u_i = W_eh [RMSNorm(Emb(t_{i+1})) ;
RMSNorm(x_{L,i})]`` from the stack's output ``x_{L,i}`` (before the output
norm) and the token after it, one block of the full-attention kind with a
sparse feed-forward, its own output norm, the main model's embedding and
head: its scores at row ``i`` are of token ``i + 2``. The family says
``drafts = 1`` and the engine runs it as the draft of a two-position
verify round (``serving/lm_engine.py`` ``_round``), keeping one more
full-kind cache layer for its block.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Optional, Tuple

from ..parallel import moe_dropless
from .deepseek_v3 import DeepseekV3Family, rms_norm
from .families import GroupedQueryLines
from .mellum import rope_frequencies, rotate_half

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


@dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 5
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    intermediate_size: int = 128          # a dense layer's MLP
    moe_intermediate_size: int = 32       # one expert's MLP
    num_experts: int = 8
    num_experts_per_tok: int = 2
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1        # unread: mlp_layer_types says it
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + (
        "full_attention", "sliding_attention")
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 4
    sliding_window: int = 16
    rope_parameters: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 128    # the limit served, not a table
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    num_nextn_predict_layers: int = 1
    mtp_layer_types: Tuple[str, ...] = ("full_attention",)
    # this chip's share of every layer: (first, count) of the routed
    # experts and of the vocabulary's rows; None is all of them. The
    # router always scores num_experts
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        rope = self.rope_parameters or {}
        unsupported = {
            "tie_word_embeddings": self.tie_word_embeddings,
            "attention_bias": self.attention_bias,
            "hidden_act": self.hidden_act != "silu",
            "scoring_func": self.scoring_func != "sigmoid",
            "n_group/topk_group": (self.n_group, self.topk_group) != (1, 1),
            "layer_types": (len(self.layer_types) < n or any(
                t not in _KINDS for t in self.layer_types)),
            "mlp_layer_types": (len(self.mlp_layer_types) < n or any(
                t not in ("dense", "sparse")
                for t in self.mlp_layer_types[:n])),
            "rope_parameters": rope.get("rope_type",
                                        "default") != "default",
            "num_key_value_heads": bool(
                self.num_attention_heads % self.num_key_value_heads),
            "num_nextn_predict_layers": self.num_nextn_predict_layers
            not in (0, 1),
            "mtp_layer_types": (
                tuple(self.mtp_layer_types[:self.num_nextn_predict_layers])
                != ("full_attention",) * self.num_nextn_predict_layers),
            "experts_held": not self._share(self.experts_held,
                                            self.num_experts),
            "vocab_held": not self._share(self.vocab_held, self.vocab_size),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"exaone_moe family: no support for the given {bad}")

    @staticmethod
    def _share(held, whole: int) -> bool:
        return held is None or (held[0] >= 0 and held[1] >= 1
                                and held[0] + held[1] <= whole)

    @classmethod
    def from_published(cls, config: dict) -> "ExaoneMoeConfig":
        """From a ``config.json``-shaped dict; keys this block does not
        read are ignored. ``layer_types`` and ``mlp_layer_types`` may be
        the published lists: the first ``num_hidden_layers`` entries are
        the layers held here."""
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        for key in ("layer_types", "mlp_layer_types", "mtp_layer_types",
                    "experts_held", "vocab_held"):
            if kw.get(key) is not None:
                kw[key] = tuple(kw[key])
        return cls(**kw)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def rows_held(self) -> Tuple[int, int]:
        return self.vocab_held or (0, self.vocab_size)

    @property
    def vocab(self) -> int:
        """The vocabulary served: the rows held here."""
        return self.rows_held[1]

    @property
    def line_width(self) -> int:
        """The values a token keeps in each of a layer's two lines."""
        return self.num_key_value_heads * self.head_dim

    def is_dense(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "dense"


def init_params(cfg: ExaoneMoeConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded float32 parameters in the program's layout (the repo's other
    initialisers' rule: normal, std 0.02; norm gains one; the selection
    bias small and non-zero so that choice and weight really differ). The
    embedding and the head are the rows held (``vocab_held``), the experts
    the ones held (``experts_held``)."""
    import jax
    import jax.numpy as jnp

    D, H, KV, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    n = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4 + 16 * n))

    def dense(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    def mlp(width, lead=()):
        return {"w_gate": dense(*lead, D, width),
                "w_up": dense(*lead, D, width),
                "w_down": dense(*lead, width, D)}

    def block(is_dense: bool):
        blk = {"ln1": jnp.ones((D,), jnp.float32),
               "wq": dense(D, H * Dh), "wk": dense(D, KV * Dh),
               "wv": dense(D, KV * Dh), "wo": dense(H * Dh, D),
               "q_norm": jnp.ones((Dh,), jnp.float32),
               "k_norm": jnp.ones((Dh,), jnp.float32),
               "ln2": jnp.ones((D,), jnp.float32)}
        if is_dense:
            blk["mlp"] = mlp(cfg.intermediate_size)
        else:
            blk["router"] = dense(D, cfg.num_experts)
            blk["router_bias"] = jax.random.normal(
                next(keys), (cfg.num_experts,), jnp.float32) * 0.05
            blk["experts"] = mlp(cfg.moe_intermediate_size, (cfg.held[1],))
            blk["shared"] = mlp(cfg.num_shared_experts
                                * cfg.moe_intermediate_size)
        return blk

    params = {"embed": dense(cfg.vocab, D),
              "blocks": [block(cfg.is_dense(li))
                         for li in range(cfg.num_hidden_layers)],
              "out_norm": jnp.ones((D,), jnp.float32),
              "head": dense(D, cfg.vocab)}
    if cfg.num_nextn_predict_layers:
        params["mtp"] = {"enorm": jnp.ones((D,), jnp.float32),
                         "hnorm": jnp.ones((D,), jnp.float32),
                         "eh_proj": dense(2 * D, D),
                         "block": block(False),
                         "out_norm": jnp.ones((D,), jnp.float32)}
    return params


class ExaoneMoeFamily(GroupedQueryLines):
    """The block above as the paged engine takes it
    (``models/families.py`` has the contract)."""

    name = "exaone_moe"
    attention_scopes = {"full": "attn.full", "window": "attn.window"}
    counters = moe_dropless.COUNTERS
    state_lines = ()       # no layer keeps a state a sequence
    serves_verify = False  # a host-side draft over two kinds: not served

    def __init__(self, cfg: ExaoneMoeConfig):
        self.cfg = cfg
        self.vocab = cfg.vocab
        self.layers = cfg.num_hidden_layers
        self.max_positions = cfg.max_position_embeddings
        self.layer_kinds = tuple(
            _KINDS[t] for t in cfg.layer_types[:self.layers])
        self.window = (cfg.sliding_window if "window" in self.layer_kinds
                       else None)
        # the tokens its MTP layer drafts a round, and the kind of the
        # cache layer the engine keeps for each drafting block
        self.drafts = cfg.num_nextn_predict_layers
        self.draft_kind = "full"
        sparse = sum(not cfg.is_dense(li) for li in range(self.layers))
        # expert slots of one call of a program without the MTP block; the
        # round's are ``(sparse + drafts) * held`` (``moe_expert_slots``,
        # counted by the layers themselves, says which ran)
        self.expert_slots = sparse * cfg.held[1]
        theta = {"rope_type": "default", "rope_theta": 10000.0,
                 **(cfg.rope_parameters or {})}
        self._rope = rope_frequencies(cfg.head_dim, theta)

    def init_params(self, seed: int):
        return init_params(self.cfg, seed=seed)

    def with_positions(self, positions: int) -> "ExaoneMoeFamily":
        from dataclasses import replace

        return ExaoneMoeFamily(
            replace(self.cfg, max_position_embeddings=positions))

    def embed(self, p, toks, pos):
        import jax.numpy as jnp

        return p["embed"][toks].astype(jnp.float32)

    def blocks(self, p):
        return p["blocks"]

    def project(self, blk, x, pos, kind):
        """``x (B, Q, D)`` at ``pos (B, Q)`` in a layer of ``kind`` → the
        normed queries ``(B, Q, H, head_dim)`` and the two lines to write,
        normed keys and values, ``(B, Q, kv_heads * head_dim)``; queries
        and keys rotated on a window layer, as they are on a full one."""
        cfg = self.cfg
        H, KV, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        h = rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
        q = (h @ blk["wq"]).reshape(*x.shape[:2], H, Dh)
        k = (h @ blk["wk"]).reshape(*x.shape[:2], KV, Dh)
        q = rms_norm(q, blk["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, blk["k_norm"], cfg.rms_norm_eps)
        if kind == "window":
            freq, factor = self._rope
            q = rotate_half(q, pos[..., None], freq, factor)
            k = rotate_half(k, pos[..., None], freq, factor)
        return q, (k.reshape(*x.shape[:2], KV * Dh), h @ blk["wv"])

    def step_by_head(self, queries: int) -> bool:
        """Whether a step of ``queries`` rows a slot hands the kernel
        head-wide rows: the kernel's rule, asked at this block's shapes."""
        from ..ops.paged_attention import contracts_by_head

        cfg = self.cfg
        return contracts_by_head(3 * queries * cfg.num_attention_heads,
                                 cfg.num_key_value_heads, cfg.head_dim)

    def step_queries(self, q):
        """``q (S, K, H, head_dim)``, ``K`` rows a slot (a step's one, a
        round's two) → where the kernel contracts by key head
        (``step_by_head``) the rows as they are, those of one key head
        together, ``(S, KV * K * G, head_dim)`` in the order ``(KV, K,
        G)``; else ``(S, K * H, line)``: row ``r * H + n`` holds head
        ``n``'s query of row ``r`` in the block of its key head."""
        import jax.numpy as jnp

        S, K, H, Dh = q.shape
        KV = self.cfg.num_key_value_heads
        if self.step_by_head(K):
            return q.reshape(S, K, KV, H // KV, Dh).swapaxes(1, 2).reshape(
                S, K * H, Dh)
        tiled = jnp.tile(q, (1, 1, 1, KV))
        return jnp.where(self._own()[None, None], tiled, 0.0).reshape(
            S, K * H, -1)

    def step_output(self, blk, o):
        """What the kernel gave for ``step_queries``' rows → ``(S, K, D)``:
        head-wide rows ``(S, KV * K * G, head_dim)`` back in the order
        ``(K, H)``, or of every whole-line row ``(S, K * H, line)`` its own
        key head's block; through the output projection."""
        import jax.numpy as jnp

        cfg = self.cfg
        S = o.shape[0]
        H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
        if self.step_by_head(o.shape[1] // H):
            o = o.reshape(S, KV, -1, H // KV, cfg.head_dim).swapaxes(1, 2)
            return o.reshape(S, o.shape[1], -1) @ blk["wo"]
        o = o.reshape(S, -1, H, KV, cfg.head_dim)
        own = self._own().reshape(H, KV, cfg.head_dim)
        o = jnp.where(own[None, None], o, 0.0).sum(axis=3)
        return o.reshape(S, o.shape[1], -1) @ blk["wo"]

    # the dense-or-sparse feed-forward is the latent family's, key for key
    # (``mlp`` or ``router`` + ``router_bias`` + ``experts`` + ``shared``
    # in the block, the same configuration names): norm, sigmoid route,
    # this chip's experts' part, the shared expert
    ffn = DeepseekV3Family.ffn

    def head(self, p, x):
        return rms_norm(x, p["out_norm"], self.cfg.rms_norm_eps) @ p["head"]

    # -- the MTP layer: what a family that drafts owes the round -------------
    def mtp_block(self, p):
        """The drafting block's parameters: a block of ``draft_kind``."""
        return p["mtp"]["block"]

    def mtp_input(self, p, x, toks):
        """``x (B, Q, D)``, the stack's output at some positions, and
        ``toks (B, Q)``, the tokens after them → the MTP block's input."""
        import jax
        import jax.numpy as jnp

        m, eps = p["mtp"], self.cfg.rms_norm_eps
        with jax.named_scope("mtp.embed"):
            e = rms_norm(p["embed"][toks].astype(jnp.float32), m["enorm"],
                         eps)
            h = rms_norm(x, m["hnorm"], eps)
            return jnp.concatenate([e, h], axis=-1) @ m["eh_proj"]

    def mtp_head(self, p, x):
        """The MTP block's output rows ``x (n, D)`` → their scores of the
        token two positions on: its own norm, the main model's head."""
        import jax

        with jax.named_scope("mtp.head"):
            return rms_norm(x, p["mtp"]["out_norm"],
                            self.cfg.rms_norm_eps) @ p["head"]
