"""The Ouro-shaped block for the paged serving engine (``model_type:
ouro``; Ouro-2.6B is one such model; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): ONE stack of layers that every token
runs ``total_ut_steps`` times with the same weights, a cache line for every
pass of every layer, and an exit gate that says which pass's hidden state
the head reads.

The configuration carries the published ``config.json`` keys under their
published names. A layer has four RMS norms with gains (sandwich norms)::

    h <- h + N_g2(Attn(N_g1(h)))
    h <- h + N_g4(W_down(silu(W_gate u) * W_up u)),   u = N_g3(h)

``Attn`` is causal softmax attention of ``num_attention_heads`` query heads
over ``num_key_value_heads`` key/value heads of ``head_dim``, queries and
keys rotated on half-split pairs ``(i, i + head_dim / 2)`` by ``rope_theta``
over the whole head, scale ``head_dim ** -0.5``, no bias anywhere.

The loop: ``h_0 = E[tokens]``; pass ``t = 1 .. total_ut_steps`` runs the
layers in order over ``h_(t-1)``, then ``h_t = N_gf(.)`` (the final norm
closes EVERY pass and its output is what the next pass starts from) and the
gate ``lambda_t = sigmoid(w_g . h_t + b_g)``, one ``Linear(hidden, 1)`` that
the passes share. Pass ``t`` of layer ``l`` attends over the keys and
values that pass ``t`` of layer ``l`` wrote at earlier positions and never
another pass's: the engine keeps ``passes x layers`` lines a token
(``models/families.py``: ``passes``).

The exit rule (the published default path, no weighted mixing of logits):
``p_t = lambda_t * prod_(s<t)(1 - lambda_s)`` for ``t < T``, ``p_T =
prod_(s<T)(1 - lambda_s)``; ``c_t = sum_(s<=t) p_s``; a token's hidden state
is ``h_t`` at the first ``t`` with ``c_t >= early_exit_threshold``, the last
pass's if none; logits ``= W_head h_t`` (the final norm is in ``h_t``
already). Every pass runs for every token whatever its exit: later
positions read the lines. :meth:`OuroFamily.open_passes` and
:meth:`OuroFamily.close_pass` carry the rule's running sums across the
engine's loop over passes, under the ``jax.named_scope`` ``loop.exit``.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Optional, Tuple

from ..parallel import moe_dropless
from .deepseek_v3 import rms_norm
from .families import GroupedQueryLines
from .mellum import rope_frequencies, rotate_half


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int = 16
    intermediate_size: int = 128
    total_ut_steps: int = 4               # passes over the stack a token
    early_exit_threshold: float = 1.0
    layer_types: Optional[Tuple[str, ...]] = None  # None: all full_attention
    sliding_window: Optional[int] = None  # unread: no layer has a window
    use_sliding_window: bool = False
    max_window_layers: int = 0            # unread: layer_types is explicit
    rope_theta: float = 1e6
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 128    # the limit served, not a table
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"

    def __post_init__(self):
        n = self.num_hidden_layers
        unsupported = {
            "rope_scaling": self.rope_scaling is not None,
            "use_sliding_window": self.use_sliding_window,
            "layer_types": self.layer_types is not None and (
                len(self.layer_types) < n or any(
                    t != "full_attention" for t in self.layer_types[:n])),
            "tie_word_embeddings": self.tie_word_embeddings,
            "hidden_act": self.hidden_act != "silu",
            "num_key_value_heads": bool(
                self.num_attention_heads % self.num_key_value_heads),
            "total_ut_steps": self.total_ut_steps < 1,
            "early_exit_threshold": not (
                0.0 <= self.early_exit_threshold <= 1.0),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"ouro family: no support for the given {bad}")

    @classmethod
    def from_published(cls, config: dict) -> "OuroConfig":
        """From a ``config.json``-shaped dict; keys this block does not
        read are ignored."""
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
        """The values a token keeps in each of a pass-layer's two lines."""
        return self.num_key_value_heads * self.head_dim


def init_params(cfg: OuroConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded float32 parameters in the program's layout (the repo's other
    initialisers' rule: normal, std 0.02; norm gains one; the gate's bias
    zero)."""
    import jax
    import jax.numpy as jnp

    D, H, KV, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    F = cfg.intermediate_size
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 3 + 7 * cfg.num_hidden_layers))

    def dense(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    ones = jnp.ones((D,), jnp.float32)
    blocks = [{"ln1": ones, "ln2": ones, "ln3": ones, "ln4": ones,
               "wq": dense(D, H * Dh), "wk": dense(D, KV * Dh),
               "wv": dense(D, KV * Dh), "wo": dense(H * Dh, D),
               "mlp": {"w_gate": dense(D, F), "w_up": dense(D, F),
                       "w_down": dense(F, D)}}
              for _ in range(cfg.num_hidden_layers)]
    return {"embed": dense(cfg.vocab_size, D), "blocks": blocks,
            "out_norm": ones, "gate_w": dense(D),
            "gate_b": jnp.zeros((), jnp.float32),
            "head": dense(D, cfg.vocab_size)}


class OuroFamily(GroupedQueryLines):
    """The block above as the paged engine takes it
    (``models/families.py`` has the contract)."""

    name = "ouro"
    attention_scopes = {"full": "attn.full"}
    window = None          # every layer sees the whole context
    state_lines = ()       # no layer keeps a state a sequence
    # a verify round over passes: K queries a slot in every pass-layer
    serves_verify = False

    def __init__(self, cfg: OuroConfig):
        self.cfg = cfg
        self.vocab = cfg.vocab_size
        self.layers = cfg.num_hidden_layers
        self.max_positions = cfg.max_position_embeddings
        self.layer_kinds = ("full",) * self.layers
        self.passes = cfg.total_ut_steps
        # for each call of a program, the live rows whose logits came from
        # pass t (``close_pass`` counts them)
        self.counters = tuple(f"exit_pass_{t + 1}"
                              for t in range(self.passes))
        self._rope = rope_frequencies(
            cfg.head_dim, {"rope_type": "default",
                           "rope_theta": cfg.rope_theta})

    def init_params(self, seed: int):
        return init_params(self.cfg, seed=seed)

    def stored(self, params):
        """The tree with every layer's ``wq`` and ``wk`` as :meth:`project`
        reads them, ``(heads * head_dim, hidden)``: each re-laid once on the
        device, every other leaf the caller's own object. As ``(hidden,
        heads * head_dim)`` the compiler transposed all of them through HBM
        ahead of the loop over passes, in every call of both programs
        (PERF.md section 6, PR 45). A tree without layers (a probe built for
        its programs' shapes) has nothing to re-lay."""
        if "blocks" not in params:
            return params
        import jax
        import jax.numpy as jnp

        relaid = jax.jit(jnp.transpose)    # not donated: the caller's stay
        return {**params, "blocks": [
            {**blk, "wq": relaid(blk["wq"]), "wk": relaid(blk["wk"])}
            for blk in params["blocks"]]}

    def with_positions(self, positions: int) -> "OuroFamily":
        from dataclasses import replace

        return OuroFamily(
            replace(self.cfg, max_position_embeddings=positions))

    def embed(self, p, toks, pos):
        import jax.numpy as jnp

        return p["embed"][toks].astype(jnp.float32)

    def blocks(self, p):
        return p["blocks"]

    def project(self, blk, x, pos, kind="full"):
        """``x (B, Q, D)`` at ``pos (B, Q)`` → the rotated queries ``(B, Q,
        H, head_dim)`` and the two lines to write, rotated keys and values,
        ``(B, Q, kv_heads * head_dim)``."""
        import jax.numpy as jnp

        cfg = self.cfg
        H, KV, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        freq, factor = self._rope
        h = rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
        # the two matrices lie as ``stored`` left them: (N, D)
        q = jnp.einsum("bqd,nd->bqn", h, blk["wq"]).reshape(
            *x.shape[:2], H, Dh)
        k = jnp.einsum("bqd,nd->bqn", h, blk["wk"]).reshape(
            *x.shape[:2], KV, Dh)
        q = rotate_half(q, pos[..., None], freq, factor)
        k = rotate_half(k, pos[..., None], freq, factor)
        return q, (k.reshape(*x.shape[:2], KV * Dh), h @ blk["wv"])

    # the sandwich: what the residual adds is normed after the projection
    def step_output(self, blk, o):
        return rms_norm(super().step_output(blk, o), blk["ln2"],
                        self.cfg.rms_norm_eps)

    def chunk_output(self, blk, o):
        return rms_norm(super().chunk_output(blk, o), blk["ln2"],
                        self.cfg.rms_norm_eps)

    def ffn(self, blk, x, live):
        import jax

        m, eps = blk["mlp"], self.cfg.rms_norm_eps
        h = rms_norm(x, blk["ln3"], eps)
        with jax.named_scope("mlp"):
            y = moe_dropless.gated_mlp(h, m["w_gate"], m["w_up"],
                                       m["w_down"])
        return rms_norm(y, blk["ln4"], eps), None

    # -- the loop over passes ----------------------------------------------------
    def open_passes(self, x):
        """The exit rule's carry before the first pass, for activations
        ``x (B, Q, D)``: the rows chosen so far, the running sum ``c``, the
        running product of ``1 - lambda`` and who has left."""
        import jax.numpy as jnp

        rows = x.shape[:2]
        return (jnp.zeros_like(x), jnp.zeros(rows, jnp.float32),
                jnp.ones(rows, jnp.float32), jnp.zeros(rows, bool))

    def close_pass(self, p, x, carry, t, live):
        """What closes pass ``t`` (0-based, may be traced): the final norm
        (its output starts the next pass), the gate, the rule's running
        sums, and the rows that leave here → ``(x', carry', counts)``;
        ``counts[t]`` is the rows in ``live (B, Q)`` whose logits come from
        this pass."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        with jax.named_scope("loop.exit"):
            x = rms_norm(x, p["out_norm"], cfg.rms_norm_eps)
            chosen, c, survive, gone = carry
            lam = jax.nn.sigmoid(
                x @ p["gate_w"].astype(jnp.float32)
                + p["gate_b"].astype(jnp.float32))
            last = t == self.passes - 1
            c = c + jnp.where(last, survive, lam * survive)
            leaves = ~gone & ((c >= cfg.early_exit_threshold) | last)
            chosen = jnp.where(leaves[..., None], x, chosen)
            counts = jnp.zeros((self.passes,), jnp.int32).at[t].set(
                jnp.sum(leaves & live).astype(jnp.int32))
            return x, (chosen, c, survive * (1.0 - lam), gone | leaves), \
                counts

    def exit_rows(self, carry):
        """What the head reads once the passes are done: each row's hidden
        state at the pass it left."""
        return carry[0]

    def head(self, p, x):
        # ``x`` are the chosen rows: the final norm closed their pass
        return x @ p["head"]
