"""The Jamba-shaped block for the paged serving engine (``model_type:
jamba``; AI21-Jamba2-3B is one such model): most layers are state-space
(Mamba-1) mixers that keep a state a *sequence*, every
``attn_layer_period``-th is grouped-query attention that keeps lines a
*token*; every layer ends in a dense gated (SiLU) MLP; RMS norms with a
gain, no bias but the conv's and the step size's, a tied head.

The configuration carries the published ``config.json`` keys under their
published names. Layer ``i`` is an attention layer where ``i %
attn_layer_period == attn_layer_offset``, and a state layer otherwise.

An attention layer has no rotary or learned positions (the config has no
key for either: the state layers carry position): query head ``n`` reads
key/value head ``n // (heads // kv_heads)``, causal softmax at ``head_dim
** -0.5``. What the engine keeps a token is two lines, keys and values of
the ``num_key_value_heads`` heads side by side (one 128-wide line each for
a multi-query model).

A state layer (``Di = mamba_expand * hidden_size`` channels, ``N =
mamba_d_state``, ``K = mamba_d_conv``, ``R = mamba_dt_rank``)::

    [u, z] = norm1(x) W_in                          ssm.in
    u <- silu(conv_K(u) + b_conv)                   ssm.conv  (causal, depthwise)
    [dt, B, C] = u W_x, each RMS-normalised         ssm.x
    dt <- softplus(dt W_dt + b_dt)
    h <- exp(dt * A) * h + (dt * u) (x) B           ssm.scan  (A = -exp(A_log))
    y = C . h + D * u
    out = (y * silu(z)) W_out                       ssm.out

What a sequence keeps of it, whatever its length (``state_lines``): the
conv's last ``K - 1`` inputs, flat, oldest first, in the cache's type
(stored ``(3, Di)`` a slot, ``_step`` compiled for a v5e copies the whole
array in and out to re-tile its three rows, 2 × 102 MB a step at 128
slots, and ``_prefill_chunk`` likewise; flat, ``3 * Di`` is whole lanes
and nothing is copied: ``tools/ssm_state_layout.py``, PR 33), and the scan
state ``h (N, Di)`` in float32
with the channels along the lanes (``(Di, N)`` would pad 16 to 128 lanes,
eight times the bytes). The engine keeps both a *slot*, hands its arrays
to :meth:`JambaFamily.mix_step` (one token of every slot, in place) and one
slot's rows to :meth:`JambaFamily.mix_chunk` (its launch), zeroed where the
launch starts a sequence, and stores what comes back.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Optional

from ..ops import selective_scan
from ..parallel import moe_dropless
from .deepseek_v3 import rms_norm
from .families import GroupedQueryLines


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 8
    num_attention_heads: int = 4
    num_key_value_heads: int = 1
    intermediate_size: int = 128
    attn_layer_period: int = 4
    attn_layer_offset: int = 2
    expert_layer_period: int = 2          # select nothing: num_experts is 1
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 8
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 128    # the limit served, not a table
    tie_word_embeddings: bool = True
    sliding_window: Optional[int] = None
    hidden_act: str = "silu"

    def __post_init__(self):
        unsupported = {
            "num_experts": self.num_experts > 1,
            "sliding_window": self.sliding_window is not None,
            "mamba_proj_bias": self.mamba_proj_bias,
            "mamba_conv_bias": not self.mamba_conv_bias,
            "tie_word_embeddings": not self.tie_word_embeddings,
            "hidden_act": self.hidden_act != "silu",
            "num_key_value_heads": bool(
                self.num_attention_heads % self.num_key_value_heads),
            "attn_layer_offset": not (
                0 <= self.attn_layer_offset < self.attn_layer_period),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"jamba family: no support for the given {bad}")

    @classmethod
    def from_published(cls, config: dict) -> "JambaConfig":
        """From a ``config.json``-shaped dict; keys this block does not
        read (``use_mamba_kernels``, ``num_logits_to_keep``, ...) are
        ignored."""
        names = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in config.items() if k in names})

    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def line_width(self) -> int:
        """The values a token keeps in each of an attention layer's two
        lines."""
        return self.num_key_value_heads * self.head_dim

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset


def init_params(cfg: JambaConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded float32 parameters in the program's layout: matrices normal
    with std 0.02 and norm gains one (the repo's other initialisers'
    rule); the state layers' constants by the published initialisation:
    ``A_log = log(1..N)`` a channel, ``b_dt`` such that ``softplus(b_dt)``
    is log-uniform in 0.001-0.1, ``D`` one. ``a_log`` lies ``(N, Di)``:
    channels last, as the state does."""
    import jax
    import jax.numpy as jnp

    D, H, KV, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    Di, N, K, R = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                   cfg.mamba_dt_rank)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 2 + 12 * cfg.num_hidden_layers))

    def dense(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    def mixer():
        step = jnp.exp(jax.random.uniform(
            next(keys), (Di,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return {"w_in": dense(D, 2 * Di), "conv_w": dense(K, Di) * 10.0,
                "conv_b": jnp.zeros((Di,), jnp.float32),
                "w_x": dense(Di, R + 2 * N),
                "dt_norm": jnp.ones((R,), jnp.float32),
                "b_norm": jnp.ones((N,), jnp.float32),
                "c_norm": jnp.ones((N,), jnp.float32),
                "w_dt": dense(R, Di),
                "b_dt": jnp.log(jnp.expm1(step)),  # softplus's inverse
                "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32))[:, None], (N, Di)),
                "d": jnp.ones((Di,), jnp.float32),
                "w_out": dense(Di, D)}

    blocks = []
    for li in range(cfg.num_hidden_layers):
        blk = {"ln1": jnp.ones((D,), jnp.float32),
               "ln2": jnp.ones((D,), jnp.float32),
               "mlp": {"w_gate": dense(D, cfg.intermediate_size),
                       "w_up": dense(D, cfg.intermediate_size),
                       "w_down": dense(cfg.intermediate_size, D)}}
        if cfg.is_attention(li):
            blk.update(wq=dense(D, H * Dh), wk=dense(D, KV * Dh),
                       wv=dense(D, KV * Dh), wo=dense(H * Dh, D))
        else:
            blk["mixer"] = mixer()
        blocks.append(blk)
    return {"embed": dense(cfg.vocab_size, D), "blocks": blocks,
            "out_norm": jnp.ones((D,), jnp.float32)}


class JambaFamily(GroupedQueryLines):
    """The block above as the paged engine takes it
    (``models/families.py`` has the contract)."""

    name = "jamba"
    attention_scopes = {"full": "attn.full"}
    window = None          # an attention layer sees the whole context
    counters = ()          # no expert layer: every MLP is dense
    # a rollback needs the state at the accepted token: not kept
    serves_verify = False

    def __init__(self, cfg: JambaConfig):
        self.cfg = cfg
        self.vocab = cfg.vocab_size
        self.layers = cfg.num_hidden_layers
        self.max_positions = cfg.max_position_embeddings
        self.layer_kinds = tuple(
            "full" if cfg.is_attention(li) else "state"
            for li in range(self.layers))
        # what a slot keeps a state layer: (shape, dtype); a dtype of None
        # is the cache's
        self.state_lines = (
            (((cfg.mamba_d_conv - 1) * cfg.d_inner,), None),
            ((cfg.mamba_d_state, cfg.d_inner), "float32"))

    def init_params(self, seed: int):
        return init_params(self.cfg, seed=seed)

    def with_positions(self, positions: int) -> "JambaFamily":
        from dataclasses import replace

        return JambaFamily(
            replace(self.cfg, max_position_embeddings=positions))

    def embed(self, p, toks, pos):
        import jax.numpy as jnp

        return p["embed"][toks].astype(jnp.float32)

    def blocks(self, p):
        return p["blocks"]

    # -- attention layers ------------------------------------------------------
    def project(self, blk, x, pos, kind="full"):
        """``x (B, Q, D)`` → the queries ``(B, Q, H, head_dim)`` and the
        two lines to write, keys and values ``(B, Q, kv_heads *
        head_dim)``. No positions: ``pos`` is unread."""
        cfg = self.cfg
        h = rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
        q = (h @ blk["wq"]).reshape(*x.shape[:2], cfg.num_attention_heads,
                                    cfg.head_dim)
        return q, (h @ blk["wk"], h @ blk["wv"])

    # -- state layers -----------------------------------------------------------
    def _selective(self, m, u):
        """The conv's output ``u (..., Di)`` → ``(dt (..., Di), B, C
        (..., N))``: the token's step size and maps."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        R, N = cfg.mamba_dt_rank, cfg.mamba_d_state
        dbc = u @ m["w_x"]
        dt = rms_norm(dbc[..., :R], m["dt_norm"], cfg.rms_norm_eps)
        b = rms_norm(dbc[..., R:R + N], m["b_norm"], cfg.rms_norm_eps)
        c = rms_norm(dbc[..., R + N:], m["c_norm"], cfg.rms_norm_eps)
        dt = jax.nn.softplus(dt @ m["w_dt"] + m["b_dt"].astype(jnp.float32))
        return dt, b, c

    def _mix(self, blk, x, conv, taps, scan):
        """What both programs share: ``x (rows, D)`` and the conv's
        earlier inputs ``conv (rows or K - 1, ...)`` → the layer's output;
        ``taps(conv, u)`` gives the K inputs under each row's conv and the
        state's next inputs, ``scan(dt, u, a, b, c, d)`` the recurrence."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        m = blk["mixer"]
        with jax.named_scope("ssm.in"):
            h = rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
            u, z = jnp.split(h @ m["w_in"], 2, axis=-1)
        with jax.named_scope("ssm.conv"):
            under, conv = taps(conv, u)
            w = m["conv_w"].astype(jnp.float32)
            u = jax.nn.silu(sum(w[k] * under[k] for k in range(w.shape[0]))
                            + m["conv_b"].astype(jnp.float32))
        with jax.named_scope("ssm.x"):
            dt, b, c = self._selective(m, u)
            a = -jnp.exp(m["a_log"].astype(jnp.float32))
        with jax.named_scope("ssm.scan"):
            y, state = scan(dt, u, a, b, c, m["d"].astype(jnp.float32))
        with jax.named_scope("ssm.out"):
            out = (y * jax.nn.silu(z)) @ m["w_out"]
        return out, (conv, state)

    def mix_step(self, blk, x, states, layer, live):
        """One token a slot: ``x (S, D)``; ``states`` = the engine's whole
        arrays, the conv's inputs ``(layers, S, (K - 1) * Di)`` and the scan
        states ``(layers, S, N, Di)``, of which state layer ``layer``'s rows
        are this layer's → what the residual adds ``(S, D)`` and the arrays
        with those rows advanced for the slots in ``live (S,)`` and every
        other row as it was (the scan states in place:
        ``ops/selective_scan.py`` ``slots_update``)."""
        import jax.numpy as jnp

        Di = self.cfg.d_inner
        conv_all, h_all = states

        def taps(conv, u):
            old = [conv[:, k * Di:(k + 1) * Di].astype(jnp.float32)
                   for k in range(conv.shape[1] // Di)]
            # a dead slot's inputs are stored as they were read
            new = jnp.concatenate([conv[:, Di:], u.astype(conv.dtype)],
                                  axis=1)
            return old + [u], conv_all.at[layer].set(
                jnp.where(live[:, None], new, conv))

        return self._mix(
            blk, x, conv_all[layer], taps,
            lambda *args: selective_scan.slots_update(
                h_all, layer, live, *args))

    def mix_chunk(self, blk, x, n_valid, state):
        """One slot's launch: ``x (C, D)`` of which the first ``n_valid``
        rows are real, ``state`` = the conv's inputs ``((K - 1) * Di,)``
        and the scan state ``(N, Di)`` → ``(C, D)`` and the state after the
        last real row (a padded row moves neither part)."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        Di, K = cfg.d_inner, cfg.mamba_d_conv
        conv, h = state

        def taps(conv, u):
            C = u.shape[0]
            rows = jnp.concatenate(
                [conv.reshape(K - 1, Di).astype(jnp.float32), u], axis=0)
            # the state's next inputs: the last K - 1 before row n_valid
            # (the old ones where the launch is shorter than that)
            last = jax.lax.dynamic_slice(rows, (n_valid, 0), (K - 1, Di))
            return ([rows[k:k + C] for k in range(K)],
                    last.astype(conv.dtype).reshape(-1))

        return self._mix(
            blk, x, conv, taps,
            lambda *args: selective_scan.chunk_scan(h, *args, n_valid))

    def ffn(self, blk, x, live):
        import jax

        m = blk["mlp"]
        h = rms_norm(x, blk["ln2"], self.cfg.rms_norm_eps)
        with jax.named_scope("mlp"):
            return moe_dropless.gated_mlp(
                h, m["w_gate"], m["w_up"], m["w_down"]), None

    def head(self, p, x):
        return rms_norm(x, p["out_norm"], self.cfg.rms_norm_eps) @ p[
            "embed"].T
