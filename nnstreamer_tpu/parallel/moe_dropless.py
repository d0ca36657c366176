"""A dropless mixture-of-experts layer for serving (DeepSeek-V3's routing
and the softmax top-k of ``norm_topk_prob`` models).

``parallel/moe.py`` is the trainer's switch layer: top-1, a capacity per
expert, tokens over it dropped. A served model may drop nothing, so this
layer has no capacity: every one of a token's ``top_k`` assignments is
served, whatever the routing. ``experts_ffn`` says which assignments are
served here and counts them; the products are ``ops/moe_grouped.py``'s, in
one of two forms chosen there by the platform and the call's static shapes
and by nothing else. On a TPU a launch of at most 256 rows whose widths
are whole lanes (a decode step's 32 rows, a prefill launch's 256) runs a
Pallas kernel that streams each reached expert's three matrices from HBM
once and offers it every row under the row's router weight, zero where
not assigned; a wider launch, other widths and the CPU run the
assignments sorted by expert through three grouped products over exactly
the rows each expert received (``jax.lax.ragged_dot``), which is also the
oracle the kernel is pinned to.

Routing, as DeepSeek-V3 publishes it (``scoring_func`` sigmoid,
``topk_method`` noaux_tc with one group): scores ``s = sigmoid(W_g h)``
over all experts in float32; the ``top_k`` are the largest of ``s + b``
where ``b`` is a selection bias used for the choice only; the weights are
the chosen ``s`` renormalised and scaled. With ``scoring`` ``"softmax"``
(a model family whose configuration has no ``scoring_func`` and no bias)
``s = softmax(W_g h)`` and the ``top_k`` are the largest of ``s`` itself. The
router runs in float32 at ``Precision.HIGHEST``: on a TPU the default precision of a float32 product
is one bfloat16 pass, which picks other experts at near ties, and an expert
swapped is not a rounding error.

The layer is told which experts it holds (``first_expert`` and the leading
axis of its weights): it routes over all of them and computes the part of
the result its own experts give; assignments to experts held elsewhere add
nothing here. One chip that holds every expert runs it without an exchange.
"""
from __future__ import annotations

COUNTERS = ("moe_experts_touched", "moe_assignments", "moe_max_load",
            "moe_expert_slots")


def route(router_w, bias, h, top_k: int, scale: float,
          norm_topk_prob: bool = True, scoring: str = "sigmoid"):
    """``h (T, D)`` → ``(experts (T, k) int32, weights (T, k) float32)``.
    ``router_w (D, E)``; ``scoring`` is the family's configuration's:
    ``"sigmoid"`` with ``bias (E,)``, which decides the choice and never
    enters the weights, or ``"softmax"`` with none."""
    import jax
    import jax.numpy as jnp

    if (scoring, bias is None) not in (("sigmoid", False), ("softmax", True)):
        raise ValueError(f"route: scoring {scoring!r} with"
                         f"{'out' if bias is None else ''} a selection bias")
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            _, experts = jax.lax.top_k(scores, top_k)
        else:
            scores = jax.nn.sigmoid(logits)
            _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                       top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk_prob:
            chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), chosen * scale


def gated_mlp(x, w_gate, w_up, w_down, product=lambda x, w: x @ w):
    """``W_down(silu(W_gate x) * W_up x)``, by ``product(x, w)``."""
    import jax

    hidden = jax.nn.silu(product(x, w_gate)) * product(x, w_up)
    return product(hidden, w_down)


def experts_ffn(w_gate, w_up, w_down, h, experts, weights, live=None,
                first_expert: int = 0):
    """The routed experts' part of the layer: ``h (T, D)`` with its
    assignments → ``(y (T, D) float32, counts (4,) int32)``.

    ``w_gate``, ``w_up`` ``(E_held, D, F)`` and ``w_down (E_held, F, D)``
    are the experts ``first_expert ..`` held here. ``live (T,)`` marks the
    rows that are real: the others reach no expert and count nowhere.
    ``counts`` is ``COUNTERS``: experts held here that received a token,
    assignments served here, the largest number any one expert received,
    and the experts held (what the first is a share of).
    """
    import jax
    import jax.numpy as jnp

    from ..ops import moe_grouped

    with jax.named_scope("moe.experts"):
        held = w_gate.shape[0]
        local = experts - first_expert
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & live[:, None]
        # an assignment not served here names the expert past the last held
        flat = jnp.where(here, local, held)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[flat.reshape(-1)].add(
            1)[:held]
        y = moe_grouped.grouped_experts(h, w_gate, w_up, w_down, flat,
                                        weights, sizes)
        counts = jnp.stack([(sizes > 0).sum(), sizes.sum(), sizes.max(),
                            jnp.int32(held)])
        return y, counts.astype(jnp.int32)


def shared_ffn(w_gate, w_up, w_down, h):
    """The shared experts: one gated MLP that every token passes."""
    import jax

    with jax.named_scope("moe.shared"):
        return gated_mlp(h, w_gate, w_up, w_down)
