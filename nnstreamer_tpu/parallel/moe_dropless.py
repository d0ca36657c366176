"""A dropless mixture-of-experts layer for serving (DeepSeek-V3's routing
and the softmax top-k of ``norm_topk_prob`` models).

``parallel/moe.py`` is the trainer's switch layer: top-1, a capacity per
expert, tokens over it dropped. A served model may drop nothing, so this
layer has no capacity: every token's ``top_k`` assignments are sorted by
expert and the experts' three matrices are applied as grouped products
over exactly the rows each expert received (``jax.lax.ragged_dot``).

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

    with jax.named_scope("moe.experts"):
        T, k = experts.shape
        held = w_gate.shape[0]
        local = experts - first_expert
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & live[:, None]
        # assignments not served here sort behind every held expert's,
        # past the last group: ragged_dot leaves their rows zero
        flat = jnp.where(here, local, held).reshape(T * k)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)[:held]
        rows = h[order // k]                               # (T*k, D)

        def product(x, w):
            # activations take the weights' type for the grouped product
            # (on the MXU a default-precision float32 product rounds them
            # to bfloat16 anyway); sums are kept in float32. The precision
            # is said outright: under a raised default the TPU's grouped
            # kernel refuses bfloat16 operands ("Bad lhs type")
            return jax.lax.ragged_dot(
                x.astype(w.dtype), w, sizes,
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)

        out = gated_mlp(rows, w_gate, w_up, w_down, product)  # (T*k, D)
        back = jnp.argsort(order)                           # undo the sort
        out = out[back].reshape(T, k, -1)
        y = jnp.where(here[..., None], out * weights[..., None], 0.0).sum(1)
        counts = jnp.stack([(sizes > 0).sum(), sizes.sum(), sizes.max(),
                            jnp.int32(held)])
        return y, counts.astype(jnp.int32)


def shared_ffn(w_gate, w_up, w_down, h):
    """The shared experts: one gated MLP that every token passes."""
    import jax

    with jax.named_scope("moe.shared"):
        return gated_mlp(h, w_gate, w_up, w_down)
