"""Operations and bytes that one decode step of a ZAYA-shaped model needs
(attention in a compressed latent whose projection keeps a state a slot, a
top-1 expert layer behind an MLP router in every block, a tied head), from
the configuration's published keys and the step's own counts: the numerators
of ``cca_decode_roofline``, ``moe_top1_roofline`` and
``moe_cca_step_roofline``.

As in ``lib/opcount.py``, what is counted is the least the mathematics asks
of the chip, never what today's program moves: each weight that the step
touches once, each visible cache line once, a slot's state read and written
once, two operations per weight and row. A share computed from it cannot
pass 100%.

Per layer (ZAYA1-8B's keys give the numbers in brackets):

* the CCA part's weights: ``W_q`` D x H d [2.10M], ``W_k`` D x G d [0.52M],
  ``W_v1`` and ``W_v2`` D x d [0.26M each], ``W_o`` H d x D [2.10M]; the
  depthwise taps 2 x (H + G) d [2,560] and the head-wise ones 2 x (H + G) x
  d x d [327,680], their biases [2,560]: 5.58M;
* a token's cache lines: finished keys and values, G d values each [256 +
  256]; per visible token and query head the scores take 2 d operations and
  the weighted sum 2 d;
* a slot's state: the last packed row, the last row between the
  convolutions and the last shifted value, 2 (H + G) d + d float32 [2,688];
* the router: D x R, two R x R, R x E and its vectors [0.66M]; one expert:
  three matrices D x F [12.58M]; the norms' gains, the temperatures and the
  eight merge vectors [20,482];
* the head D x V [537.1M], which is the embedding: the rows looked up are
  part of it.
"""
from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """Weights a layer by part, from the published keys."""
    d, h, g = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd, r, e = cfg["head_dim"], cfg["router_hidden_size"], cfg["num_experts"]
    packed = (h + g) * hd
    return {
        "cca": (2 * d * h * hd + d * g * hd + 2 * d * hd
                + 4 * packed + 2 * (h + g) * hd * hd),
        "line": 2 * g * hd,            # keys and values of one token
        "state": 2 * packed + hd,      # float32 values a slot keeps
        "heads": h, "head_dim": hd, "hidden": d,
        "router": d * r + 2 * r * r + r * e + 5 * r + e,
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "vectors": 10 * d + g,         # two gains, eight merge vectors, tau
        "head": d * cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
    }


def layer_weights(cfg: dict) -> int:
    """Parameters of one block from layer 1 on, with every expert."""
    s = sizes(cfg)
    return (s["cca"] + s["router"] + s["vectors"]
            + cfg["num_experts"] * s["expert"])


def cca_decode(cfg: dict, active: float, ctx: float,
               line_bytes: int = 2) -> dict:
    """What the step's line-attention kernel needs, all layers: the visible
    lines of the live sequences once (``ctx``: their contexts summed), the
    queries in and the results out in float32."""
    s = sizes(cfg)
    rows = active * s["heads"] * s["head_dim"]
    return {
        "bytes": s["layers"] * (ctx * s["line"] * line_bytes + 8.0 * rows),
        "flops": s["layers"] * ctx * s["heads"] * 4.0 * s["head_dim"],
    }


def moe_top1(cfg: dict, active: float, touched: float, assignments: float,
             weight_bytes: int = 2) -> dict:
    """What the experts' kernel needs, all layers: each reached expert's
    three matrices once (``touched``: experts reached, summed over the
    layers), the rows in and out in float32, ``assignments`` rows through
    an expert each."""
    s = sizes(cfg)
    return {
        "bytes": (touched * s["expert"] * weight_bytes
                  + s["layers"] * 8.0 * active * s["hidden"]),
        "flops": 2.0 * assignments * s["expert"],
    }


def step(cfg: dict, active: float, ctx: float, touched: float,
         assignments: float, slots: float, weight_bytes: int = 2,
         line_bytes: int = 2) -> dict:
    """The whole decode step: every layer's CCA weights, router and vectors
    once, the reached experts, the visible lines and the new ones, every
    slot's state read and written, the head (the embedding rows looked up
    are rows of it)."""
    s = sizes(cfg)
    moe = moe_top1(cfg, active, touched, assignments, weight_bytes)
    lines = cca_decode(cfg, active, ctx, line_bytes)
    once = s["layers"] * (s["cca"] + s["router"] + s["vectors"]) + s["head"]
    return {
        "bytes": (moe["bytes"] + lines["bytes"] + once * weight_bytes
                  + s["layers"] * (active * s["line"] * line_bytes
                                   + 8.0 * slots * s["state"])),
        "flops": (moe["flops"] + lines["flops"] + 2.0 * active * (
            s["layers"] * (s["cca"] + s["router"]) + s["head"])),
    }
