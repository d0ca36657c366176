"""Operations and bytes that one decode step of a Mellum-shaped model needs
(grouped-query attention, window layers beside full ones, a dropless top-k
expert layer in every block), from the configuration's published keys and
the step's own counts: the numerators of ``moe_topk_roofline``,
``gqa_window_decode_roofline`` and ``moe_gqa_step_roofline``.

As in ``lib/opcount.py``, what is counted is the least the mathematics asks
of the chip, never what today's program moves: each weight that the step
touches once, each visible cache line once, two operations per weight and
row. A share computed from it cannot pass 100%.

Per layer (Mellum2-12B-A2.5B's keys give the numbers in brackets):

* attention's weights: ``W_q`` D x H d [9.44M], ``W_k`` and ``W_v`` D x KV d
  [1.18M each], ``W_o`` H d x D [9.44M]: 21.23M;
* a token's cache lines: keys and values, KV d values each [512 + 512]; a
  full layer's query sees the whole context, a window layer's
  ``min(context, sliding_window)`` of it; per visible token and query head
  the scores take 2 d operations and the weighted sum 2 d;
* one routed expert: three matrices D x F_moe [6.19M]; the router D x E
  [0.147M]; no shared expert;
* the head D x V [226.5M].
"""
from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """Weights per layer kind, from the published keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    types = cfg["layer_types"][:layers]
    return {
        "attention": 2 * d * h * hd + 2 * d * kv * hd,
        "line": 2 * kv * hd,          # keys and values of one token
        "heads": h, "head_dim": hd,
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "router": d * cfg["num_experts"],
        "head": d * cfg["vocab_size"],
        "hidden": d,
        "layers": layers,
        "window_layers": sum(t == "sliding_attention" for t in types),
        "full_layers": sum(t == "full_attention" for t in types),
        "window": cfg["sliding_window"],
    }


def layer_weights(cfg: dict) -> int:
    """Parameters of one block with every expert."""
    s = sizes(cfg)
    return s["attention"] + s["router"] + cfg["num_experts"] * s["expert"]


def moe_decode(cfg: dict, active: float, touched: float, assignments: float,
               weight_bytes: int = 2) -> dict:
    """The expert layers of one decode step over ``active`` sequences:
    ``touched`` experts reached and ``assignments`` served, both summed over
    the layers. Each reached expert's matrices once, every router once."""
    s = sizes(cfg)
    routers = s["layers"] * s["router"]
    return {
        "bytes": (touched * s["expert"] + routers) * weight_bytes,
        "flops": 2.0 * (assignments * s["expert"] + active * routers),
    }


def gqa_decode(cfg: dict, active: float, ctx_full: float, ctx_window: float,
               weight_bytes: int = 2, line_bytes: int = 2) -> dict:
    """Attention of one decode step, all layers: the projections' weights
    once, the visible lines of the live sequences once by layer kind
    (``ctx_full``: their contexts summed; ``ctx_window``: each context cut
    to the window, summed), the new lines written."""
    s = sizes(cfg)
    visible = s["full_layers"] * ctx_full + s["window_layers"] * ctx_window
    return {
        "bytes": (s["layers"] * s["attention"] * weight_bytes
                  + (visible + s["layers"] * active) * s["line"]
                  * line_bytes),
        "flops": (2.0 * active * s["layers"] * s["attention"]
                  + visible * s["heads"] * 4.0 * s["head_dim"]),
    }


def step(cfg: dict, active: float, ctx_full: float, ctx_window: float,
         touched: float, assignments: float, weight_bytes: int = 2,
         line_bytes: int = 2) -> dict:
    """The whole decode step: attention and expert layers as above, the
    head, the embedding rows looked up."""
    s = sizes(cfg)
    moe = moe_decode(cfg, active, touched, assignments, weight_bytes)
    gqa = gqa_decode(cfg, active, ctx_full, ctx_window, weight_bytes,
                     line_bytes)
    return {
        "bytes": (moe["bytes"] + gqa["bytes"]
                  + (s["head"] + active * s["hidden"]) * weight_bytes),
        "flops": moe["flops"] + gqa["flops"] + 2.0 * active * s["head"],
    }
