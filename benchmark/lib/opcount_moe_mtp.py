"""Operations and bytes that one round of an EXAONE-MoE-shaped model needs
(a two-position verify through the stack, then the MTP block on the
committed rows), from the configuration's published keys and the round's
own counts: the numerators of ``gqa_verify_roofline``,
``moe_share_roofline`` and ``moe_mtp_step_roofline``.

As in ``lib/opcount.py``, what is counted is the least the mathematics asks
of the chip, never what today's program moves: each weight that the round
touches once (the head's slice twice: the MTP head can only run after the
main head's token is known, and the slice does not fit beside the round in
fast memory), each cache line the kernel fetches once, two operations per
weight and row. A share computed from it cannot pass 100%.

Per layer (K-EXAONE-236B-A23B's keys give the numbers in brackets):

* attention's weights: ``W_q`` D x H d [50.33M], ``W_k`` and ``W_v`` D x KV d
  [6.29M each], ``W_o`` H d x D [50.33M]: 113.25M;
* a token's cache lines: keys and values, KV d values each [1024 + 1024];
  per visible token and query head the scores take 2 d operations and the
  weighted sum 2 d;
* the dense block: three matrices D x F [339.74M]; one routed expert and
  the shared expert: three matrices D x F_moe [37.75M]; the router D x E
  [0.79M];
* the head's slice D x V_held [117.96M]; the MTP layer's ``eh_proj``
  2 D x D [75.50M].
"""
from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """Weights per kind of layer, from the published keys; counts of the
    layers held here (``num_hidden_layers``) by kind."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    types = cfg["layer_types"][:layers]
    sparse = sum(t == "sparse" for t in cfg["mlp_layer_types"][:layers])
    mtp = cfg["num_nextn_predict_layers"]
    return {
        "attention": 2 * d * h * hd + 2 * d * kv * hd,
        "line": 2 * kv * hd,          # keys and values of one token
        "heads": h, "head_dim": hd, "hidden": d,
        "dense": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "shared": 3 * d * cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        "router": d * cfg.get("published", {}).get(
            "num_experts", cfg["num_experts"]),
        "head": d * (cfg.get("vocab_held") or (0, cfg["vocab_size"]))[1],
        "eh_proj": 2 * d * d,
        "layers": layers, "dense_layers": layers - sparse,
        "sparse_layers": sparse, "mtp_layers": mtp,
        "window_layers": sum(t == "sliding_attention" for t in types),
        "full_layers": sum(t == "full_attention" for t in types),
        "window": cfg["sliding_window"],
    }


def moe_round(cfg: dict, rows: float, touched: float, assignments: float,
              weight_bytes: int = 2) -> dict:
    """The expert layers of one round over ``rows`` live rows, the stack's
    sparse layers and the MTP block's together (the counters sum them):
    ``touched`` experts reached and ``assignments`` served here. Each
    reached expert's matrices once, every router and shared expert once."""
    s = sizes(cfg)
    n = s["sparse_layers"] + s["mtp_layers"]
    every = n * (s["router"] + s["shared"])
    return {
        "bytes": (touched * s["expert"] + every) * weight_bytes,
        "flops": 2.0 * (assignments * s["expert"] + rows * every),
    }


def gqa_verify(cfg: dict, rows: float, lines_full: float,
               lines_window: float, weight_bytes: int = 2,
               line_bytes: int = 2) -> dict:
    """The stack's attention of one round (the MTP block's is not in it):
    the projections' weights once, the lines the kernel fetches by layer
    kind (``lines_full``, ``lines_window``: pages fetched by the kernel's
    own rule times the page, one layer's), the rows' new lines written."""
    s = sizes(cfg)
    fetched = s["full_layers"] * lines_full + s["window_layers"] * lines_window
    return {
        "bytes": (s["layers"] * s["attention"] * weight_bytes
                  + (fetched + s["layers"] * rows) * s["line"] * line_bytes),
        "flops": (2.0 * rows * s["layers"] * s["attention"]
                  + fetched * s["heads"] * 4.0 * s["head_dim"]),
    }


def round_cost(cfg: dict, rows: float, lines_full: float,
               lines_window: float, touched: float, assignments: float,
               weight_bytes: int = 2, line_bytes: int = 2) -> dict:
    """The whole round: the stack's attention and the MTP block's (a full
    layer more), the dense block, the expert layers, ``eh_proj``, the
    head's slice twice, the embedding rows looked up (the stack's and the
    MTP layer's)."""
    s = sizes(cfg)
    moe = moe_round(cfg, rows, touched, assignments, weight_bytes)
    gqa = gqa_verify(cfg, rows, lines_full, lines_window, weight_bytes,
                     line_bytes)
    mtp = s["mtp_layers"]
    once = (s["dense_layers"] * s["dense"]
            + mtp * (s["attention"] + s["eh_proj"]) + (1 + mtp) * s["head"])
    mtp_lines = mtp * (lines_full + rows) * s["line"]
    return {
        "bytes": (moe["bytes"] + gqa["bytes"] + mtp_lines * line_bytes
                  + (once + (1 + mtp) * rows * s["hidden"]) * weight_bytes),
        "flops": (moe["flops"] + gqa["flops"] + 2.0 * rows * once
                  + mtp * lines_full * s["heads"] * 4.0 * s["head_dim"]),
    }
