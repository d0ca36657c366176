"""Operations and bytes that one decode step of a DeepSeek-V3-shaped model
needs (latent attention, a dropless expert layer with shared experts), from
the configuration's published keys and the step's own counts: the
numerators of ``moe_roofline``, ``mla_decode_roofline`` and
``moe_mla_step_roofline``.

As in ``lib/opcount.py``, what is counted is the least the mathematics asks
of the chip, never what today's program moves: each weight that the step
touches once, each visible cache line once, two operations per weight and
row. A share computed from it cannot pass 100%.

Per layer (Kanana-2-30B-A3B's keys give the numbers in brackets):

* attention's weights: ``W_q`` D x H(nope+rope) [12.58M], ``W_kva``
  D x (latent+rope) [1.18M], ``W_kvb`` H(nope+v) x latent [4.19M], ``W_o``
  H v x D [8.39M]: 26.35M;
* a token's cache line: latent + rope values [576], read by every head as
  keys and as values, so once; per visible token and head the scores take
  2(latent+rope) operations and the weighted sum 2 latent;
* one routed expert: three matrices D x F_moe [4.72M]; the shared experts
  one gated MLP of width n_shared F_moe [9.44M]; the router D x E [0.26M];
* the dense layer's MLP: 3 D F [37.75M]; the head D x V [262.7M].
"""
from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """Weights per layer kind, from the published keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    latent, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {
        "attention": (d * h * (nope + rope) + d * (latent + rope)
                      + h * (nope + v) * latent + h * v * d),
        "line": latent + rope,
        "latent": latent,
        "heads": h,
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "shared": 3 * d * cfg["n_shared_experts"]
                  * cfg["moe_intermediate_size"],
        "router": d * cfg["n_routed_experts"],
        "dense_mlp": 3 * d * cfg["intermediate_size"],
        "head": d * cfg["vocab_size"],
        "hidden": d,
        "layers": layers,
        "dense_layers": dense,
        "moe_layers": layers - dense,
    }


def moe_decode(cfg: dict, active: float, touched: float, assignments: float,
               weight_bytes: int = 2) -> dict:
    """The expert layers of one decode step over ``active`` sequences:
    ``touched`` experts reached and ``assignments`` served, both summed over
    the expert layers. Each reached expert's matrices once, the router and
    the shared experts of every expert layer once."""
    s = sizes(cfg)
    every = s["moe_layers"] * (s["router"] + s["shared"])
    return {
        "bytes": (touched * s["expert"] + every) * weight_bytes,
        "flops": 2.0 * (assignments * s["expert"] + active * every),
    }


def mla_decode(cfg: dict, active: float, context_tokens: float,
               weight_bytes: int = 2, line_bytes: int = 2) -> dict:
    """Latent attention of one decode step, all layers: the projections'
    weights once, the visible lines of the live sequences once
    (``context_tokens`` in all), the new lines written."""
    s = sizes(cfg)
    per_token_head = 2.0 * (s["line"] + s["latent"])  # scores, weighted sum
    return {
        "bytes": s["layers"] * (
            s["attention"] * weight_bytes
            + (context_tokens + active) * s["line"] * line_bytes),
        "flops": s["layers"] * (
            2.0 * active * s["attention"]
            + context_tokens * s["heads"] * per_token_head),
    }


def step(cfg: dict, active: float, context_tokens: float, touched: float,
         assignments: float, weight_bytes: int = 2,
         line_bytes: int = 2) -> dict:
    """The whole decode step: attention and expert layers as above, the
    leading dense layers' MLP, the head, the embedding rows looked up."""
    s = sizes(cfg)
    moe = moe_decode(cfg, active, touched, assignments, weight_bytes)
    mla = mla_decode(cfg, active, context_tokens, weight_bytes, line_bytes)
    rest = s["dense_layers"] * s["dense_mlp"] + s["head"]
    return {
        "bytes": (moe["bytes"] + mla["bytes"]
                  + (rest + active * s["hidden"]) * weight_bytes),
        "flops": moe["flops"] + mla["flops"] + 2.0 * active * rest,
    }
