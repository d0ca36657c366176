"""Operations and bytes that an Ouro-shaped model needs (one stack of
layers run ``total_ut_steps`` times a token with the same weights, a cache
line for every pass of every layer, an exit gate, an untied head), from the
configuration's published keys and the step's own counts: the numerators of
``loop_layers_roofline``, ``loop_step_roofline`` and
``loop_lines_bytes_share``.

As in ``lib/opcount.py``, what is counted is the least the mathematics asks
of the chip, never what today's program moves: two operations per weight
and row, each visible cache line once, each new line written once. One
thing is particular to the loop: a layer's weights count once A PASS, not
once a step. The stack (Ouro-2.6B: 4.93 GB in bfloat16) cannot stay on the
chip between two passes of it (a v5e has 128 MiB of VMEM), so no program of
this model can read it from HBM fewer than ``passes`` times a step; with
that a share computed from the count cannot pass 100%. The head and the
embedding rows are read once a step.

Per pass-layer (Ouro-2.6B's keys give the numbers in brackets; 192
pass-layers a token):

* attention's weights: ``W_q`` and ``W_o`` D x H d, ``W_k`` and ``W_v`` D x
  KV d [16,777,216 in all]; a token's cache lines: rotated keys and values,
  KV d values each [2 x 2048 x 2 B = 8,192 B]; per visible token and query
  head the scores take 2 d operations and the weighted sum 2 d [4 x 2048 a
  visible token];
* the MLP: three matrices D x F [34,603,008];
* four norms' gains [4 D].

Once a pass: the final norm's gains and the gate [2 D + 1]. Once a step:
the head D x V [100,663,296] and the live rows of the embedding.
"""
from __future__ import annotations


def sizes(cfg: dict) -> dict:
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return {"attention": 2 * d * h * hd + 2 * d * kv * hd,
            "mlp": 3 * d * cfg["intermediate_size"],
            "norms": 4 * d, "close": 2 * d + 1,
            "head": d * cfg["vocab_size"], "hidden": d,
            "line": 2 * kv * hd,          # keys and values of one token
            "scores": 4 * h * hd,         # operations a visible token
            "pass_layers": cfg["total_ut_steps"] * cfg["num_hidden_layers"],
            "passes": cfg["total_ut_steps"]}


def attention_step(cfg: dict, active: float, context_tokens: float,
                   weight_bytes: int = 2, line_bytes: int = 2) -> dict:
    """The attention of one decode step, every pass-layer's: its weights
    once a pass, the lines of every visible token read, the live tokens'
    written."""
    sz = sizes(cfg)
    n = sz["pass_layers"]
    return {"bytes": (n * sz["attention"] * weight_bytes
                      + (context_tokens + active) * n * sz["line"]
                      * line_bytes),
            "flops": (2.0 * active * n * sz["attention"]
                      + context_tokens * n * sz["scores"])}


def mlp_step(cfg: dict, active: float, weight_bytes: int = 2) -> dict:
    """The gated MLPs of one decode step, every pass-layer's."""
    sz = sizes(cfg)
    n = sz["pass_layers"]
    return {"bytes": n * sz["mlp"] * weight_bytes,
            "flops": 2.0 * active * n * sz["mlp"]}


def layers_step(cfg: dict, active: float, context_tokens: float,
                weight_bytes: int = 2, line_bytes: int = 2) -> dict:
    """The layers of one decode step, attention and MLP of every
    pass-layer together (no norm's gains, no head)."""
    attn = attention_step(cfg, active, context_tokens, weight_bytes,
                          line_bytes)
    mlp = mlp_step(cfg, active, weight_bytes)
    return {"bytes": attn["bytes"] + mlp["bytes"],
            "flops": attn["flops"] + mlp["flops"]}


def lines_bytes(cfg: dict, active: float, context_tokens: float,
                line_bytes: int = 2) -> float:
    """The cache lines' part of a step's bytes: read for every visible
    token, written for every live one."""
    sz = sizes(cfg)
    return ((context_tokens + active) * sz["pass_layers"] * sz["line"]
            * line_bytes)


def step(cfg: dict, active: float, context_tokens: float,
         weight_bytes: int = 2, line_bytes: int = 2) -> dict:
    """One whole decode step over ``active`` sequences whose visible
    contexts hold ``context_tokens`` tokens in all."""
    sz = sizes(cfg)
    attn = attention_step(cfg, active, context_tokens, weight_bytes,
                          line_bytes)
    mlp = mlp_step(cfg, active, weight_bytes)
    small = sz["pass_layers"] * sz["norms"] + sz["passes"] * sz["close"]
    return {"bytes": (attn["bytes"] + mlp["bytes"]
                      + (sz["head"] + active * sz["hidden"] + small)
                      * weight_bytes),
            "flops": (attn["flops"] + mlp["flops"]
                      + 2.0 * active * sz["head"])}
