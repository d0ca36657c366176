"""Operations and bytes that a Jamba-shaped model needs (state-space layers
that keep a state a sequence, a few multi-query attention layers that keep
lines a token, a dense gated MLP in every block, a tied head), from the
configuration's published keys and the step's own counts: the numerators of
``ssm_step_roofline``, ``ssm_chunk_scan_roofline`` and
``ssm_mqa_step_roofline``.

As in ``lib/opcount.py``, what is counted is the least the mathematics asks
of the chip, never what today's program moves: each weight that the step
touches once, each live state read once and written once, each visible
cache line once, two operations per weight and row. A share computed from
it cannot pass 100%.

Per layer (AI21-Jamba2-3B's keys give the numbers in brackets):

* a state-space mixer: ``W_in`` D x 2 Di [26.21M], ``W_out`` Di x D
  [13.11M], ``W_x`` Di x (R + 2 N) [0.98M], ``W_dt`` R x Di [0.82M],
  ``A_log`` Di x N [0.08M], the conv's K x Di taps, its bias, ``b_dt`` and
  ``D`` [Di each], the three norms' gains [R + 2 N]: 41,241,792;
* what a sequence keeps of it: the scan state N x Di in float32 [327,680
  B] and the conv's last K - 1 inputs in the cache's type [30,720 B]; a
  token's update of one (state, channel) pair takes six operations (the
  step size times ``A``, the exponential, two products and a sum into the
  state, the product with ``C`` and its sum into ``y``);
* attention's weights: ``W_q`` and ``W_o`` D x H d [6.55M each], ``W_k``
  and ``W_v`` D x KV d [0.33M each]: 13,762,560; a token's cache lines:
  keys and values, KV d values each [128 + 128]; per visible token and
  query head the scores take 2 d operations and the weighted sum 2 d;
* the MLP: three matrices D x F [62,914,560]; two norms' gains [2 D];
* the embedding V x D [167,772,160], which is the head too (tied: read
  once a step as the head; the rows a step looks up are 128 of 65536).
"""
from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """Weights per layer kind and what a sequence keeps, from the published
    keys."""
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = d // h
    di, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    r, k = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    layers = cfg["num_hidden_layers"]
    attention = sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
                    for i in range(layers))
    return {
        "mixer": (d * 2 * di + di * d + di * (r + 2 * n) + r * di + n * di
                  + k * di + 3 * di + r + 2 * n),
        "attention": 2 * d * h * hd + 2 * d * kv * hd,
        "mlp": 3 * d * cfg["intermediate_size"],
        "norms": 2 * d,
        "embed": d * cfg["vocab_size"],
        "hidden": d, "inner": di, "state": n,
        "heads": h, "head_dim": hd,
        "line": 2 * kv * hd,              # keys and values of one token
        "scan_state_bytes": n * di * 4,   # float32
        "conv_state_values": (k - 1) * di,
        "layers": layers, "attention_layers": attention,
        "state_layers": layers - attention,
    }


def parameters(cfg: dict) -> int:
    """Every parameter, the tied embedding once."""
    s = sizes(cfg)
    return (s["state_layers"] * s["mixer"]
            + s["attention_layers"] * s["attention"]
            + s["layers"] * (s["mlp"] + s["norms"])
            + s["embed"] + s["hidden"])


def state_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """What one sequence keeps in one state layer."""
    s = sizes(cfg)
    return s["scan_state_bytes"] + s["conv_state_values"] * cache_bytes


def _scan_flops(s: dict, rows: float) -> float:
    return 6.0 * rows * s["inner"] * s["state"]


def ssm_step(cfg: dict, live: float, weight_bytes: int = 2) -> dict:
    """The state-space layers of one decode step over ``live`` sequences:
    every mixer's weights once, every live sequence's state read once and
    written once."""
    s = sizes(cfg)
    layers = s["state_layers"]
    return {
        "bytes": (layers * s["mixer"] * weight_bytes
                  + live * layers * 2 * state_bytes(cfg)),
        "flops": (2.0 * live * layers * s["mixer"]
                  + layers * _scan_flops(s, live)),
    }


def chunk_scan(cfg: dict, rows: float) -> dict:
    """The recurrence of ONE state layer over ``rows`` real rows of one
    sequence (a prefill launch's scan, nothing else of the layer): reads
    ``u`` and the step sizes (rows x Di, float32), ``B`` and ``C`` (rows x
    N), ``A`` and the state; writes ``y`` and the state. The gate ``z`` is
    the output projection's operand and is not counted here."""
    s = sizes(cfg)
    return {
        "bytes": 4.0 * (3 * rows * s["inner"] + 2 * rows * s["state"]
                        + 3 * s["inner"] * s["state"]),
        "flops": _scan_flops(s, rows),
    }


def step(cfg: dict, live: float, visible: float, weight_bytes: int = 2,
         line_bytes: int = 2) -> dict:
    """One whole decode step over ``live`` sequences that see ``visible``
    tokens together: every weight once with the tied embedding as the head,
    the live states read and written, the visible lines read and the new
    ones written in the attention layers."""
    s = sizes(cfg)
    return {
        "bytes": (parameters(cfg) * weight_bytes
                  + live * s["state_layers"] * 2 * state_bytes(cfg)
                  + (visible + live) * s["attention_layers"] * s["line"]
                  * line_bytes),
        "flops": (2.0 * live * parameters(cfg)
                  + visible * s["attention_layers"] * s["heads"] * 4.0
                  * s["head_dim"]
                  + s["state_layers"] * _scan_flops(s, live)),
    }
