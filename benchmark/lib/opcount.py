"""Operations and bytes that an algorithm needs, from its shapes: the
numerators of roofline shares. What is counted is the least the mathematics
asks of the chip, never what today's program happens to move, so a share
computed from it cannot pass 100% and stays comparable when the program
changes.
"""
from __future__ import annotations


def lm_decode_step(hidden: int, layers: int, ffn: int, vocab: int,
                   active: float, context_tokens: float,
                   weight_bytes: int = 2, kv_bytes: int = 2) -> dict:
    """One decode step of a dense decoder-only model with a tied head over
    ``active`` sequences whose visible contexts hold ``context_tokens``
    tokens in all.

    bytes: every weight matrix once (blocks and the tied embedding as the
    head; the ``active`` embedding rows looked up are part of it), the keys
    and values of every visible token once, the new keys and values written.
    flops: two per weight per active sequence, and four per layer, hidden
    unit and visible token for the scores and the weighted sum.
    """
    block = 4 * hidden * hidden + 2 * hidden * ffn   # wqkv + wo, w1 + w2
    weights = layers * block + vocab * hidden
    kv_token = 2 * layers * hidden                    # keys and values
    return {
        "bytes": (weights * weight_bytes
                  + context_tokens * kv_token * kv_bytes
                  + active * kv_token * kv_bytes),
        "flops": (2.0 * active * weights
                  + 4.0 * layers * hidden * context_tokens),
    }


def least_seconds(cost: dict, peaks: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take for
    ``cost``, and which peak sets it (``"hbm"`` or ``"flops"``)."""
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = cost["flops"] / peaks["flops_per_s"]
    return (by_bytes, "hbm") if by_bytes >= by_flops else (by_flops, "flops")
