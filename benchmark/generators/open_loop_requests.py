"""Open loop at a fixed rate: request ``i`` of the file's list is due at
``i / rate_per_s``, whatever the system does with the ones before it.

    {"kind": "open_loop_requests",
     "rate_per_s": 0.42,         arrivals per second (found by a sweep)
     "requests": [[128, 48]...], (prompt, output) lengths, in arrival order
     "drain_s": 11.5}            no arrival later than seconds - drain_s

The list is used once, as far as the window reaches; a file is written so
that at the benchmark's run length all of it is due. Token ids are uniform
from the seed, so no two prompts share a prefix.
"""
from benchmark.lib.traffic import rng


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    rate = float(mix["rate_per_s"])
    last_due = seconds - float(mix["drain_s"])
    tokens = rng(seed, 2)
    out = []
    for i, (prompt, steps) in enumerate(mix["requests"]):
        due = i / rate
        if due > last_due:
            break
        out.append({"due_s": due, "after": None, "ramp": False,
                    "steps": int(steps),
                    "prompt": tokens.integers(0, vocab, int(prompt),
                                              dtype="int32")})
    return out
