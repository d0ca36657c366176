"""Closed loop: ``clients`` users, each with one request in flight, who send
their next the moment the last one finished.

    {"kind": "closed_loop_requests",
     "clients": 16,
     "rounds": 8,                  requests a client has to send, at most
     "requests": [[384, 192]...]}  (prompt, output) lengths, cycled: client
                                   c sends entries c, c + clients, ...

Every client's first request is sent before the window opens (``ramp``), and
the window opens once each has its first token: it measures the system with
every client's sequence live, not the filling of an empty one. ``rounds`` is
set so that no client runs out inside the benchmark's run length, with room
for a faster program; one that does run out just stops. Token ids are
uniform from the seed.
"""
from benchmark.lib.traffic import rng


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    clients = int(mix["clients"])
    tokens = rng(seed, 2)
    out = []
    lengths = mix["requests"]
    for i in range(clients * int(mix["rounds"])):
        prompt, steps = lengths[i % len(lengths)]
        out.append({"due_s": None, "ramp": i < clients,
                    "after": i - clients if i >= clients else None,
                    "steps": int(steps),
                    "prompt": tokens.integers(0, vocab, int(prompt),
                                              dtype="int32")})
    return out
