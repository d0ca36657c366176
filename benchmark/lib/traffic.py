"""Traffic: a mix is a data file under ``benchmark/traffic/``, and its
``kind`` names the generator under ``benchmark/generators/`` that turns it, a
seed and the window's length into the requests of a run. Nothing here knows
a mix or a kind by name: a new kind of traffic is a new generator file.

The seed never changes the population: lengths, order, arrival times and
who waits for whom are written in the file; the seed draws the token ids.

A generator has one function, ``requests(mix, seed, seconds, vocab)``, and
returns the run's requests in the order in which they are sent, each a dict:

    prompt   int32 array of token ids
    steps    output tokens asked for
    due_s    seconds after the window opens at which it is due, or None
    after    index of the request that has to finish first, or None
    ramp     True: sent before the window opens, which it does once every
             such request has its first token (set-up, not measured)

The driver sends a request once the window (or the ramp) has reached its
``due_s`` and its ``after`` request has finished: open loop is ``due_s``
alone, closed loop is ``after`` alone, and a mix may use both.
"""
from __future__ import annotations

import importlib
import json
import os

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")


def load(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as fh:
        mix = json.load(fh)
    generator_for(mix)  # an unknown kind is refused before anything is built
    return mix


def generator_for(mix: dict):
    kind = str(mix.get("kind"))
    if not os.path.exists(os.path.join(BENCH_DIR, "generators", f"{kind}.py")):
        raise ValueError(f"traffic kind {kind!r} has no generator under "
                         "benchmark/generators/")
    return importlib.import_module(f"benchmark.generators.{kind}")


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    return generator_for(mix).requests(mix, seed, seconds, vocab)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])
