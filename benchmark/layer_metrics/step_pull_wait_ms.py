"""What the return of a decode step's tokens costs the host: mean of
``engine.step.pull`` (``device_get`` of the tokens) per step, over the
passes inside the traced part of the window (``lib/program_spans.py`` has
the rule). Beside ``decode_step_dev_ms`` it says whether the call returns
before the device is done: a pull about as long as the device's step is a
wait for the device, the rest is the transfer and the interpreter."""
from benchmark.lib.program_spans import mean_ms_per_call


def read(facts):
    return mean_ms_per_call(facts, "step", "pull")
