"""Seconds from the first ramp request's submission to the window's
opening: the prefill of every client's first prompt (a launch at a time,
beside the decode steps of those already in), which is set-up. The only
place a cell whose window holds no launch prices the prefill of its
configuration. From the benchmark's own clock."""


def read(facts):
    return facts.get("ramp_s")
