"""Seconds jax reports for XLA compilation, or for the load from the
persistent cache that takes its place, from process start to the window's
start (``lib/compile_clock.CompileClock``)."""


def read(facts):
    return facts.get("setup_compile_s")
