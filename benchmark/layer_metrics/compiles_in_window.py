"""XLA compile-or-load events jax reported between the window's start and
its end (``lib/compile_clock.CompileClock``). Expected 0: every shape is
warmed during set-up."""


def read(facts):
    return facts.get("compiles_in_window")
