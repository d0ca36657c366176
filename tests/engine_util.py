"""For tests that drive a decode engine by hand."""


def step_now(engine):
    """One decode step and its own tokens: ``step()``, then ``collect()``,
    which is the synchronous order (``serving.DecodeEngine.collect``). The
    engine keeps a step in flight otherwise, and ``step()`` alone answers
    with the tokens of the step before."""
    engine.step()
    return engine.collect()
