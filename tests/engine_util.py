"""For tests that drive a decode engine by hand."""
import numpy as np


def step_now(engine):
    """One decode step and its own tokens: ``step()``, then ``collect()``,
    which is the synchronous order (``serving.DecodeEngine.collect``). The
    engine keeps a step in flight otherwise, and ``step()`` alone answers
    with the tokens of the step before."""
    engine.step()
    return engine.collect()


def spy_head(engine):
    """The scores of every call of the family's head from here on, one
    ``(rows, vocab)`` array a call in the order the device made them. A
    prompt's first token is made on the device and its scores stay there
    (since PR 49): a test that compares scores takes them from here. The
    head is wrapped on the engine's family object, which the programs read
    when they are traced: call this before the engine's first launch and
    step. ``_seed``, behind a prompt's last launch, calls the head on the
    launch's one last real row; a launch calls none (a drafting family's
    does, on that row too); a step scores a row a slot."""
    import jax

    seen, real = [], engine.family.head

    def head(p, x):
        scores = real(p, x)
        jax.debug.callback(lambda s: seen.append(np.asarray(s)), scores)
        return scores

    engine.family.head = head
    return seen


def spy_launches(engine):
    """Every launch's ``(start, n_valid, scores)`` from here on, through
    ``prefill_tick`` or by hand (``launch``): ``scores (vocab,)`` are what
    the head made of the launch's last real row, the row the prompt's
    first token is the best of, and ``None`` for a launch no ``_seed``
    followed (not its prompt's last: nothing runs a head for it). Call it
    before the engine's first launch and step (``spy_head``)."""
    import jax

    heads, seen = spy_head(engine), []
    launch_, seed = engine._prefill_chunk, engine._seed

    def launched(*args):
        seen.append((int(args[1]), int(args[2]), None))
        return launch_(*args)

    def seeded(*args):
        carry, ends = seed(*args)
        token = int(np.asarray(ends)[0])
        jax.effects_barrier()
        # the device runs what it is given in order: this call of the head
        # is the newest (a drafting family's launch made the token itself,
        # of the head's scores as well)
        seen[-1] = (*seen[-1][:2], heads[-1][0])
        assert int(heads[-1][0].argmax()) == token
        return carry, ends

    engine._prefill_chunk, engine._seed = launched, seeded
    return seen


def launch(engine, padded, start, n_valid, slot=0, last=True):
    """One launch by hand, as ``prefill_tick`` calls it, over the pages
    ``slot`` holds, the pools and states kept; where ``last``, ``_seed``
    behind it as behind a prompt's last launch. Returns what went into the
    decode carry, ``[token]`` (``[token, draft]`` where the family drafts),
    or ``None`` where ``last`` is false."""
    import jax.numpy as jnp

    more = ()
    if engine._states:
        more = (jnp.int32(slot), *engine._states)
    elif engine.drafts:
        if not last:
            raise ValueError("a drafting launch that is not the last takes "
                             "the prompt's next token: call the program")
        more = (jnp.int32(-1),)
    ends, *rest = engine._prefill_chunk(
        jnp.asarray(padded, jnp.int32), jnp.int32(start), jnp.int32(n_valid),
        *engine._tables(slot), *engine._pools, *more)
    if engine.family.counters:
        rest.pop(0)
    engine._keep(rest)
    if not last:
        return None
    at = [start + n_valid] if engine.drafts else n_valid - 1
    engine._tok_dev, ends = engine._seed(
        engine._tok_dev, np.asarray(slot, np.int32), ends,
        np.asarray(at, np.int32))
    return np.asarray(ends)
