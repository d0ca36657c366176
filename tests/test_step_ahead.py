"""The decode loop runs one step ahead (ISSUE 35).

``PagedLMEngine.step()`` dispatches step k+1 before it brings step k's
tokens home. Every request must still receive exactly the tokens of the
synchronous order (the same engine collected after every step), whatever
joins, ends, is preempted or runs out of pages meanwhile: proven here for the
four model families at tiny sizes on the CPU, where greedy tokens are exact.
What the run-ahead gains is the chip's to say (PERF.md).
"""
import time

import numpy as np
import pytest
from engine_util import step_now
from test_kv_paged import leakcheck  # noqa: F401 - the ledger, armed a test

from nnstreamer_tpu.serving import DecodeEngine, DecodeScheduler, PagedLMEngine
from nnstreamer_tpu.serving.kv_pool import PagePoolExhausted

# (prompt length, steps, where an EOS ending falls or None): five requests
# over three slots, so slots churn; 16 pages of 4 hold any one request (9 at
# most) and not three at once (23), so a step runs out of pages mid-decode
REQUESTS = ((9, 22, None), (5, 18, 6), (14, 20, None), (7, 16, 4),
            (11, 12, 6))
SLOTS, PAGE, PAGES = 3, 4, 16


def _gpt():
    from nnstreamer_tpu.models.lm_serving import tiny
    from nnstreamer_tpu.models.transformer import init_params

    return PagedLMEngine(tiny.cfg, init_params(tiny.cfg, seed=0),
                         slots=SLOTS, page_size=PAGE, chunk=8, pages=PAGES,
                         share_prefixes=False)


def _latent():
    from test_deepseek_v3_serving import _model

    cfg, _, _, params = _model()
    return PagedLMEngine(cfg, params, slots=SLOTS, page_size=PAGE, chunk=8,
                         pages=PAGES, share_prefixes=False)


def _window():
    from test_mellum_serving import HELD, _engine

    return _engine(slots=SLOTS, page_size=PAGE,
                   pages={"full": PAGES, "window": SLOTS * HELD})[3]


def _state():
    from test_jamba_serving import _engine

    return _engine(slots=SLOTS, page_size=PAGE, pages=PAGES)[3]


FAMILIES = {"gpt": _gpt, "deepseek_v3": _latent, "mellum": _window,
            "jamba": _state}


class Synchronous:
    """The engine collected after every step: what the loop was before it
    ran ahead. Forwards every other name, as a measuring proxy does."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self):
        return step_now(self._engine)


def _prompts(vocab):
    rng = np.random.default_rng(35)
    return [rng.integers(1, vocab - 4, n).astype(np.int32)
            for n, _, _ in REQUESTS]


def _alone(engine, prompt, steps):
    out = [engine.admit(0, prompt, steps)]
    while len(out) < steps:
        out.append(int(step_now(engine)[0]))
    engine.release(0)
    return out


def _serve(engine, prompts, eos, name):
    """The seeded schedule through a scheduler: the first three requests
    join one by one while the earlier ones decode, the last two as slots
    free. Returns every request's tokens, the snapshot, the count of steps
    that ran out of pages."""
    starved, real_step = [], engine.step

    def step():
        try:
            return real_step()
        except PagePoolExhausted:
            starved.append(1)
            raise

    engine.step = step
    sched = DecodeScheduler(engine, name=name)
    try:
        reqs = []
        for prompt, (_, steps, _), e in zip(prompts, REQUESTS, eos):
            if 0 < len(reqs) < SLOTS:  # staggered: the last one decodes
                deadline = time.monotonic() + 120
                while (len(reqs[-1].tokens) < 2
                       and time.monotonic() < deadline):
                    time.sleep(0.001)
            reqs.append(sched.submit(prompt, steps=steps, eos_id=e))
        outs = [r.result(timeout=300)[0].tolist() for r in reqs]
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
        del engine.step
    return outs, snap, len(starved)


@pytest.mark.parametrize("family", FAMILIES)
def test_every_request_gets_the_synchronous_orders_tokens(family, leakcheck):  # noqa: F811
    leakcheck.reset_leakcheck()
    ahead, plain = FAMILIES[family](), FAMILIES[family]()
    prompts = _prompts(plain.family.vocab)
    # each request alone, a step at a time: its whole stream, and from it an
    # EOS id that ends it early about where REQUESTS says
    whole = [_alone(plain, p, s) for p, (_, s, _) in zip(prompts, REQUESTS)]
    eos, want = [], []
    for stream, (_, steps, at) in zip(whole, REQUESTS):
        if at is None:
            eos.append(None)
            want.append(stream)
        else:
            # a token the stream has not held before, nearest to there and
            # neither the first nor the last: an ending the budget does not
            # foresee (a tiny model may repeat one token: then none)
            new = [i for i in range(1, steps - 1)
                   if stream.index(stream[i]) == i]
            at = min(new, key=lambda i: abs(i - at), default=steps - 1)
            eos.append(stream[at] if new else None)
            want.append(stream[:at + 1])
    n_eos = sum(e is not None for e in eos)
    assert n_eos >= 1

    sync, sync_snap, _ = _serve(Synchronous(plain), prompts, eos,
                                f"sync-{family}")
    got, snap, starved = _serve(ahead, prompts, eos, f"ahead-{family}")
    assert sync == want, "the synchronous order is each request alone"
    assert got == sync, "one step ahead, every request's tokens are the same"

    # the schedule held what it was written for
    assert snap["completed"] == len(REQUESTS) and snap["failed"] == 0
    assert starved >= 1, "a step ran out of pages and was tried again"
    assert snap["preempted"] >= 1 and snap["restored"] == snap["preempted"]
    assert snap["retired_early"] == n_eos
    if family == "mellum":
        assert snap["window_pages_released"] > 0
    # and the run-ahead engaged, as the counters say
    assert snap["steps_ahead"] > snap["decode_steps"] // 2
    assert snap["steps_collected_early"] >= 1
    assert snap["surplus_steps"] == n_eos, "one step over an EOS, no other"
    assert sync_snap["steps_ahead"] == sync_snap["surplus_steps"] == 0
    for engine in (ahead, plain):
        assert engine.compile_count == 2, "one step and one chunk program"
        assert engine.active_slots == 0
        assert all(p.used_pages == 0 for p in engine.pools_by_kind.values())
    assert leakcheck.outstanding("kv_page") == []


# -- by hand: what an answer holds, and what a release may leave in flight --------

def test_a_slot_that_was_not_in_the_step_has_no_token():
    eng = _gpt()
    a, b = _prompts(64)[:2]
    alone = [_alone(eng, p, 8) for p in (a, b)]
    first = eng.admit(0, a, 8)
    out = {0: [first], 1: []}
    assert eng.step().tolist() == [-1] * SLOTS, "nothing was in flight"
    tok = eng.step()                       # the first step's tokens
    assert tok[0] >= 0 and tok[1] == tok[2] == -1
    out[0].append(int(tok[0]))
    out[1].append(eng.admit(1, b, 8))      # joins with a step in flight
    tok = eng.step()
    assert tok[0] >= 0 and tok[1] == -1, "it was not in that step"
    out[0].append(int(tok[0]))
    while len(out[1]) < 8:
        tok = eng.step()
        for s in (0, 1):
            if tok[s] >= 0:
                out[s].append(int(tok[s]))
    assert [out[0], out[1]] == alone
    # slot 0 took no step past its budget, and nothing is left in flight
    assert eng.collect().tolist() == [-1] * SLOTS
    assert int(eng._pos[0]) == a.size + 7 and int(eng._left[0]) == 0
    assert eng.run_ahead["surplus_steps"] == 0
    eng.close()
    assert eng.pool.used_pages == 0


@pytest.mark.parametrize("family", ["gpt", "jamba"])
def test_a_release_under_a_step_in_flight_is_safe(family):
    """The step in flight still writes the released slot's line (and
    advances its state); the pages go back at once and the slot's next
    prompt takes them. The device's order keeps both sequences exact."""
    eng = FAMILIES[family]()
    a, b, c = _prompts(eng.family.vocab)[:3]
    alone = [_alone(eng, p, 10) for p in (b, c)]
    eng.admit(0, a, 30)
    out = [eng.admit(1, b, 10)]
    for _ in range(3):
        tok = eng.step()
        if tok[1] >= 0:
            out.append(int(tok[1]))
    assert eng._flight is not None and eng._flight[1][0]
    eng.release(0)                          # as the scheduler does on EOS
    assert eng.run_ahead["surplus_steps"] == 1
    new = [eng.admit(0, c, 10)]             # the freed pages, the same slot
    while len(out) < 10 or len(new) < 10:
        tok = eng.step()
        for s, stream in ((1, out), (0, new)):
            if tok[s] >= 0:
                stream.append(int(tok[s]))
    assert [out, new] == alone
    assert eng.run_ahead["surplus_steps"] == 1
    assert eng.compile_count == 2
    eng.close()
    assert eng.pool.used_pages == 0


def test_the_contracts_default_keeps_nothing_in_flight():
    assert DecodeEngine().collect() is None
