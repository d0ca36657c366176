"""The pass in which a prompt joins runs ahead like every other (ISSUE 49).

A prompt's last launch makes its first token on the device, ``_seed`` puts
it into the decode carry there, and, where a step is in flight, the pass's
step is dispatched behind them before the host pulls the token
(``PagedLMEngine.prefill_tick`` / ``_ride``). Every request must still
receive the tokens of the synchronous form: the same engine collected
after every step, so that a prompt's last launch finds nothing in flight
and its token is pulled before anything else is dispatched. Proven here
for the six model families at tiny sizes on the CPU, where greedy tokens
are exact, pass by pass and by hand: no thread decides what a pass meets.
What the run-ahead gains is the chip's to say (PERF.md).
"""
import time

import jax
import numpy as np
import pytest

from nnstreamer_tpu.obs import context as obs_context
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine
from nnstreamer_tpu.serving.kv_pool import PagePoolExhausted

SLOTS, PAGE, CHUNK = 3, 4, 8
ENGINE = dict(slots=SLOTS, page_size=PAGE, chunk=CHUNK)


def _gpt(**over):
    """The small ``gpt`` model with weights eight times larger: its greedy
    streams differ from token to token and from prompt to prompt."""
    from nnstreamer_tpu.models.lm_serving import tiny
    from nnstreamer_tpu.models.transformer import init_params

    params = jax.tree_util.tree_map(lambda a: a * 8,
                                    init_params(tiny.cfg, seed=0))
    return PagedLMEngine(tiny.cfg, params, **{
        **ENGINE, "share_prefixes": True, **over})


def _latent(**over):
    from test_deepseek_v3_serving import _model

    cfg, _, _, params = _model()
    return PagedLMEngine(cfg, params, **{
        **ENGINE, "share_prefixes": True, **over})


def _window(**over):
    from test_mellum_serving import _model

    cfg, _, _, params = _model()
    return PagedLMEngine(cfg, params, **{
        **ENGINE, "share_prefixes": False, **over})


def _state(**over):
    from test_jamba_serving import _model

    cfg, _, _, params = _model()
    return PagedLMEngine(cfg, params, **{
        **ENGINE, "share_prefixes": False, **over})


def _looped(**over):
    from test_ouro_serving import _model

    cfg, _, _, params = _model()
    return PagedLMEngine(cfg, params, **{
        **ENGINE, "share_prefixes": True, **over})


def _drafting(**over):
    from test_exaone_moe_serving import _model

    cfg, _, _, params = _model()
    return PagedLMEngine(cfg, params, **{
        **ENGINE, "share_prefixes": False, **over})


FAMILIES = {"gpt": _gpt, "deepseek_v3": _latent, "mellum": _window,
            "jamba": _state, "ouro": _looped, "exaone_moe": _drafting}


def advance(eng, sync):
    """One pass's decode call: the tokens it answers with, a list a slot.
    ``sync``: collected at once (each step's own tokens, nothing left in
    flight); else one step (one round) ahead, as the scheduler drives it."""
    if eng.drafts:
        bursts = eng.step_tokens()
        if sync:
            bursts = [[int(t) for t in row if t >= 0]
                      for row in eng.collect()]
        return bursts
    tok = eng.step()
    if sync:
        tok = eng.collect()
    return [[int(t)] if t >= 0 else [] for t in tok]


class Loop:
    """The scheduler's pass by hand and in one thread: admit what is due
    into free slots, one ``prefill_tick``, retire what its token finished,
    one decode call, route and retire. A request is ``(pass it is due at,
    prompt, steps, the token count it ends at unforeseen or None)``: an
    ending by count stands for an EOS (the engine sees a ``release`` it
    could not foresee either way, and the count does not hang on what the
    toy model says)."""

    def __init__(self, eng, requests, sync):
        self.eng, self.requests, self.sync = eng, requests, sync
        self.out = [[] for _ in requests]
        self.waiting = list(range(len(requests)))
        self.free = list(range(eng.slots))[::-1]
        self.prefilling, self.live = {}, {}
        self.passes = 0
        self.joins = []     # (request, was a step in flight before its tick)

    def done(self):
        return not (self.waiting or self.prefilling or self.live)

    def _ends(self, i):
        _, _, steps, cut = self.requests[i]
        return len(self.out[i]) >= (steps if cut is None else cut)

    def _retire(self, slot, i):
        steps = self.requests[i][2]
        del self.out[i][steps:]          # a round's surplus past the budget
        self.eng.release(slot)
        self.free.append(slot)

    def tick(self):
        eng = self.eng
        while self.free and self.waiting and \
                self.requests[self.waiting[0]][0] <= self.passes:
            i = self.waiting.pop(0)
            slot = self.free.pop()
            eng.admit_start(slot, self.requests[i][1], self.requests[i][2])
            self.prefilling[slot] = i
        if not self.prefilling:
            return []
        in_flight = eng._flight is not None
        joined = eng.prefill_tick()
        for slot, first in joined:
            i = self.prefilling.pop(slot)
            self.joins.append((i, in_flight))
            self.out[i].append(int(first))
            if self._ends(i):
                self._retire(slot, i)
            else:
                self.live[slot] = i
        return joined

    def step(self):
        if self.live:
            for slot, burst in enumerate(advance(self.eng, self.sync)):
                i = self.live.get(slot)
                if i is None:
                    continue
                self.out[i].extend(burst)
                if self._ends(i):
                    del self.live[slot]
                    self._retire(slot, i)
        self.passes += 1

    def run(self, limit=400):
        while not self.done():
            self.tick()
            self.step()
            assert self.passes < limit, "the schedule does not end"
        return self.out


def _requests(vocab):
    """The mixed run: a long request that decodes throughout; prompts of one
    launch and of several that join while it decodes (one over a prefix of
    the first prompt's pages, where the family shares; one of a single
    token's budget; one that ends unforeseen at its first token and one
    three tokens in); and, when all of that is over and nothing is live,
    one more."""
    rng = np.random.default_rng(49)

    def prompt(n):
        return rng.integers(1, vocab - 4, n).astype(np.int32)

    first = prompt(21)                                  # three launches
    return [(0, first, 30, None),
            (5, prompt(5), 10, None),                   # one launch
            (7, np.concatenate([first[:20], prompt(14)]), 8, None),
            (9, prompt(9), 1, None),                    # its budget: 1 token
            (11, prompt(3), 9, 1),                      # "EOS" at the first
            (13, prompt(12), 9, 4),                     # "EOS" three on
            (200, prompt(7), 6, None)]                  # joins an idle engine


def _mirrors(eng):
    return {"pos": eng._pos.tolist(), "left": eng._left.tolist(),
            "mask": eng._mask.tolist(), "tok": eng._tok[:, 0].tolist(),
            "join": eng._join.tolist(), "held_from": eng._held_from.tolist(),
            "tables": [bt.tolist() for bt in eng._bts.values()],
            "used": [p.used_pages for p in eng.pools_by_kind.values()]}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_mixed_runs_streams_are_the_synchronous_forms(family):
    ahead, plain = FAMILIES[family](), FAMILIES[family]()
    requests = _requests(plain.family.vocab)
    sync = Loop(plain, requests, sync=True)
    want = sync.run()
    loop = Loop(ahead, requests, sync=False)
    got = loop.run()
    assert got == want, "riding ahead, every request's tokens are the same"
    for (_, _, steps, cut), stream in zip(requests, want):
        assert len(stream) == (steps if cut is None else cut)
    assert len({t for stream in want for t in stream}) > 4, \
        "the toy model does not say one token"
    n = len(requests)
    # the synchronous form never has a step in flight when a prompt ends
    assert plain.run_ahead["joins_ahead"] == 0
    assert plain.run_ahead["joins_drained"] == n
    # riding ahead: a join rode iff a step was in flight for it to ride
    # behind; in this schedule that is every join but the first and the
    # last, which find an idle engine
    rode = [i for i, in_flight in loop.joins if in_flight]
    assert sorted(i for i, _ in loop.joins) == list(range(n))
    assert rode == list(range(1, n - 1))
    assert ahead.run_ahead["joins_ahead"] == len(rode) == n - 2
    assert ahead.run_ahead["joins_drained"] == 2
    # the two unforeseen endings cost a step each and nothing else did:
    # the request of one token's budget was never in a step
    assert ahead.run_ahead["surplus_steps"] == 2
    assert plain.run_ahead["surplus_steps"] == 0
    for eng in (ahead, plain):
        assert eng.collect().max() == -1, "nothing is left in flight"
        assert eng._flight is eng._ahead is None and not eng._kept
        # one decode program, one launch: the seed is not counted, as the
        # state movers are not
        assert eng.compile_count == 2
    if ahead.share_prefixes:
        assert ahead.pool.prefix_hits == plain.pool.prefix_hits >= 1
        ahead.pool.clear_prefixes()
        plain.pool.clear_prefixes()
    assert _mirrors(ahead) == _mirrors(plain)
    assert all(used == 0 for used in _mirrors(ahead)["used"])
    ahead.close()
    plain.close()


# -- straight after a joining pass --------------------------------------------

def _until_joined(eng, sync, steps_b):
    """A (slot 0) decodes; B (slot 1, two launches) joins while it does.
    Returns the loop as B's last launch returned, before the pass's decode
    call: in the ahead form with that call's step already dispatched."""
    rng = np.random.default_rng(7)
    vocab = eng.family.vocab
    requests = [(0, rng.integers(1, vocab - 4, 6).astype(np.int32), 24, None),
                (3, rng.integers(1, vocab - 4, 11).astype(np.int32), steps_b,
                 None)]
    loop = Loop(eng, requests, sync)
    loop._ends = lambda i: False        # the test retires by hand
    while True:
        joined = loop.tick()
        if joined and joined[0][0] == 1:
            return loop
        loop.step()


def _finish(loop, keep=(0,)):
    """Decode until every request in ``keep`` has its budget, then release."""
    eng = loop.eng
    for _ in range(80):
        if all(len(loop.out[i]) >= loop.requests[i][2] for i in keep):
            break
        loop.step()
    for slot, i in list(loop.live.items()):
        del loop.out[i][loop.requests[i][2]:]
        eng.release(slot)
    loop.live.clear()
    # what a caller that stops stepping leaves in flight comes home
    while eng._flight is not None or eng._kept:
        eng.collect()
    return loop.out


ACTIONS = ["first_token_ends", "one_tokens_budget", "release_after",
           "preempt_restore", "close"]


@pytest.mark.parametrize("action", ACTIONS)
@pytest.mark.parametrize("family", ["gpt", "jamba", "exaone_moe"])
def test_what_follows_a_joining_pass_leaves_what_the_synchronous_form_does(
        family, action):
    runs = []
    for sync in (True, False):
        eng = FAMILIES[family]()
        loop = _until_joined(
            eng, sync, steps_b=1 if action == "one_tokens_budget" else 14)
        rode = eng.run_ahead["joins_ahead"]
        assert rode == (0 if sync else 1)
        assert (eng._ahead is not None) == (not sync)
        keep = (0, 1)
        if action in ("first_token_ends", "one_tokens_budget"):
            # the scheduler retires B between its launch and the pass's step
            assert eng._left[1] == (0 if action == "one_tokens_budget"
                                    else 13 - (not sync))
            del loop.live[1]
            eng.release(1)
            keep = (0,)
        loop.step()                      # the joining pass's decode call
        assert eng._ahead is None
        if action == "release_after":
            del loop.live[1]
            eng.release(1)
            keep = (0,)
        elif action == "preempt_restore":
            # what the drain brought home for B is owed to it, and comes
            # with the first call after its restore
            blob = eng.preempt(1)
            assert not eng._mask[1] and eng._flight is None
            assert (np.asarray(blob["owed"]) >= 0).any() == (not sync)
            del loop.live[1]
            loop.step()                  # A alone meanwhile
            eng.restore(1, blob)
            loop.live[1] = 1
        elif action == "close":
            eng.close()
            assert all(p.used_pages == 0 for p in eng.pools_by_kind.values())
            runs.append((None, _mirrors(eng), eng))
            continue
        outs = _finish(loop, keep)
        if eng.share_prefixes:
            eng.pool.clear_prefixes()
        runs.append((outs, _mirrors(eng), eng))
    (want, want_mirrors, plain), (got, mirrors, ahead) = runs
    if action != "close":
        # a request that was let go has what it had by then: one step's
        # tokens fewer where that step's were still on their way
        n = {i: len(got[i]) if i in keep else min(len(got[i]), len(want[i]))
             for i in (0, 1)}
        assert [got[i][:n[i]] for i in n] == [want[i][:n[i]] for i in n]
        assert n[0] == 24 and n[1] >= 1
    assert mirrors == want_mirrors
    assert all(used == 0 for used in mirrors["used"])
    if action == "one_tokens_budget":
        # B had no token to make: it was in no step, and none was dropped
        assert ahead.run_ahead["surplus_steps"] == 0
    elif action in ("first_token_ends", "release_after"):
        assert ahead.run_ahead["surplus_steps"] == 1
    for eng in (plain, ahead):
        eng.close()


def test_a_drain_between_a_ride_and_its_step_keeps_both_steps_tokens():
    """By hand only (the scheduler calls nothing that drains between a
    launch and the pass's step): ``preempt`` of a slot that is in the step
    in flight and in the one that rode behind it. Both steps' tokens come
    home, two are owed to the slot, and the calls that follow return the
    other slot's in order."""
    runs = []
    for sync in (True, False):
        eng = _gpt()
        loop = _until_joined(eng, sync, steps_b=14)
        if not sync:
            assert eng._flight is not None and eng._ahead is not None
            assert eng._flight[1][0] and eng._ahead[1][0]
        blob = eng.preempt(0)
        assert eng._flight is eng._ahead is None
        owed = [int(t) for t in np.ravel(blob["owed"]) if t >= 0]
        assert len(owed) == (0 if sync else 2)
        assert blob["pos"] == 6 + len(loop.out[0]) - 1 + len(owed)
        del loop.live[0]
        for _ in range(3):               # B alone: first what the drain kept
            loop.step()
        eng.restore(0, blob)
        loop.live[0] = 0
        runs.append(_finish(loop, (0, 1)))
        # the two steps under the preempt, and B's in flight at the restore
        assert eng.run_ahead["steps_collected_early"] == (0 if sync else 3)
        eng.close()
        assert eng.pool.used_pages == 0
    assert runs[1] == runs[0]


def test_a_pool_that_cannot_supply_the_step_leaves_the_pass_as_it_was():
    """B's last launch fits the pool and the step that would carry B does
    not: the ride's prepare runs out of pages, the join is a drained one,
    and ``step()`` raises where the scheduler handles it."""
    eng = _gpt(slots=2, pages=5, share_prefixes=False)
    rng = np.random.default_rng(3)
    a, b = (rng.integers(1, 60, n).astype(np.int32) for n in (6, 8))
    alone = []
    for p in (a, b):
        out = [eng.admit(0, p, 12)]
        while len(out) < 12:
            eng.step()
            out.append(int(eng.collect()[0]))
        eng.release(0)
        alone.append(out)
    out_a = [eng.admit(0, a, 12)]
    base = dict(eng.run_ahead)
    while len(out_a) < 4:                 # A holds three pages: 0..8 written
        tok = eng.step()
        if tok[0] >= 0:
            out_a.append(int(tok[0]))
    assert eng._flight is not None and eng.pool.used_pages == 3
    eng.admit_start(1, b, 12)             # two pages: positions 0..7
    (slot, first), = eng.prefill_tick()
    assert (slot, first) == (1, alone[1][0])
    assert eng._ahead is None, "the step could not be prepared"
    assert eng.run_ahead["joins_drained"] == base["joins_drained"] + 1
    assert eng.run_ahead["joins_ahead"] == base["joins_ahead"]
    assert eng.pool.used_pages == 5
    with pytest.raises(PagePoolExhausted):
        eng.step()
    blob = eng.preempt(0)                 # as the scheduler would
    out_b = [first]
    while len(out_b) < 12:
        tok = eng.step()
        if tok[1] >= 0:
            out_b.append(int(tok[1]))
    eng.release(1)
    eng.restore(0, blob)
    while len(out_a) < 12:
        tok = eng.step()
        if tok[0] >= 0:
            out_a.append(int(tok[0]))
    assert [out_a, out_b] == alone
    eng.close()
    assert eng.pool.used_pages == 0


def _wait_for(done, seconds=120.0):
    deadline = time.monotonic() + seconds
    while not done():
        assert time.monotonic() < deadline, "the scheduler made no progress"
        time.sleep(0.001)


# -- what a snapshot and the spans show ----------------------------------------

def test_the_counts_reach_the_snapshot_and_the_pull_says_whether_it_rode():
    eng = _gpt()
    obs_context.reset()
    sched = DecodeScheduler(eng, name="join-ahead")
    rng = np.random.default_rng(5)
    try:
        long = sched.submit(rng.integers(1, 60, 9).astype(np.int32),
                            steps=50)
        _wait_for(lambda: len(long.tokens) >= 3)  # decoding, a step in flight
        short = sched.submit(rng.integers(1, 60, 5).astype(np.int32),
                             steps=4)
        short.result(timeout=120)
        long.result(timeout=120)
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
    assert snap["joins_drained"] == 1 and snap["joins_ahead"] == 1
    assert snap["completed"] == 2 and snap["failed"] == 0
    spans = obs_context.finished_spans()
    pulls = [s for s in spans if s.name == "engine.chunk.pull"]
    assert [s.attrs["ahead"] for s in pulls] == [0, 1]
    # the pass in which the short prompt joined: its launch's dispatch, the
    # step's prepare and dispatch, and only then the pull of its token; the
    # pass's own step call dispatches nothing and pulls the step before
    rode = pulls[1]
    names = [s.name for s in sorted(
        (s for s in spans if s.parent is rode.parent
         and s.name.startswith("engine.")), key=lambda s: s.start_s)]
    assert names == ["engine.chunk.prepare", "engine.chunk.dispatch",
                     "engine.step.prepare", "engine.step.dispatch",
                     "engine.chunk.pull", "engine.step.pull"]


def test_the_benchmarks_proxy_stamps_every_token_of_a_finished_request():
    """``EngineProxy`` (the benchmark's, not this tree's to edit) stamps a
    request's first token at ``prefill_tick``'s return and one more at
    every ``step()`` it is live in: a finished request has at least as
    many stamps as tokens, whichever pass its tokens came home in."""
    from benchmark.drivers.lm_serving import EngineProxy

    eng = _gpt()
    proxy = EngineProxy(eng)
    sched = DecodeScheduler(proxy, name="join-ahead-proxy")
    rng = np.random.default_rng(6)
    records = []
    try:
        for n, steps in ((9, 30), (5, 7), (20, 12), (3, 1), (12, 9), (6, 5)):
            prompt = rng.integers(1, 60, n).astype(np.int32)
            record = {"token_t": [], "prompt_len": n}
            proxy.track(prompt, record)
            record["request"] = sched.submit(prompt, steps=steps)
            records.append(record)
            # the next one joins while this one decodes
            _wait_for(lambda: bool(record["token_t"]))
        for record in records:
            record["tokens"] = record["request"].result(timeout=120)[0]
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
    assert snap["joins_ahead"] >= 3
    for record in records:
        assert len(record["token_t"]) >= len(record["tokens"])
        assert record["token_t"] == sorted(record["token_t"])
