"""What decides ``correct``, shown to fail when it should: the control (the
plain reference put through the precision below the configuration's) and a
run of the driver with the timed path broken underneath. CPU, at the sizes
the configurations give under ``rehearsal``; the chip's own readings and the
limits set from them are in PERF.md."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import lm_serving  # noqa: E402
from benchmark.lib import harness, traffic  # noqa: E402
from benchmark.lib.compile_clock import CompileClock  # noqa: E402
from benchmark.lib.correct import served_gaps  # noqa: E402

BENCH = harness.load_benchmark()


def rehearsal_ctx(workload: str, seed: int, seconds: float) -> dict:
    """What ``run.py`` hands a driver under ``--rehearse``, without its
    look for a chip."""
    cell, config = harness.find_cell(BENCH, workload)
    mix = traffic.load(cell["traffic"])
    return {"seed": seed, "seconds": seconds, "cell": cell, "bench": BENCH,
            "config": {**config, **config["rehearsal"]},
            "mix": {**mix, **mix["rehearsal"]}, "clock": CompileClock(),
            "tracer": None, "t_start": time.monotonic(), "rehearse": True}


def run_counting_tokens_home(driver, proxy_class, ctx) -> dict:
    """A driver's ``run`` of an expert family's cell, with
    ``out["tokens_home"]``: for every ``step()`` call of the proxy (keyed by
    the time it notes for the step) how many tokens the call brought home.
    The loop runs one step ahead, so those tokens, and the expert counts
    that came home behind them, are of the step dispatched a call earlier,
    not of the batch the proxy holds at the call."""
    home = {}
    real_step = proxy_class.step

    def counting(self):
        out = real_step(self)
        home[self.moe_steps[-1][0]] = int((np.asarray(out) >= 0).sum())
        return out

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(proxy_class, "step", counting)
        out = driver.run(ctx)
    out["tokens_home"] = home
    return out


def test_served_gaps_is_zero_for_the_best_and_the_distance_otherwise():
    scores = np.array([[0.1, 0.9, 0.3], [2.0, -1.0, 1.5]], np.float32)
    assert served_gaps(scores, np.array([1, 0])).tolist() == [0.0, 0.0]
    assert served_gaps(scores, np.array([2, 1])) == pytest.approx([0.6, 3.0])
    with pytest.raises(ValueError):
        served_gaps(scores, np.array([1]))


# -- language-model serving --------------------------------------------------------

@pytest.fixture(scope="module")
def sound_lm_run():
    ctx = rehearsal_ctx("opt1b3_chat", 2**31 + 77, 2.0)
    return ctx, lm_serving.run(ctx)


def test_a_sound_serving_run_is_correct(sound_lm_run):
    ctx, out = sound_lm_run
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == len(ctx["mix"]["requests"])
    assert set(out["end_to_end"]) == {"setup_s", "ttft_p50_ms", "tpot_p50_ms"}
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_max"][0] <= checks["served_gap_max"][1]
    assert checks["served_tokens_compared"][0] > 0
    # every due request is a sample of time to first token
    assert len(out["facts"]["ttft_ms"]) == out["attempted"]
    assert out["facts"]["compiles_in_window"] == 0


# the control at a size a test run can hold: wider than the rehearsal's, so
# that a few hundred positions hold near ties for a lower precision to flip
CONTROL_SIZES = {"vocab_size": 2048, "hidden_size": 64, "num_hidden_layers": 2,
                 "num_attention_heads": 4, "ffn_dim": 256,
                 "max_position_embeddings": 128}


@pytest.mark.parametrize("seed", [7, 2**31 + 8, 99])
def test_lower_precision_in_the_references_place_fails_the_limits(seed):
    _, config = harness.find_cell(BENCH, "opt1b3_chat")
    config = {**config, **config["rehearsal"], **CONTROL_SIZES}
    rng = np.random.default_rng(seed)
    # teacher-forced positions: at each, the token the lower precision puts
    # first against the reference's best (what was served plays no part)
    pairs = [(rng.integers(0, 2048, 1, dtype=np.int32),
              rng.integers(0, 2048, 100, dtype=np.int32)) for _ in range(4)]
    got = lm_serving.served_logit_gaps(config, seed, pairs, [(1, 100)],
                                       quants=("none", "int8", "fp8"))
    limits = config["check"]  # a sound run on the CPU reads 0 for both
    for q in ("int8", "fp8"):
        control = np.concatenate(got[q])
        assert control.max() > limits["served_gap_max_limit"], q
        assert control.mean() > limits["served_gap_mean_limit"], q


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    real_step = lm_serving.EngineProxy.step

    def altered(self):
        return (real_step(self) + 1) % self._engine.cfg.vocab

    monkeypatch.setattr(lm_serving.EngineProxy, "step", altered)
    out = lm_serving.run(rehearsal_ctx("opt1b3_chat", 5, 2.0))
    assert out["correct"] is False
    checks = {n: (v, lim) for n, v, lim in out["checks"]}
    assert checks["served_gap_mean"][0] > checks["served_gap_mean"][1]


def test_a_closed_loop_run_opens_its_window_with_every_client_live():
    ctx = rehearsal_ctx("opt1b3_saturated", 2**31 + 9, 1.5)
    out = lm_serving.run(ctx)
    assert out["correct"] and out["failed"] == 0
    clients = ctx["mix"]["clients"]
    assert out["attempted"] > clients  # the clients went on to their next
    # no request was due at a time of the window: a first token is timed
    # only for those that waited for another, from that one's last token
    assert 0 < len(out["facts"]["ttft_ms"]) <= out["attempted"] - clients
    assert min(out["facts"]["ttft_ms"]) >= 0.0
    assert min(out["facts"]["gen_late_ms"]) >= 0.0
    assert out["facts"]["out_tokens"] > 0


class _Req:
    """A request of a scheduler that finishes it ``steps`` polls later."""
    error = None

    def __init__(self, polls):
        self.polls = polls

    def done(self):
        self.polls -= 1
        return self.polls < 0


class _Sched:
    def __init__(self, proxy):
        self.proxy, self.sent = proxy, []

    def submit(self, prompt, steps):
        self.sent.append(int(prompt[0]))
        record = self.proxy.by_prompt[id(prompt)]
        record["token_t"].extend([time.monotonic()] * 2)
        return _Req(steps)


class _Proxy:
    def __init__(self):
        self.by_prompt = {}

    def track(self, prompt, record):
        self.by_prompt[id(prompt)] = record


def _item(tag, **kw):
    return {"prompt": np.array([tag], np.int32), "steps": 3, "due_s": None,
            "after": None, "ramp": False, **kw}


def test_drive_sends_by_due_time_and_by_what_a_request_waits_for():
    proxy = _Proxy()
    sched = _Sched(proxy)
    items = [_item(0, ramp=True), _item(1, ramp=True),
             _item(2, after=0), _item(3, due_s=0.05),
             _item(4, due_s=0.10, after=3), _item(5, due_s=9.0)]
    opened = []
    t0, cutoff, records = lm_serving.drive(
        sched, proxy, items, 0.4, None, lambda: opened.append(list(sched.sent)))
    # the ramp's requests went out before the window opened, no other did
    assert opened == [[0, 1]]
    assert sched.sent[:2] == [0, 1] and sorted(sched.sent) == [0, 1, 2, 3, 4]
    by_tag = {int(r["prompt"][0]): r for r in records}
    assert 5 not in by_tag  # due after the window's end: never sent
    assert by_tag[3]["due_t"] == pytest.approx(t0 + 0.05)
    assert by_tag[3]["sent_t"] >= by_tag[3]["due_t"]
    # one that waits for another is due when that one finished, and no
    # earlier than its own time
    assert by_tag[2]["due_t"] == by_tag[0]["token_t"][-1]
    assert by_tag[4]["due_t"] >= t0 + 0.10
    assert by_tag[4]["sent_t"] >= by_tag[3]["sent_t"]
    ttft, tpot, out_tokens = lm_serving.window_samples(records, t0, cutoff)
    # request 2 was due before the window opened (when request 0 finished),
    # and the ramp's tokens fell before it too
    assert len(ttft) == 2 and out_tokens == 6
