"""The program's own account of its start-up (ISSUE 36): the compile account
that ``obs.context`` keeps from ``jax.monitoring``, the ``setup.*`` and
``program.first_call`` spans of an engine built by ``make_continuous`` and
served through a ``DecodeScheduler``, the pass that recompiled, and the
split of a benchmark cell's ``setup_s`` that ``tools/startup_split.py``
prints from them.

CPU, ``tiny`` preset: which span paid, what was counted and what outlives
the ring are proven here; every second is the chip's to give (PERF.md).
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.obs import context as obs_ctx
from nnstreamer_tpu.serving import DecodeScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

JAX_SECONDS = ("trace_s", "lower_s", "compile_s")
# two prompts of two chunks of 8 and one: every program of a plain engine
# runs more than once
REQUESTS = ((13, 5), (6, 4))
# the shortest pass of a cell that keeps its batch full (opt1b3_saturated;
# ledger, PR 35: tpot_p50_ms 7.54)
FASTEST_PASS_S = 0.0075


def _engine(**kw):
    from nnstreamer_tpu.models.lm_serving import tiny

    return tiny.make_continuous(slots=4, page_size=8, chunk=8, **kw)


def _serve(engine, name, requests=REQUESTS, seed=36):
    rng = np.random.default_rng(seed)
    sched = DecodeScheduler(engine, name=name)
    try:
        reqs = [sched.submit(rng.integers(0, 64, n).astype(np.int32), steps=s)
                for n, s in requests]
        for r in reqs:
            r.result(timeout=120)
    finally:
        sched.close()
    return sched.metrics_snapshot()


@pytest.fixture(scope="module")
def started():
    """One engine built and served, beside the benchmark's own listener
    over the same interval."""
    from benchmark.lib.compile_clock import CompileClock

    obs_ctx.reset()
    clock = CompileClock()
    t0 = time.monotonic()
    before = obs_ctx.compile_account()["totals"]
    engine = _engine()
    snap = _serve(engine, "startup")
    return {"engine": engine, "snap": snap, "t0": t0, "before": before,
            "clock": clock.read(), "spans": obs_ctx.finished_spans(),
            "startup": obs_ctx.startup_spans(),
            "account": obs_ctx.compile_account(since=t0)}


# -- the spans of start-up ------------------------------------------------------

def test_make_continuous_leaves_one_span_for_the_weights_and_one_for_the_engine(
        started):
    by_name = {}
    for s in started["startup"]:
        by_name.setdefault(s.name, []).append(s)
    (params,), (engine,) = by_name["setup.params"], by_name["setup.engine"]
    assert params.attrs["bytes"] == started["engine"].param_bytes > 0
    assert params.end_s <= engine.start_s
    eng = started["engine"]
    assert engine.attrs["slots"] == 4 and engine.attrs["page_size"] == 8
    assert engine.attrs["chunk"] == eng.chunk == 8
    assert engine.attrs["pool_bytes"] == {
        "full": (eng.pool.pages + 1) * eng.pool.page_bytes}
    assert engine.attrs["state_bytes"] == 0
    assert engine.dur_s > 0 and engine.parent is None


def test_each_program_that_ran_has_one_first_call_under_the_span_that_made_it(
        started):
    calls = [s for s in started["startup"] if s.name == "program.first_call"]
    # ``_seed``: the few bytes that put a prompt's first token into the
    # decode carry behind its last launch (PR 49), in the launch's dispatch
    assert sorted(s.attrs["program"] for s in calls) == [
        "_prefill_chunk", "_seed", "_step"]
    under = {s.attrs["program"]: s.parent.name for s in calls}
    assert under == {"_prefill_chunk": "engine.chunk.dispatch",
                     "_seed": "engine.chunk.dispatch",
                     "_step": "engine.step.dispatch"}
    # every later call opened nothing: the dispatch spans are many
    dispatches = [s for s in started["spans"]
                  if s.name in ("engine.chunk.dispatch",
                                "engine.step.dispatch")]
    assert len(dispatches) > 4
    in_ring = [s for s in started["spans"] if s.name == "program.first_call"]
    assert len(in_ring) == 3


def test_a_first_call_carries_what_jax_spent_on_it(started):
    for s in started["startup"]:
        if s.name != "program.first_call":
            continue
        assert s.attrs["trace_s"] > 0 and s.attrs["lower_s"] > 0
        assert s.attrs["compiles"] >= 1 and s.attrs["compile_s"] > 0
        # the call waited for all three
        assert sum(s.attrs[k] for k in JAX_SECONDS) <= s.dur_s
        # and the dispatch span above it gained nothing of its own
        assert not set(JAX_SECONDS) & set(s.parent.attrs)


def test_a_steady_pass_gains_no_attribute(started):
    passes = [s for s in started["spans"] if s.name == "serving.pass"]
    quiet = [p for p in passes if "compiles" not in p.attrs]
    assert quiet and len(quiet) < len(passes)
    for p in quiet:
        assert not {"compile_s", *JAX_SECONDS[:2]} & set(p.attrs)


# -- the account ----------------------------------------------------------------

def test_the_account_counts_what_the_benchmarks_clock_counts(started):
    mine, clock = started["account"]["totals"], started["clock"]
    assert mine["compiles"] == clock["compiles"] > 0
    assert mine["compile_s"] == pytest.approx(clock["compile_s"], rel=1e-9)
    assert mine["trace_s"] + mine["lower_s"] == pytest.approx(
        clock["trace_s"], rel=1e-9)
    assert mine["cache_hits"] == clock["cache_hits"]
    assert mine["cache_misses"] == clock["cache_misses"]
    assert mine["load_s"] + mine["fresh_s"] == pytest.approx(mine["compile_s"])
    assert mine["loads"] + mine["fresh"] == mine["compiles"]
    # the running totals moved by as much
    now = obs_ctx.compile_account()["totals"]
    assert now["compiles"] - started["before"]["compiles"] >= mine["compiles"]


def test_every_event_names_the_span_it_was_charged_to(started):
    events = started["account"]["events"]
    assert [e.t for e in events] == sorted(e.t for e in events)
    compiled = [e for e in events if e.event == "compile_s"]
    assert {"jit(_step)", "jit(_prefill_chunk)"} <= {e.fun for e in compiled}
    for e in compiled:
        if e.fun in ("jit(_step)", "jit(_prefill_chunk)"):
            assert e.span == "program.first_call" and e.hit in (True, False)
    assert {e.event for e in events} <= {
        "trace_s", "lower_s", "compile_s", "cache_hits", "cache_misses"}


def test_an_event_on_a_thread_with_no_span_is_kept_with_none_and_counted():
    import jax
    import jax.numpy as jnp

    seen = {}

    def compile_bare():
        seen["top"] = getattr(obs_ctx._open, "top", None)
        jax.jit(lambda x: x * 36 + 1)(jnp.ones((5,))).block_until_ready()

    t0 = time.monotonic()
    before = obs_ctx.compile_account()["totals"]
    with obs_ctx.span("held.elsewhere") as held:  # on this thread, not that
        worker = threading.Thread(target=compile_bare)
        worker.start()
        worker.join()
    got = obs_ctx.compile_account(since=t0)
    assert seen["top"] is None and not set(JAX_SECONDS) & set(held.attrs)
    heard = got["totals"]["compiles"]
    assert heard >= 1   # the jitted lambda, and the array it was given
    assert got["events"] and all(e.span is None for e in got["events"])
    assert got["totals"]["unspanned"] == len(got["events"])
    after = obs_ctx.compile_account()["totals"]
    assert after["compiles"] == before["compiles"] + heard
    assert after["unspanned"] >= before["unspanned"] + len(got["events"])


def test_a_compile_that_follows_a_cache_hit_on_its_thread_is_a_load():
    t0 = time.monotonic()
    hit, compiled = ("/jax/compilation_cache/cache_hits",
                     "/jax/core/compile/backend_compile_duration")
    with obs_ctx.span("setup.engine") as sp:
        obs_ctx._heard_count(hit)
        obs_ctx._heard_seconds(compiled, 2 ** -9, fun_name="jit(loaded)")
        time.sleep(0.02)  # the next one began after that one had ended
        obs_ctx._heard_seconds(compiled, 2 ** -8, fun_name="jit(fresh)")
        obs_ctx._heard_seconds("/jax/core/compile/unknown_duration", 9.0)
    totals = obs_ctx.compile_account(since=t0)["totals"]
    assert (totals["loads"], totals["load_s"]) == (1, 2 ** -9)
    assert (totals["fresh"], totals["fresh_s"]) == (1, 2 ** -8)
    assert sp.attrs == {"cache_hits": 1, "compile_s": 3 * 2 ** -9,
                        "compiles": 2}
    events = obs_ctx.compile_account(since=t0)["events"]
    assert [(e.fun, e.hit) for e in events if e.event == "compile_s"] == [
        ("jit(loaded)", True), ("jit(fresh)", False)]


def test_a_duration_that_ended_inside_another_is_counted_once():
    """Tracing a function holds the tracing of the jitted functions it
    calls: jax reports both, the account knows which second is whose."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    t0 = time.monotonic()
    time.sleep(0.3)
    with obs_ctx.span("program.first_call", program="nested") as sp:
        obs_ctx._heard_seconds(trace, 0.0625, fun_name="inner")
        obs_ctx._heard_seconds(trace, 0.25, fun_name="outer")  # holds inner
        time.sleep(0.02)
        obs_ctx._heard_seconds(lower, 0.015625, fun_name="jit(outer)")
    got = obs_ctx.compile_account(since=t0)
    assert [(e.fun, e.seconds, e.own) for e in got["events"]] == [
        ("inner", 0.0625, 0.0625), ("outer", 0.25, 0.1875),
        ("jit(outer)", 0.015625, 0.015625)]
    assert got["totals"]["trace_s"] == 0.3125       # as any listener sums
    assert got["totals"]["trace_own_s"] == 0.25     # no second twice
    assert got["totals"]["lower_own_s"] == got["totals"]["lower_s"] == 0.015625
    # the span is charged own seconds: what it carries is wall time
    assert sp.attrs == {"program": "nested", "trace_s": 0.25,
                        "lower_s": 0.015625}


def test_the_account_is_bounded_and_its_totals_run_on():
    before = obs_ctx.compile_account()["totals"]["cache_misses"]
    for _ in range(obs_ctx.MAX_COMPILE_EVENTS + 5):
        obs_ctx._heard_count("/jax/compilation_cache/cache_misses")
    got = obs_ctx.compile_account()
    assert len(got["events"]) == obs_ctx.MAX_COMPILE_EVENTS
    assert got["totals"]["cache_misses"] == \
        before + obs_ctx.MAX_COMPILE_EVENTS + 5
    assert got["dropped"] == 0, "the running totals lack nothing"
    # a sum over an interval that reaches before the oldest event kept
    # says how many it lacks; one that begins at or after it lacks none
    oldest = got["events"][0].t
    assert obs_ctx.compile_account(until=time.monotonic())["dropped"] >= 5
    assert obs_ctx.compile_account(since=oldest - 1.0)["dropped"] >= 5
    assert obs_ctx.compile_account(since=oldest)["dropped"] == 0


def test_a_pass_counts_its_own_threads_compiles_and_no_others():
    compile_ = "/jax/core/compile/backend_compile_duration"
    mine = obs_ctx.compile_running()
    heard = obs_ctx.compile_account()["totals"]["compiles"]
    other = threading.Thread(
        target=lambda: obs_ctx._heard_seconds(compile_, 0.5, fun_name="x"))
    other.start()
    other.join()
    assert obs_ctx.compile_running() == mine, "another thread's compile"
    assert obs_ctx.compile_account()["totals"]["compiles"] == heard + 1
    obs_ctx._heard_seconds(compile_, 0.25, fun_name="y")
    assert obs_ctx.compile_running() == (mine[0] + 1, mine[1] + 0.25)


# -- start-up outlives the ring ---------------------------------------------------

def test_the_startup_spans_outlive_the_ring(started):
    def kept():  # the served engine's, among what later cases left
        return [s for s in obs_ctx.startup_spans() if s in started["startup"]]

    assert kept() == started["startup"]
    for _ in range(obs_ctx.MAX_FINISHED + 1):
        obs_ctx.span("filler").record(0.0, 1.0)
    ring = obs_ctx.finished_spans()
    assert len(ring) == obs_ctx.MAX_FINISHED
    assert not any(s.name.startswith(obs_ctx.STARTUP_NAMES) for s in ring)
    assert kept() == started["startup"]
    assert {"setup.params", "setup.engine", "program.first_call"} == {
        s.name for s in kept()}
    obs_ctx.reset()
    assert obs_ctx.startup_spans() == []


def test_the_ring_holds_a_window_of_the_fastest_cells_passes():
    """The spans of a stretch of passes are all still there after as many
    later passes as a 48 s window of the fastest cell holds (PERF.md
    section 6, PR 36: the ring's sizing)."""
    obs_ctx.reset()
    per_pass = ("serving.pass", "sched.admit", "engine.step.prepare",
                "engine.step.dispatch", "engine.step.pull", "sched.route",
                "engine.release", "engine.chunk.dispatch")  # and then some

    def a_pass(tag):
        for name in per_pass:
            obs_ctx.span(name, tag=tag).record(0.0, 1.0)

    for _ in range(100):
        a_pass("first")
    for _ in range(int(48 / FASTEST_PASS_S)):
        a_pass("later")
    first = [s for s in obs_ctx.finished_spans() if s.attrs["tag"] == "first"]
    assert len(first) == 100 * len(per_pass)
    obs_ctx.reset()


# -- the pass that recompiled -------------------------------------------------------

def test_a_compile_inside_a_served_pass_shows_on_the_pass_and_in_the_snapshot():
    # a pool too small for both streams: the first preempt's gather is a
    # program nobody has called yet, so it compiles inside a served pass
    engine = _engine(pages=5, share_prefixes=False)
    obs_ctx.reset()
    t0 = time.monotonic()
    snap = _serve(engine, "recompiled", requests=((12, 14), (12, 14)), seed=3)
    assert snap["preempted"] >= 1 and snap["restored"] >= 1
    spans = obs_ctx.finished_spans()
    calls = {s.attrs["program"]: s for s in obs_ctx.startup_spans()
             if s.name == "program.first_call"}
    gather = calls["gather.full"]
    assert gather.parent.name == "engine.preempt"
    assert gather.attrs["compiles"] >= 1 and gather.attrs["trace_s"] > 0
    assert calls["scatter.full"].parent.name == "engine.restore"
    # the pass above the preempt says that it compiled, and how long
    the_pass = gather.parent
    while the_pass.parent is not None:
        the_pass = the_pass.parent
    assert the_pass.name == "serving.pass"
    assert the_pass.attrs["compiles"] >= gather.attrs["compiles"]
    assert the_pass.attrs["compile_s"] >= gather.attrs["compile_s"]
    # the snapshot sums the passes', which is the account's over the serving
    passes = [s for s in spans if s.name == "serving.pass"]
    assert snap["compiles"] == sum(p.attrs.get("compiles", 0) for p in passes)
    assert snap["compile_s"] == pytest.approx(
        sum(p.attrs.get("compile_s", 0.0) for p in passes))
    heard = obs_ctx.compile_account(since=t0)["totals"]
    assert 0 < snap["compiles"] <= heard["compiles"]
    assert sum(1 for p in passes if "compiles" in p.attrs) < len(passes)


def test_the_pass_compiles_reach_the_metrics_plane():
    from nnstreamer_tpu.obs import metrics as obs_metrics

    sched = DecodeScheduler(_engine(), name="compiled-plane")
    try:
        sched.submit(np.arange(5, dtype=np.int32), steps=2).result(timeout=120)
        text = obs_metrics.render()
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
    assert snap["compiles"] >= 2  # this engine's two programs, first called
    for name in ("compiles", "compile_seconds"):
        assert f'nns_serving_{name}_total{{scheduler="compiled-plane"}}' in text


# -- the split of a cell's set-up -----------------------------------------------------

def _hand_built():
    """An account and the spans of a start, before a window that opened at
    t = 10: a load, two fresh compiles, tracing and lowering."""
    def ev(t, event, seconds=0.0, hit=None, span=None, fun=None, own=None):
        return obs_ctx.CompileEvent(
            t, event, seconds, seconds if own is None else own, hit, span, fun)

    events = [
        ev(0.9, "trace_s", 0.125, fun="inner", own=0.125),
        ev(1.0, "trace_s", 0.5, fun="<lambda>", own=0.375),
        ev(1.5, "compile_s", 1.0, False, None, "jit(<lambda>)"),
        ev(3.0, "trace_s", 0.25, span="setup.engine"),
        ev(3.1, "compile_s", 0.125, False, "setup.engine", "jit(zeros)"),
        ev(5.0, "trace_s", 1.5, span="program.first_call", fun="_step"),
        ev(5.5, "lower_s", 0.75, span="program.first_call", fun="jit(_step)"),
        ev(6.0, "cache_hits", 0.0, True, "program.first_call"),
        ev(6.5, "compile_s", 2.0, True, "program.first_call", "jit(_step)"),
    ]
    totals = dict(obs_ctx._NO_TOTALS)
    for e in events:
        obs_ctx._tally(totals, e)
    totals["fresh_s"] = totals["compile_s"] - totals["load_s"]
    totals["fresh"] = totals["compiles"] - totals["loads"]
    params = obs_ctx.span("setup.params", bytes=8)
    params.start_s, params.dur_s = 2.0, 0.5
    engine = obs_ctx.span("setup.engine", slots=4, trace_s=0.25,
                          compile_s=0.125, compiles=1)
    engine.start_s, engine.dur_s = 2.5, 1.375
    dispatch = obs_ctx.span("engine.step.dispatch")
    call = obs_ctx.span("program.first_call", parent=dispatch,
                        program="_step", trace_s=1.5, lower_s=0.75,
                        compile_s=2.0, compiles=1, cache_hits=1)
    call.start_s, call.dur_s = 4.9, 4.5
    return {"totals": totals, "events": events}, [params, engine, call]


def test_the_split_adds_up_to_the_setup_it_was_given():
    from tools import startup_split

    account, spans = _hand_built()
    got = startup_split.split(account, spans, setup_s=10.0, ramp_s=1.5)
    assert got["engine_build_gross_s"] == 1.875
    assert got["engine_build_s"] == 1.5       # less jax's 0.375 inside it
    assert got["trace_lower_s"] == 3.0      # the nested trace counted once
    assert got["trace_lower_jax_sum_s"] == 3.125
    assert got["cache_load_s"] == 2.0 and got["loads"] == 1
    assert got["fresh_compile_s"] == 1.125 and got["fresh_compiles"] == 2
    assert got["ramp_s"] == 1.5
    assert got["remainder_s"] == 10.0 - (1.5 + 3.0 + 2.0 + 1.125 + 1.5)
    assert got["unspanned_events"] == 3
    # a cell without a ramp: no entry, and its seconds fall to the remainder
    bare = startup_split.split(account, spans, setup_s=10.0)
    assert "ramp_s" not in bare and bare["remainder_s"] == 2.375
    assert startup_split.first_calls(spans) == [{
        "program": "_step", "under": "engine.step.dispatch", "dur_s": 4.5,
        "trace_s": 1.5, "lower_s": 0.75, "compile_s": 2.0, "compiles": 1,
        "cache_hits": 1}]


@pytest.mark.parametrize("cell,programs,relaid", [
    ("opt1b3_chat", {"_prefill_chunk", "_step"}, 0),
    ("jamba2_reasoning_saturated", {"_prefill_chunk", "_step"}, 0),
    # wq and wk of the rehearsal's four layers (``family.stored``)
    ("ouro_reasoning_saturated", {"_prefill_chunk", "_step"}, 8),
])
def test_a_rehearsed_cell_prints_the_split_of_its_setup(cell, programs,
                                                        relaid):
    """The tool runs the benchmark's command in its process (the CPU, the
    cell's rehearsal sizes), leaves its lines as they are and adds one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "startup_split.py"),
         "--workload", cell, "--seed", "3600000036", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-2])
    assert line["correct"] is True and line["rehearsal"] is True
    assert lines[-1].startswith("startup_split: ")
    got = json.loads(lines[-1][len("startup_split: "):])
    for key in ("engine_build_s", "trace_lower_s", "cache_load_s",
                "fresh_compile_s", "fresh_compiles", "remainder_s"):
        assert got[key] is not None and got[key] >= 0, key
    # the account heard what the benchmark's own listener heard
    assert got["cache_load_s"] + got["fresh_compile_s"] == pytest.approx(
        got["bench_setup_compile_s"], rel=1e-6)
    assert got["window"]["account_compiles"] \
        == got["window"]["pass_compiles"] \
        == got["window"]["bench_compiles_in_window"] == 0
    assert programs <= {c["program"] for c in got["first_calls"]}
    parts = sum(got.get(k, 0.0) for k in (
        "engine_build_s", "trace_lower_s", "cache_load_s", "fresh_compile_s",
        "ramp_s", "remainder_s"))
    assert parts == pytest.approx(got["setup_s"])
    marks = got["timeline"]
    assert (marks["setup_params_s"] <= marks["setup_engine_s"]
            <= marks["setup_engine_end_s"] <= marks["first_program_call_s"]
            <= marks["window_opened_s"])
    assert got["engine"]["relaid_matrices"] == relaid
    assert (got["engine"]["relaid_bytes"] > 0) == (relaid > 0)
    assert got["ring"]["oldest_before_traced_part"] is True
    assert got["ring"]["retained"] <= got["ring"]["max_finished"]
