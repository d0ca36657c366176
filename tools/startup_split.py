"""Where a start went: one benchmark cell run in this process, then the
program's own account of its set-up.

    chiprun -- python tools/startup_split.py --workload opt1b3_chat \
        --seed 7 --seconds 48 --trace 1

Every argument goes to ``benchmark/run.py`` as it is (``--rehearse`` for the
same path on the CPU), and its lines come out as they do there. One line
follows, ``startup_split: {...}``: ``setup_s`` as the benchmark measured it,
split by what the program itself recorded before the window opened
(``obs.context.compile_account`` and ``startup_spans``):

* ``engine_build_s``: ``setup.params`` + ``setup.engine``, less jax's
  seconds charged to them (they are in the sums below);
* ``trace_lower_s``: jax's tracing and lowering, paid at every start, no
  second counted twice (``trace_lower_jax_sum_s``: the durations summed as
  the benchmark's listener sums them, nested ones counted again);
* ``cache_load_s`` / ``fresh_compile_s`` / ``fresh_compiles``: backend
  compiles that a persistent-cache hit preceded, and the others
  (``events_dropped``: events the account's bounded list had let go by
  then, which these sums lack; 0 in every cell today);
* ``ramp_s`` where the cell has a ramp, and ``remainder_s``: what none of
  these holds (the interpreter and imports, the backend's start, the
  benchmark's seeded weights, its warm-up requests' device time);
* ``timeline``: when each part began, in seconds from the process's start;
* ``engine``: what the ``setup.engine`` span says was built (slots, page
  size, launch width, pool and state bytes, and the weight matrices the
  family re-laid for serving: ``relaid_matrices``, ``relaid_bytes``);
* ``first_calls``: every ``program.first_call`` span with what jax charged
  to it, and ``fresh``: jax's names of the programs compiled fresh;
* ``window``: backend compiles inside the window, by the account, by the
  ``serving.pass`` spans that carry ``compiles``, and by the benchmark; and
  the prompts that joined inside it, ``joins_ahead`` / ``joins_drained``
  (of the spans the ring still holds: ``ring`` says how far back it goes);
* ``ring``: ``obs.context.stats()``, the age of the oldest span kept and
  whether it is older than the window's opening and the traced part.

The benchmark's files are run, not edited: the result line's ``outcome`` is
taken on its way through ``harness.result_line``.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SPLIT_KEYS = ("engine_build_s", "trace_lower_s", "cache_load_s",
              "fresh_compile_s", "ramp_s")


def split(account: dict, spans: list, setup_s: float, ramp_s=None) -> dict:
    """``setup_s`` split over an account's totals and the start-up spans,
    both already cut to what began before the window opened."""
    totals = account["totals"]
    build = [s for s in spans if s.name.startswith("setup.")]
    charged = sum(s.attrs.get(k, 0.0) for s in build
                  for k in ("trace_s", "lower_s", "compile_s"))
    out = {
        "setup_s": setup_s,
        "engine_build_s": sum(s.dur_s for s in build) - charged,
        "engine_build_gross_s": sum(s.dur_s for s in build),
        "trace_lower_s": totals["trace_own_s"] + totals["lower_own_s"],
        "trace_lower_jax_sum_s": totals["trace_s"] + totals["lower_s"],
        "cache_load_s": totals["load_s"],
        "fresh_compile_s": totals["fresh_s"],
        "fresh_compiles": totals["fresh"],
        "loads": totals["loads"],
        "unspanned_events": totals["unspanned"],
        "events_dropped": account.get("dropped", 0),
    }
    if ramp_s is not None:
        out["ramp_s"] = ramp_s
    out["remainder_s"] = setup_s - sum(out.get(k, 0.0) for k in SPLIT_KEYS)
    return out


def first_calls(spans: list) -> list:
    """One entry a ``program.first_call`` span: the program, the span that
    made the call, the call's host wall and what jax charged to it."""
    return [{"program": s.attrs.get("program"),
             "under": None if s.parent is None else s.parent.name,
             "dur_s": s.dur_s,
             **{k: s.attrs[k] for k in ("trace_s", "lower_s", "compile_s",
                                        "compiles", "cache_hits",
                                        "cache_misses") if k in s.attrs}}
            for s in spans if s.name == "program.first_call"]


def report(outcome: dict, t_start: float) -> dict:
    from nnstreamer_tpu.obs import context as obs_context

    facts = outcome["facts"]
    setup_s = outcome["end_to_end"]["setup_s"]
    opened = t_start + setup_s
    closed = opened + facts["window_s"]
    before = obs_context.compile_account(until=opened)
    spans = [s for s in obs_context.startup_spans() if s.start_s < opened]
    out = split(before, spans, setup_s, facts.get("ramp_s"))
    out["bench_setup_compile_s"] = facts.get("setup_compile_s")

    def rel(span_name, end=False):
        for s in spans:
            if s.name == span_name:
                return (s.end_s if end else s.start_s) - t_start
        return None

    events = obs_context.compile_account()["events"]
    out["timeline"] = {
        "first_jax_event_s": events[0].t - t_start if events else None,
        "setup_params_s": rel("setup.params"),
        "setup_engine_s": rel("setup.engine"),
        "setup_engine_end_s": rel("setup.engine", end=True),
        "first_program_call_s": rel("program.first_call"),
        "ramp_from_s": (setup_s - facts["ramp_s"]
                        if facts.get("ramp_s") is not None else None),
        "window_opened_s": setup_s}
    out["engine"] = next((dict(s.attrs) for s in spans
                          if s.name == "setup.engine"), None)
    out["first_calls"] = first_calls(spans)
    out["fresh"] = [{"fun": e.fun, "seconds": e.seconds, "span": e.span}
                    for e in before["events"]
                    if e.event == "compile_s" and not e.hit]
    ring = obs_context.finished_spans()
    inside = obs_context.compile_account(since=opened, until=closed)
    out["window"] = {
        "account_compiles": inside["totals"]["compiles"],
        "pass_compiles": sum(s.attrs.get("compiles", 0) for s in ring
                             if s.name == "serving.pass"
                             and opened <= s.start_s < closed),
        "bench_compiles_in_window": facts.get("compiles_in_window")}
    # the prompts that joined inside the window, by whether the pass's step
    # rode behind their last launch (``engine.chunk.pull``'s ``ahead``)
    pulls = [s.attrs.get("ahead") for s in ring
             if s.name == "engine.chunk.pull" and opened <= s.start_s < closed]
    out["window"]["joins_ahead"] = pulls.count(1)
    out["window"]["joins_drained"] = pulls.count(0)
    now = time.monotonic()
    oldest = ring[0].start_s if ring else None
    bounds = facts.get("trace_bounds")
    spec = facts["mix"]["trace"]
    # where the traced part begins, or would in a run with --trace 1
    traced_from = bounds[0] if bounds else opened + min(
        spec["start_s"], max(facts["window_s"] - spec["seconds"], 0.0))
    out["ring"] = {
        **obs_context.stats(), "max_finished": obs_context.MAX_FINISHED,
        "oldest_age_s": None if oldest is None else now - oldest,
        "oldest_before_window": None if oldest is None else oldest <= opened,
        "oldest_before_traced_part": (
            None if oldest is None else oldest <= traced_from),
        "spans_in_window": sum(1 for s in ring
                               if opened <= s.start_s < closed),
        "passes_in_window": sum(1 for s in ring if s.name == "serving.pass"
                                and opened <= s.start_s < closed)}
    return out


def main(argv=None) -> int:
    import benchmark.run as bench_run
    from benchmark.lib import harness

    taken = {}
    result_line = harness.result_line

    def spy(bench, cell, outcome, *rest):
        taken["outcome"] = outcome
        return result_line(bench, cell, outcome, *rest)

    harness.result_line = spy
    try:
        code = bench_run.main(argv)
    finally:
        harness.result_line = result_line
    if "outcome" in taken:
        print("startup_split: " + json.dumps(
            report(taken["outcome"], bench_run.T_START)), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
