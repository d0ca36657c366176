"""Spans inside the serving loop (ISSUE 24): the program's own span source
(``obs.context.span``), the tree a ``DecodeScheduler`` leaves behind over a
paged engine, the counters at the same boundaries, the same spans in the
profiler's host plane, and what all of it costs when nothing is looking.

CPU, ``tiny`` preset: counts, nesting and order are proven here; every time
is the chip's to give (PERF.md).
"""
import glob
import os
import time

import numpy as np
import pytest

from nnstreamer_tpu.obs import context as obs_ctx
from nnstreamer_tpu.obs import flight as obs_flight
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine

PASS_PHASES = {"sched.admit", "engine.chunk.prepare", "engine.chunk.dispatch",
               "engine.chunk.pull", "engine.step.prepare",
               "engine.step.dispatch", "engine.step.pull", "sched.route"}
REQUEST_PHASES = ["request.queue", "request.lane", "request.prefill",
                  "request.decode"]
# (prompt length, steps): three chunks of 8, one, two; all steps differ, so
# no pass retires two requests
REQUESTS = ((20, 6), (5, 3), (13, 9))
SPAN_BUDGET_S = 5e-6   # ISSUE 24: one span() with no profiler session
SPANS_PER_PASS = 12


def _tiny_engine(**kw):
    from nnstreamer_tpu.models.lm_serving import tiny
    from nnstreamer_tpu.models.transformer import init_params

    params = init_params(tiny.cfg, seed=0)
    return PagedLMEngine(tiny.cfg, params, slots=4, page_size=8, chunk=8, **kw)


def _prompts(vocab=64):
    rng = np.random.default_rng(24)
    return [rng.integers(0, vocab, n).astype(np.int32) for n, _ in REQUESTS]


def _serve(engine, name):
    """Run REQUESTS through a scheduler over ``engine``; returns the
    finished requests, the last snapshot and the ring's spans."""
    obs_ctx.reset()
    sched = DecodeScheduler(engine, name=name)
    try:
        reqs = [sched.submit(p, steps=s)
                for p, (_, s) in zip(_prompts(), REQUESTS)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        sched.close()  # joins the loop: the last pass and tree are written
    return reqs, sched.metrics_snapshot(), obs_ctx.finished_spans()


def _children(spans):
    kids = {}
    for s in spans:
        if getattr(s, "parent", None) is not None:
            kids.setdefault(id(s.parent), []).append(s)
    return kids


def _under(span, kids):
    for child in kids.get(id(span), ()):
        yield child
        yield from _under(child, kids)


@pytest.fixture(scope="module")
def served():
    flight_before = obs_flight.count()
    reqs, snap, spans = _serve(_tiny_engine(), "spans")
    return {"reqs": reqs, "snap": snap, "spans": spans,
            "kids": _children(spans),
            "flight_events": obs_flight.dump(after=flight_before - 1)}


def _passes(served):
    return [s for s in served["spans"] if s.name == "serving.pass"]


# -- the tree --------------------------------------------------------------------

def test_every_child_lies_inside_its_parent(served):
    nested = [s for s in served["spans"]
              if isinstance(getattr(s, "parent", None), obs_ctx.ProgramSpan)]
    assert nested
    for s in nested:
        assert s.parent.start_s <= s.start_s
        assert s.end_s <= s.parent.end_s + 1e-9, (s, s.parent)
        assert s.trace_id == s.parent.trace_id
        assert s.parent_id == s.parent.span_id


def test_a_pass_is_its_phases_in_order_and_its_own_time(served):
    passes = _passes(served)
    assert passes
    for p in passes:
        kids = sorted(served["kids"].get(id(p), ()), key=lambda s: s.start_s)
        assert {k.name for k in kids} <= PASS_PHASES
        assert kids[0].name == "sched.admit"
        # phases follow one another, so self time + children = duration
        # with a self time that is not negative
        for a, b in zip(kids, kids[1:]):
            assert a.end_s <= b.start_s + 1e-9
        assert sum(k.dur_s for k in kids) <= p.dur_s + 1e-9
        assert 1 + len(list(_under(p, served["kids"]))) <= SPANS_PER_PASS
    names = {s.name for s in served["spans"]}
    assert PASS_PHASES | {"serving.idle_wait", "engine.release"} <= names
    releases = [s for s in served["spans"] if s.name == "engine.release"
                and s.parent is not None]
    assert len(releases) == len(REQUESTS)
    assert all(s.parent.name == "sched.route" for s in releases)


def test_idle_waits_are_not_passes(served):
    waits = [s for s in served["spans"] if s.name == "serving.idle_wait"]
    assert waits and all(s.parent is None for s in waits)
    assert all(id(s) not in served["kids"] for s in waits)
    # a pass did work: it admitted, ran a chunk or ran a step
    for p in _passes(served):
        admit = served["kids"][id(p)][0]
        assert (admit.attrs["admitted"] or p.attrs["chunks"]
                or p.attrs["steps"])


def test_one_request_tree_per_finished_request(served):
    roots = {s.attrs["request_id"]: s for s in served["spans"]
             if s.name == "request"}
    assert sorted(roots) == sorted(r.id for r in served["reqs"])
    for req, (n_prompt, steps) in zip(served["reqs"], REQUESTS):
        root, m = roots[req.id], req.metrics
        assert len(m["token_t"]) == len(req.tokens) == steps
        assert m["token_t"] == sorted(m["token_t"])
        assert m["first_token_t"] == m["token_t"][0]
        assert (m["enqueue_time"] <= m["admit_t"] <= m["first_chunk_t"]
                <= m["first_token_t"])
        assert root.attrs == {"request_id": req.id, "prompt_len": n_prompt,
                              "chunks": -(-n_prompt // 8), "tokens": steps,
                              "slot": m["slot"]}
        phases = sorted(served["kids"][id(root)], key=lambda s: s.start_s)
        assert [s.name for s in phases] == REQUEST_PHASES
        marks = [m["enqueue_time"], m["admit_t"], m["first_chunk_t"],
                 m["token_t"][0], m["token_t"][-1]]
        for s, t0, t1 in zip(phases, marks, marks[1:]):
            assert s.start_s == t0 and s.end_s == pytest.approx(t1, abs=1e-9)
        assert "device_time_s" not in m


def test_the_second_prompt_waits_in_the_lane_for_the_firsts_chunks(served):
    lanes = {s.attrs["request_id"]: s.dur_s for s in served["spans"]
             if s.name == "request.lane"}
    first, second, third = (r.id for r in served["reqs"])
    chunks = sorted((s for s in served["spans"]
                     if s.name == "engine.chunk.dispatch"),
                    key=lambda s: s.start_s)
    # oldest first, one chunk a pass: 3 + 1 + 2 chunks in that order
    assert [c.attrs["start"] for c in chunks] == [0, 8, 16, 0, 0, 8]
    assert lanes[first] < lanes[second] < lanes[third]
    assert lanes[second] >= chunks[2].end_s - chunks[0].start_s


# -- one step ahead (ISSUE 35) ---------------------------------------------------------

def test_a_step_is_dispatched_before_the_one_before_comes_home(served):
    """In every pass the next step's ``dispatch`` ends before the ``pull``
    starts, and that pull is the wait for the step dispatched a pass
    earlier: it carries that step's ``live``."""
    in_flight, first_dispatches, no_token = None, 0, 0
    for p in _passes(served):
        kids = {k.name: k for k in served["kids"].get(id(p), ())}
        pull = kids.get("engine.step.pull")
        if pull is None:
            continue
        assert p.attrs["steps"] == 1
        assert pull.attrs["live"] == (in_flight.attrs["live"]
                                      if in_flight is not None else 0)
        no_token += pull.attrs["no_token"]
        dispatch = kids.get("engine.step.dispatch")
        if dispatch is not None:
            assert kids["engine.step.prepare"].end_s <= dispatch.start_s
            assert dispatch.end_s <= pull.start_s + 1e-9
            assert dispatch.attrs["ahead"] == int(in_flight is not None)
            first_dispatches += in_flight is None
        in_flight = dispatch
    snap = served["snap"]
    dispatched = sum(1 for s in served["spans"]
                     if s.name == "engine.step.dispatch")
    # ahead on all but the first dispatch after nothing was in flight
    assert snap["steps_ahead"] == dispatched - first_dispatches > 0
    assert snap["steps_collected_early"] == snap["surplus_steps"] == 0
    # a request has no token in the answer of the pass in which it joined
    assert no_token == len(REQUESTS)
    # every token of a step but each request's first: budgets end on time
    assert sum(s.attrs["live"] for s in served["spans"]
               if s.name == "engine.step.dispatch") == sum(
        steps - 1 for _, steps in REQUESTS)


# -- the counters at the same boundaries -------------------------------------------

def test_the_counters_agree_with_the_spans(served):
    snap, passes = served["snap"], _passes(served)

    def holding(name):
        return sum(1 for p in passes if any(
            s.name == name for s in _under(p, served["kids"])))

    both = sum(1 for p in passes if {"engine.chunk.dispatch",
                                     "engine.step.dispatch"} <= {
        s.name for s in _under(p, served["kids"])})
    assert snap["passes"] == len(passes)
    assert snap["passes_with_chunk"] == holding("engine.chunk.dispatch") == 6
    # a pass with a step brings a step's tokens home; it dispatches the
    # next one unless every live slot waits for its last token
    assert snap["passes_with_step"] == holding("engine.step.pull")
    assert snap["passes_with_step"] > holding("engine.step.dispatch") > 0
    assert snap["passes_with_step"] == snap["decode_steps"]
    assert snap["passes_with_both"] == both
    assert snap["prefill_chunks"] == 6
    assert [bool(p.attrs["chunks"]) for p in passes] == [
        any(s.name == "engine.chunk.dispatch"
            for s in _under(p, served["kids"])) for p in passes]
    assert sum(p.attrs["tokens"] for p in passes) == sum(
        s for _, s in REQUESTS)
    # the pass's host wall, split three ways, adds up to the passes
    under = [s for p in passes for s in _under(p, served["kids"])]
    host = sum(s.dur_s for s in under
               if s.name.endswith((".prepare", ".dispatch")))
    pull = sum(s.dur_s for s in under if s.name.endswith(".pull"))
    assert snap["host_engine_s"] == pytest.approx(host, rel=1e-6)
    assert snap["pull_wait_s"] == pytest.approx(pull, rel=1e-6)
    assert (snap["host_sched_s"] + snap["host_engine_s"]
            + snap["pull_wait_s"]) == pytest.approx(
        sum(p.dur_s for p in passes), rel=1e-6)
    assert snap["host_sched_s"] > 0


def test_the_counters_reach_the_metrics_plane():
    from nnstreamer_tpu.obs import metrics as obs_metrics

    sched = DecodeScheduler(_tiny_engine(), name="plane")
    try:
        sched.submit(_prompts()[1], steps=2).result(timeout=120)
        text = obs_metrics.render()
    finally:
        sched.close()
    for name in ("passes", "passes_with_step", "passes_with_chunk",
                 "passes_with_both", "prefill_chunks", "host_sched_seconds",
                 "host_engine_seconds", "pull_wait_seconds"):
        assert f'nns_serving_{name}_total{{scheduler="plane"}}' in text


# -- what is not written, and where --------------------------------------------------

def test_pass_spans_stay_out_of_the_flight_ring(served):
    assert len(served["spans"]) > 100
    assert [e for e in served["flight_events"] if e["kind"] == "span"] == []


def test_tracing_stays_off_and_its_spans_stay_gated(served):
    assert obs_ctx.TRACING is False
    assert {s.kind for s in served["spans"]} == {"program"}


def test_the_ring_stays_bounded():
    obs_ctx.reset()
    for _ in range(obs_ctx.MAX_FINISHED + 10):
        obs_ctx.span("filler").record(0.0, 1.0)
    assert len(obs_ctx.finished_spans()) == obs_ctx.MAX_FINISHED
    # a 48 s window kept full at the fastest full-batch pass (7.5 ms since
    # PR 35; eight spans a pass with its launches), and its requests
    assert obs_ctx.MAX_FINISHED >= 48 / 0.0075 * 8 + 5 * 500
    obs_ctx.reset()


def test_a_span_costs_microseconds_with_no_profiler_session():
    def batch(n=2000):
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_ctx.span("engine.step.prepare", live=3):
                pass
        return (time.perf_counter() - t0) / n

    batch(200)
    best = min(batch() for _ in range(5))
    obs_ctx.reset()
    # loose (shared runners): four times the budget catches a flight
    # event, an id string per span or a lock held across the body
    assert best < 4 * SPAN_BUDGET_S, f"{best * 1e6:.2f} us a span"


# -- the span source ------------------------------------------------------------------

def test_the_parent_is_the_open_span_unless_one_is_named():
    obs_ctx.reset()
    caller = obs_ctx.TraceContext("trace-of-the-caller", "s1")
    with obs_ctx.span("outer") as outer:
        with obs_ctx.span("inner", x=1) as inner:
            pass
        named = obs_ctx.span("request", parent=caller, request_id=7)
        named.record(1.0, 3.0)
        meta = obs_ctx.span("request", parent=caller.to_meta()).record(1.0, 2.0)
        old_style = obs_ctx.start_span("gated", parent=outer)
        old_style.end()  # the two sources share a tree
    with obs_ctx.span("next") as after:
        pass
    assert inner.parent is outer and after.parent is None
    assert (old_style.trace_id, old_style.parent_id) == (
        outer.trace_id, outer.span_id)
    assert outer.trace_id == inner.trace_id != after.trace_id
    assert (named.trace_id, named.parent_id) == ("trace-of-the-caller", "s1")
    assert meta.trace_id == "trace-of-the-caller"
    assert (named.start_s, named.dur_s, named.end_s) == (1.0, 2.0, 3.0)
    assert [s.name for s in obs_ctx.finished_spans()] == [
        "inner", "request", "request", "gated", "outer", "next"]
    exported = obs_ctx.export_spans()["spans"]
    assert exported[0]["parent_span_id"] == outer.span_id
    assert exported[1]["attrs"] == {"request_id": 7}
    events = obs_ctx.export_chrome_trace()["traceEvents"]
    assert events[0]["args"]["x"] == 1 and events[0]["cat"] == "program"
    obs_ctx.reset()


def test_a_span_that_raises_is_recorded_and_the_stack_unwinds():
    obs_ctx.reset()
    with pytest.raises(KeyError):
        with obs_ctx.span("outer"):
            with obs_ctx.span("failing"):
                raise KeyError("x")
    with obs_ctx.span("after") as after:
        pass
    by_name = {s.name: s for s in obs_ctx.finished_spans()}
    assert by_name["failing"].status == "error:KeyError"
    assert by_name["outer"].status == "error:KeyError"
    assert after.parent is None and after.status == "ok"
    obs_ctx.reset()


def test_a_request_tree_hangs_under_the_callers_trace():
    caller = obs_ctx.TraceContext("t-caller", "s-caller")
    obs_ctx.reset()
    sched = DecodeScheduler(_tiny_engine(), name="caller")
    try:
        sched.submit(_prompts()[1], steps=2, trace=caller).result(timeout=120)
    finally:
        sched.close()
    tree = [s for s in obs_ctx.finished_spans()
            if s.name.startswith("request")]
    assert len(tree) == 5
    assert {s.trace_id for s in tree} == {"t-caller"}
    assert tree[0].name == "request" and tree[0].parent_id == "s-caller"


def test_a_failed_request_gets_the_phases_it_reached():
    obs_ctx.reset()
    sched = DecodeScheduler(_tiny_engine(), name="cut", autostart=False)
    req = sched.submit(_prompts()[0], steps=4)
    sched.close()  # never admitted
    assert req.error is not None
    tree = [s for s in obs_ctx.finished_spans() if s.name.startswith("request")]
    assert [s.name for s in tree] == ["request"]
    assert tree[0].status == "error" and tree[0].attrs["tokens"] == 0


# -- a proxy between the scheduler and the engine -----------------------------------------

class _Proxy:
    """The benchmark's ``EngineProxy`` in outline: the scheduler's calls by
    these signatures, everything else through ``__getattr__``, a request
    found by the identity of its prompt array."""

    def __init__(self, engine, prompts):
        self._engine = engine
        self._by_prompt = {id(p): i for i, p in enumerate(prompts)}
        self.seen = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def admit_start(self, slot, tokens, steps):
        self.seen.append(self._by_prompt[id(tokens)])
        self._engine.admit_start(slot, tokens, steps)

    def prefill_tick(self):
        return self._engine.prefill_tick()

    def step(self):
        return self._engine.step()

    def release(self, slot):
        self._engine.release(slot)

    def preempt(self, slot):
        return self._engine.preempt(slot)

    def restore(self, slot, blob):
        self._engine.restore(slot, blob)


def test_the_engine_calls_work_through_a_delegating_proxy(served):
    prompts = _prompts()
    proxy = _Proxy(_tiny_engine(), prompts)
    obs_ctx.reset()
    sched = DecodeScheduler(proxy, name="proxied")
    try:
        reqs = [sched.submit(p, steps=s)
                for p, (_, s) in zip(prompts, REQUESTS)]
        got = [r.result(timeout=120)[0].tolist() for r in reqs]
    finally:
        sched.close()
    assert proxy.seen == [0, 1, 2]  # the same array objects came through
    assert got == [r.result()[0].tolist() for r in served["reqs"]]
    snap = sched.metrics_snapshot()
    # the engine's stamps and sums reach the scheduler through the proxy
    assert [r.metrics["chunks"] for r in reqs] == [3, 1, 2]
    assert snap["host_engine_s"] > 0 and snap["pull_wait_s"] > 0
    assert snap["prefill_chunks"] == 6


def test_the_benchmarks_stamps_give_the_programs_token_gap_to_within_one_in_n():
    """The benchmark's proxy stamps a token for every slot it holds live at
    each return of ``step()``. With a step in flight such a stamp falls a
    pass before the token it stands for and a request has one more of them
    than tokens; the gaps between stamps are still the pass period. The
    program's own stamps (``token_t``, written where a token is emitted)
    stay exact."""
    from benchmark.drivers.lm_serving import EngineProxy, _record

    prompts, budgets = _prompts(), (12, 9, 15)
    proxy = EngineProxy(_tiny_engine())
    records = [_record({"prompt": p, "steps": n})
               for p, n in zip(prompts, budgets)]
    sched = DecodeScheduler(proxy, name="stamped")
    try:
        for p, rec in zip(prompts, records):
            proxy.track(p, rec)
        reqs = [sched.submit(p, steps=n) for p, n in zip(prompts, budgets)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        sched.close()
    for req, rec, n in zip(reqs, records, budgets):
        own, seen = req.metrics["token_t"], rec["token_t"]
        assert len(own) == n and len(seen) == n + 1
        # its first token and its last come out in the passes the proxy
        # saw them in: the same span of time, over n gaps and not n - 1
        assert seen[0] == pytest.approx(own[0], abs=5e-3)
        assert seen[-1] == pytest.approx(own[-1], abs=5e-3)
        mean_own = (own[-1] - own[0]) / (n - 1)
        mean_seen = (seen[-1] - seen[0]) / n
        assert abs(mean_seen / mean_own - 1) <= 1 / n + 0.05


def test_a_preempted_request_keeps_its_first_stamps():
    # a pool too small for both streams: one is evicted to host and restored
    engine = _tiny_engine(pages=5, share_prefixes=False)
    obs_ctx.reset()
    sched = DecodeScheduler(engine, name="tight-spans")
    rng = np.random.default_rng(3)
    try:
        reqs = [sched.submit(rng.integers(0, 64, 12).astype(np.int32),
                             steps=14) for _ in range(2)]
        for r in reqs:
            r.result(timeout=120)
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
    assert snap["preempted"] >= 1 and snap["restored"] >= 1
    roots = [s for s in obs_ctx.finished_spans() if s.name == "request"]
    assert len(roots) == 2
    for r in reqs:
        assert len(r.metrics["token_t"]) == 14
        assert r.metrics["admit_t"] <= r.metrics["first_chunk_t"]


# -- the same spans on the profiler's clock -----------------------------------------------

def test_the_profilers_host_plane_holds_the_spans_nested_as_the_ring_says(
        tmp_path):
    import jax
    from jax.profiler import ProfileData, ProfileOptions

    engine = _tiny_engine()
    sched = DecodeScheduler(engine, name="profiled")
    try:
        sched.submit(_prompts()[1], steps=2).result(timeout=120)  # compiled
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        obs_ctx.reset()
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            sched.submit(_prompts()[2], steps=4).result(timeout=120)
        finally:
            sched.close()
            jax.profiler.stop_trace()
    finally:
        sched.close()
    ring = sorted((s for s in obs_ctx.finished_spans()
                   if not s.name.startswith("request")),
                  key=lambda s: s.start_s)
    loop_tid = next(s.tid for s in ring if s.name == "serving.pass")
    ring = [s for s in ring if s.tid == loop_tid]
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert found
    host = [p for p in ProfileData.from_file(found[-1]).planes
            if p.name == "/host:CPU"]
    assert host
    lines = [sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in ln.events
                     if e.name.startswith(obs_ctx.ANNOTATION_PREFIX)),
                    key=lambda e: e[1]) for ln in host[0].lines]
    events = max(lines, key=len)  # the scheduler's loop thread
    # the session may have started inside an idle wait: align on the first pass
    first = next(i for i, s in enumerate(ring) if s.name == "serving.pass")
    first_ev = next(i for i, e in enumerate(events)
                    if e[0] == "nns:serving.pass")
    ring, events = ring[first:], events[first_ev:]
    assert len(ring) > 20
    assert [e[0] for e in events] == ["nns:" + s.name for s in ring]
    at = {id(s): e for s, e in zip(ring, events)}
    for s in ring:
        if s.parent is not None and id(s.parent) in at:
            (_, c0, c1), (_, p0, p1) = at[id(s)], at[id(s.parent)]
            assert p0 <= c0 and c1 <= p1, (s, s.parent)
