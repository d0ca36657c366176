"""The per-layer readers that read the program's own spans (PR 24), over a
ring built by hand, and ``tools/idle_by_span.py`` on the recorded trace.

The ring is ``nnstreamer_tpu.obs.context``'s; the spans are written with
the program's span source at chosen times, so every expected number below
is arithmetic on this file's own table."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness, traffic, xplane  # noqa: E402
from benchmark.tools import idle_by_span  # noqa: E402

BENCH = harness.load_benchmark()
NEW = ("sched_self_ms_per_pass", "passes_with_chunk_share", "step_host_ms",
       "chunk_host_ms", "step_pull_wait_ms", "prefill_lane_wait_p50_ms",
       "host_serial_share")
MS = 1e-3

# one pass with a chunk and a step, 200 ms long, from t: (name, start, end)
# in ms from the pass's start; release lies under route
BOTH = (("sched.admit", 0, 1), ("engine.chunk.prepare", 1, 2),
        ("engine.chunk.dispatch", 2, 4), ("engine.chunk.pull", 4, 60),
        ("engine.step.prepare", 60, 61), ("engine.step.dispatch", 61, 64),
        ("engine.step.pull", 64, 190), ("sched.route", 190, 195))
# one with a step only, 150 ms long
STEP = (("sched.admit", 0, 1), ("engine.step.prepare", 1, 3),
        ("engine.step.dispatch", 3, 8), ("engine.step.pull", 8, 140),
        ("sched.route", 140, 148))


def _pass(ctx, t, dur_ms, phases, **attrs):
    root = ctx.span("serving.pass", **attrs).record(t, t + dur_ms * MS)
    for name, a, b in phases:
        s = ctx.span(name, parent=root).record(t + a * MS, t + b * MS)
        if name == "sched.route":
            ctx.span("engine.release", parent=s).record(
                t + (b - 2) * MS, t + (b - 1) * MS)
    return root


def _request(ctx, rid, enqueue, admit, first_chunk, first_token, last_token):
    root = ctx.span("request", request_id=rid).record(enqueue, last_token)
    marks = (enqueue, admit, first_chunk, first_token, last_token)
    for name, t0, t1 in zip(("request.queue", "request.lane",
                             "request.prefill", "request.decode"),
                            marks, marks[1:]):
        ctx.span(name, parent=root, request_id=rid).record(t0, t1)


@pytest.fixture
def ring():
    """Three passes inside the traced part [100, 110] of a window [90, 138],
    one before it; three requests with a first token in the window (lane
    waits of 30, 70 and 500 ms) and one whose first token came before."""
    from nnstreamer_tpu.obs import context as ctx

    ctx.reset()
    _pass(ctx, 99.9, 200, BOTH, steps=1, chunks=1)   # starts before 100
    _pass(ctx, 100.2, 200, BOTH, steps=1, chunks=1)
    _pass(ctx, 100.5, 150, STEP, steps=1, chunks=0)
    _pass(ctx, 100.7, 150, STEP, steps=1, chunks=0)
    ctx.span("serving.idle_wait").record(100.85, 100.9)
    _request(ctx, 1, 91.0, 91.2, 91.23, 92.0, 95.0)
    _request(ctx, 2, 101.0, 101.1, 101.17, 103.0, 105.0)
    _request(ctx, 3, 120.0, 120.5, 121.0, 125.0, 130.0)
    _request(ctx, 4, 80.0, 80.1, 80.2, 89.0, 99.0)   # first token too early
    yield {"trace_bounds": (100.0, 110.0), "window_s": 48.0,
           "decode_steps": [(90.5, 1, 10, 1), (100.0, 1, 10, 1),
                            (137.5, 1, 10, 1)],
           "config": {}, "mix": {}, "end_to_end": {}, "trace": None,
           "peaks": None}
    ctx.reset()


# by hand from the tables: a BOTH pass spends 200 - (1+2+56) - (1+3+126) = 11
# ms outside its engine calls, a STEP pass 150 - (2+5+132) = 11 ms
EXPECTED = {
    "sched_self_ms_per_pass": 11.0,
    "passes_with_chunk_share": 100.0 / 3,
    "step_host_ms": (4 + 7 + 7) / 3,
    "chunk_host_ms": 3.0,
    "step_pull_wait_ms": (126 + 132 + 132) / 3,
    "prefill_lane_wait_p50_ms": 70.0,
    # pass self 3 x 11, chunk host 3, step host 4 + 7 + 7, of 10 s
    "host_serial_share": 100.0 * (33 + 3 + 18) * MS / 10.0,
}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_of_program_spans_reads_the_hand_built_ring(ring, name):
    value = harness.reader_for(name)(dict(ring, metric={"name": name}))
    assert value == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_of_program_spans_returns_nothing_with_nothing_to_read(
        ring, name):
    from nnstreamer_tpu.obs import context as ctx

    read = harness.reader_for(name)
    bare = {k: v for k, v in ring.items()
            if k not in ("trace_bounds", "decode_steps")}
    assert read(dict(bare, metric={"name": name})) is None
    # bounds that hold no pass, steps that hold no first token
    assert read(dict(ring, trace_bounds=(200.0, 210.0),
                     decode_steps=[(200.0, 1, 1, 1), (210.0, 1, 1, 1)],
                     metric={"name": name})) is None
    ctx.reset()  # a program without the spans: the parent commit
    assert read(dict(ring, metric={"name": name})) is None


STARTUP = {"setup_engine_build_s": 3.5 - 1.0, "setup_trace_lower_s": 3.25,
           "setup_cache_load_s": 4.0, "setup_fresh_compile_s": 1.5,
           "setup_fresh_compiles": 9}


def test_the_startup_readers_split_set_up_at_the_windows_opening(monkeypatch):
    """The five readers over the program's account of its start-up
    (``lib/startup.py``): what began before the window opened, the build's
    spans less jax's seconds charged to them."""
    from types import SimpleNamespace as Span

    from nnstreamer_tpu.obs import context as ctx

    spans = [Span(name="setup.params", start_s=10.0, dur_s=2.0,
                  attrs={"trace_s": 0.3, "compile_s": 0.2}),
             Span(name="setup.engine", start_s=12.0, dur_s=1.5,
                  attrs={"lower_s": 0.5}),
             Span(name="program.first_call", start_s=14.0, dur_s=4.0,
                  attrs={"trace_s": 1.0}),           # no part of the build
             Span(name="setup.engine", start_s=70.0, dur_s=9.0, attrs={})]
    asked = []

    def account(since=None, until=None):
        asked.append((since, until))
        return {"totals": {"trace_own_s": 2.0, "lower_own_s": 1.25,
                           "load_s": 4.0, "fresh_s": 1.5, "fresh": 9}}

    monkeypatch.setattr(ctx, "startup_spans", lambda: spans)
    monkeypatch.setattr(ctx, "compile_account", account)
    # the traced part began 12 s into a window of 48: it opened at 50
    facts = {"trace_bounds": (62.0, 72.0), "window_s": 48.0,
             "mix": {"trace": {"start_s": 12.0, "seconds": 10.0}}}
    for name, value in STARTUP.items():
        assert harness.reader_for(name)(facts) == pytest.approx(value)
    assert set(asked) == {(None, 50.0)}
    assert {m["name"] for m in BENCH["per_layer"]} >= set(STARTUP)
    # a window shorter than the mix's offset is traced from where run.py
    # starts it: 2 s less the traced 10 is the window's own start
    del asked[:]
    harness.reader_for("setup_cache_load_s")(dict(facts, window_s=2.0))
    assert asked == [(None, 62.0)]
    # nothing to read: no traced part, no span of the build, no account
    read = harness.reader_for("setup_engine_build_s")
    assert read(dict(facts, trace_bounds=None)) is None
    monkeypatch.setattr(ctx, "startup_spans", lambda: spans[2:])
    assert read(facts) is None
    monkeypatch.delattr(ctx, "compile_account")
    assert read(facts) is None


def test_the_new_entries_follow_the_suffix_rule():
    """``harness.py``'s naming rule, for every entry: one entry a quantity
    (a reader file) and moved metric; ``x`` where the quantity has one
    entry and no end-to-end metric is called ``x``, else ``x.<moved>`` with
    the moved metric's name up to its first underscore."""
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [c["name"] for c in BENCH["workloads"]]
    by_quantity = {}
    for m in BENCH["per_layer"]:
        by_quantity.setdefault(harness.reader_file(m["name"]), []).append(m)
    for quantity, entries in by_quantity.items():
        moves = [m["moves"] for m in entries]
        assert len(set(moves)) == len(moves), quantity  # no copy for a cell
        split = len(entries) > 1 or quantity in e2e
        for m in entries:
            suffix = "." + m["moves"].split("_")[0] if split else ""
            assert m["name"] == quantity + suffix
            # read only where the metric it moves is reported
            assert set(m.get("workloads", ())) <= set(
                e2e[m["moves"]].get("workloads", cells))
        for key in ("unit", "better", "source", "layer"):
            assert len({m[key] for m in entries}) == 1, (quantity, key)
    # PR 24's seven: in every serving cell or, for what reads a launch or
    # a first token, in the cells whose traced part holds one
    assert set(NEW) <= set(by_quantity)
    layers = {m["layer"] for q, ms in by_quantity.items() if q not in NEW
              for m in ms}
    for quantity in NEW:
        entries = {m["name"]: m for m in by_quantity[quantity]}
        assert set(entries) == {quantity + ".tpot", quantity + ".ttft"}
        for name, m in entries.items():
            assert m["layer"] in layers and m["better"] == "lower"
            assert m["moves"] == name.rsplit(".", 1)[1] + "_p50_ms"
            assert m["source"] == ("program_counter" if quantity
                                   == "passes_with_chunk_share"
                                   else "program_span")
            read_in = [c for c in cells if m in harness.metrics_of(
                BENCH, "per_layer", c)]
            judged = e2e[m["moves"]]["workloads"]
            if quantity in ("chunk_host_ms", "prefill_lane_wait_p50_ms"):
                assert m["workloads"] == read_in and set(read_in) <= set(
                    judged)
            else:
                assert "workloads" not in m and read_in == judged


@pytest.mark.parametrize("workload", [c["name"] for c in BENCH["workloads"]])
def test_a_traced_rehearsal_prints_every_new_metric_of_the_cell(
        workload, tmp_path):
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 24), "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    listed = harness.metrics_of(BENCH, "per_layer", workload)
    assert set(line["metrics"]) <= {m["name"] for m in listed}
    mine = [m for m in listed if harness.reader_file(m["name"]) in NEW]
    # the five that every pass has; a launch's and a first token's where
    # the cell lists them
    assert 5 <= len(mine) <= 7
    # whether the traced part held a pass at all, by a counter that
    # another reader takes from the same passes: a closed loop of a few
    # rounds (mellum's rehearsal: six requests) has ended by the time the
    # profiler has started on a CPU, and then a reader of passes returns
    # nothing, never 0
    (cell,) = [c for c in BENCH["workloads"] if c["name"] == workload]
    mix = traffic.load(cell["traffic"])
    held_a_pass = bool({"attn_pages_read_share", "prefill_fill_share.ttft"}
                       & set(line["metrics"]))
    assert held_a_pass or {**mix, **mix["rehearsal"]}.get("rounds", 99) < 10
    for m in mine:
        if not held_a_pass:
            assert m["name"] not in line["metrics"]
            continue
        got = line["metrics"][m["name"]]  # none left out
        if m["source"] == "program_counter":
            assert 0.0 <= got["value"] <= 100.0
        else:
            assert got["value"] is None  # a CPU's time is not a device's


# -- tools/idle_by_span.py ----------------------------------------------------------

SMALL = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")


def test_idle_by_span_with_the_proxys_prefix_is_the_runs_reduction():
    reduced = xplane.reduce_trace(SMALL)
    mine = idle_by_span.idle_by_span(SMALL, xplane.SPAN_PREFIX)
    assert mine["window_s"] == pytest.approx(reduced["window_s"])
    assert mine["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    assert list(mine["idle"].items()) == [
        (k, pytest.approx(v)) for k, v in reduced["idle_gaps"]]
    assert sum(mine["idle"].values()) == pytest.approx(mine["idle_s"])
    # what record_testdata.py ran: four of each span, a sleep of 20 ms
    assert {k: v[0] for k, v in mine["spans"].items()} == {
        "matmul": 4, "sleep": 4, "copy": 4}
    assert mine["spans"]["sleep"][1] == pytest.approx(20.0, rel=0.1)
    # the three spans of each round follow one another; the device's last
    # operations end after the last span does
    assert 0.98 < mine["covered"] < mine["covered_inside"]
    assert 0.999 < mine["covered_inside"] <= 1.0


def test_idle_by_span_with_a_prefix_the_trace_lacks_charges_no_span(capsys):
    mine = idle_by_span.idle_by_span(SMALL, "nns:")
    assert list(mine["idle"]) == ["unattributed"] and mine["spans"] == {}
    assert mine["covered"] == mine["covered_inside"] == 0.0
    assert idle_by_span.main([SMALL, "--prefix", "bench:"]) == 0
    out = capsys.readouterr().out
    assert "sleep" in out and "unattributed" in out and "prefix bench:" in out
