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

from benchmark.lib import harness, xplane  # noqa: E402
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


def test_the_new_entries_follow_the_suffix_rule():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    mine = {n: m for n, m in entries.items() if n.split(".")[0] in NEW}
    assert len(mine) == 15
    layers = {m["layer"] for n, m in entries.items() if n not in mine}
    for name, m in mine.items():
        assert m["layer"] in layers and m["better"] == "lower"
        suffix = name.partition(".")[2]
        lane = name.startswith("prefill_lane_wait")
        cells = {"": ["opt1b3_longprompt"] if lane
                 else ["opt1b3_chat", "opt1b3_saturated"],
                 "long": ["opt1b3_longprompt"], "chat": ["opt1b3_chat"],
                 "sat": ["opt1b3_saturated"]}[suffix]
        assert m["workloads"] == cells
        assert m["moves"] == ("ttft_p50_ms" if cells == ["opt1b3_longprompt"]
                              else "tpot_p50_ms")
        assert m["source"] == ("program_counter" if name.startswith(
            "passes_with_chunk_share") else "program_span")


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
    mine = [m for m in harness.metrics_of(BENCH, "per_layer", workload)
            if m["name"].split(".")[0] in NEW]
    assert len(mine) == 7
    for m in mine:
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
