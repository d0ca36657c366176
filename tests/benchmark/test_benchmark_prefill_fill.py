"""``prefill_fill_share`` (PR 30) over a ring built by hand: the engine
writes ``n_valid`` and ``width`` on each ``engine.chunk.prepare`` span, and
the reader sums both over the chunks of the traced passes. Every expected
number is arithmetic on this file's own table."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402

BENCH = harness.load_benchmark()
NAME = "prefill_fill_share"
LAYER = "engine + pool (serving/lm_engine.py, kv_pool.py)"
MS = 1e-3
WIDTH = 256
# (pass start, tokens its chunk ingested; None: a pass with a step alone).
# A pass is 20 ms; the first starts before the traced part and the last
# ends after it
PASSES = ((99.99, 256), (100.2, 256), (100.5, None), (100.7, 44),
          (109.99, 200))


@pytest.fixture
def ring(request):
    from nnstreamer_tpu.obs import context as ctx

    ctx.reset()
    for t, n_valid in PASSES:
        chunks = int(n_valid is not None)
        root = ctx.span("serving.pass", steps=1, chunks=chunks).record(
            t, t + 20 * MS)
        if chunks:
            ctx.span("engine.chunk.prepare", parent=root, slot=0, start=0,
                     **request.param(n_valid)).record(t, t + 1 * MS)
            ctx.span("engine.chunk.dispatch", parent=root).record(
                t + 1 * MS, t + 2 * MS)
        ctx.span("engine.step.prepare", parent=root, live=1).record(
            t + 2 * MS, t + 3 * MS)
    yield {"trace_bounds": (100.0, 110.0), "window_s": 48.0, "config": {},
           "mix": {}, "end_to_end": {}, "trace": None, "peaks": None,
           "metric": {"name": NAME}}
    ctx.reset()


def _wide(n_valid):
    return {"n_valid": n_valid, "width": WIDTH}


def _unmarked(n_valid):
    return {"n_valid": n_valid}


def _no_chunk(n_valid):
    return {}


@pytest.mark.parametrize("ring", [_wide], indirect=True)
def test_the_share_is_summed_over_the_traced_passes_chunks(ring):
    # two chunks count: one full, one of a prompt's last 44 tokens
    value = harness.reader_for(NAME)(ring)
    assert value == pytest.approx(100.0 * (256 + 44) / (2 * WIDTH),
                                  rel=1e-12)
    assert round(value, 1) == 58.6


@pytest.mark.parametrize("ring", [_unmarked, _no_chunk], indirect=True)
def test_chunks_without_a_width_leave_the_metric_out(ring):
    # the parent's chunks carry ``n_valid`` alone: nothing, and no error
    assert harness.reader_for(NAME)(ring) is None


@pytest.mark.parametrize("ring", [_wide], indirect=True)
def test_no_traced_part_is_nothing_to_read(ring):
    bare = {k: v for k, v in ring.items() if k != "trace_bounds"}
    assert harness.reader_for(NAME)(bare) is None


def test_a_traced_part_without_a_chunk_is_nothing_to_read():
    from nnstreamer_tpu.obs import context as ctx

    ctx.reset()
    try:
        root = ctx.span("serving.pass", steps=1, chunks=0).record(
            100.2, 100.22)
        ctx.span("engine.step.prepare", parent=root, live=1).record(
            100.2, 100.201)
        assert harness.reader_for(NAME)(
            {"trace_bounds": (100.0, 110.0)}) is None
    finally:
        ctx.reset()


@pytest.mark.parametrize("name, moves, cell", [
    (NAME + ".ttft", "ttft_p50_ms", "opt1b3_longprompt"),
    (NAME + ".tpot", "tpot_p50_ms", "opt1b3_saturated"),
    (NAME + ".tpot", "tpot_p50_ms", "opt1b3_chat"),
    (NAME + ".tpot", "tpot_p50_ms", "kanana2_decode_saturated"),
])
def test_each_entry_moves_what_its_cell_is_judged_by(name, moves, cell):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    entry = dict(entry)
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": LAYER,
                     "moves": moves}
    judged = {m["name"]: m for m in BENCH["end_to_end"]}[moves]
    assert cell in listed and set(listed) <= set(judged["workloads"])
    assert name in [m["name"] for m in harness.metrics_of(
        BENCH, "per_layer", cell)]
    # one reader file serves both names
    assert harness.reader_for(name).__module__.endswith("prefill_fill_share")
