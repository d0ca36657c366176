"""``prefill_ctx_read_share`` (PR 42) over a ring built by hand: the engine
writes ``ctx_read`` and ``ctx_padded`` on each ``engine.chunk.prepare`` span,
and the reader sums both over the chunks of the traced passes. Every
expected number is arithmetic on this file's own table."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_benchmark_units as units  # noqa: E402

from benchmark.lib import harness  # noqa: E402

BENCH = harness.load_benchmark()
NAME = "prefill_ctx_read_share"
LAYER = "engine + pool (serving/lm_engine.py, kv_pool.py)"
MS = 1e-3
PADDED = 24 * 2048
# (pass start, positions a layer of its launch read; None: a pass with a
# step alone). A pass is 20 ms; the first starts before the traced part and
# the last ends after it
PASSES = ((99.99, 256), (100.2, 256), (100.5, None), (100.7, 1024),
          (109.99, 2048))


@pytest.fixture
def ring(request):
    from nnstreamer_tpu.obs import context as ctx

    ctx.reset()
    for t, read in PASSES:
        chunks = int(read is not None)
        root = ctx.span("serving.pass", steps=1, chunks=chunks).record(
            t, t + 20 * MS)
        if chunks:
            ctx.span("engine.chunk.prepare", parent=root, slot=0, start=0,
                     n_valid=256, width=256,
                     **request.param(24 * read)).record(t, t + 1 * MS)
            ctx.span("engine.chunk.dispatch", parent=root).record(
                t + 1 * MS, t + 2 * MS)
        ctx.span("engine.step.prepare", parent=root, live=1).record(
            t + 2 * MS, t + 3 * MS)
    yield {"trace_bounds": (100.0, 110.0), "window_s": 48.0, "config": {},
           "mix": {}, "end_to_end": {}, "trace": None, "peaks": None,
           "metric": {"name": NAME}}
    ctx.reset()


def _walked(read):
    return {"ctx_read": read, "ctx_padded": PADDED}


def _gathered(read):
    return {}    # the parent's launches count neither


@pytest.mark.parametrize("ring", [_walked], indirect=True)
def test_the_share_is_summed_over_the_traced_passes_chunks(ring):
    # two launches count: a prompt's first and one four blocks in
    value = harness.reader_for(NAME)(ring)
    assert value == pytest.approx(100.0 * (256 + 1024) / (2 * 2048),
                                  rel=1e-12)
    assert value == 31.25


@pytest.mark.parametrize("ring", [_gathered], indirect=True)
def test_launches_that_count_nothing_leave_the_metric_out(ring):
    # a program without the walk: nothing, and no error
    assert harness.reader_for(NAME)(ring) is None


@pytest.mark.parametrize("ring", [_walked], indirect=True)
def test_no_traced_part_is_nothing_to_read(ring):
    bare = {k: v for k, v in ring.items() if k != "trace_bounds"}
    assert harness.reader_for(NAME)(bare) is None


@pytest.mark.parametrize("name, moves, cell", [
    (NAME + ".ttft", "ttft_p50_ms", "opt1b3_longprompt"),
    (NAME + ".tpot", "tpot_p50_ms", "opt1b3_saturated"),
    (NAME + ".tpot", "tpot_p50_ms", "opt1b3_chat"),
    (NAME + ".tpot", "tpot_p50_ms", "kanana2_decode_saturated"),
    (NAME + ".tpot", "tpot_p50_ms", "jamba2_reasoning_saturated"),
])
def test_each_entry_moves_what_its_cell_is_judged_by(name, moves, cell):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    entry = dict(entry)
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": LAYER,
                     "moves": moves}
    # the cells whose traced part holds a launch: ``prefill_fill_share``'s
    (fill,) = [m for m in BENCH["per_layer"]
               if m["name"] == "prefill_fill_share." + name.rsplit(".")[-1]]
    assert listed == fill["workloads"] and cell in listed
    assert name in [m["name"] for m in harness.metrics_of(
        BENCH, "per_layer", cell)]
    assert harness.reader_for(name).__module__.endswith(NAME)


# what a cell prints besides since ``test_benchmark_units.BEFORE`` was taken:
# the entry and the cells that read it
SINCE = {
    NAME + ".ttft": ["opt1b3_longprompt"],
    NAME + ".tpot": ["opt1b3_chat", "opt1b3_saturated",
                     "kanana2_decode_saturated", "jamba2_reasoning_saturated"],
}


@pytest.mark.parametrize("name", sorted(SINCE))
def test_the_quantity_is_read_in_its_cells_and_no_other(name):
    assert units._cells_reading(BENCH, name) == SINCE[name]
    assert callable(harness.reader_for(name))
    # no cell lost a quantity to it, and none gained another
    for cell in units.BEFORE:
        now = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell)}
        kept = {units.OLD_TO_NEW.get(old, old) for old in units.BEFORE[cell]}
        assert now - kept - units.STARTUP == {n for n, cs in SINCE.items()
                                              if cell in cs}


def test_the_engines_own_launches_are_what_the_reader_reads():
    """A real engine's spans through the reader: three launches of a
    53-token prompt over blocks of 16 positions."""
    import numpy as np

    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from nnstreamer_tpu.obs import context as ctx
    from nnstreamer_tpu.ops import paged_attention
    from nnstreamer_tpu.serving.lm_engine import PagedLMEngine

    cfg = TransformerConfig(vocab=61, dim=32, heads=4, layers=2, mlp_mult=2,
                            max_seq=128)
    old = paged_attention.SCORE_BYTES
    paged_attention.SCORE_BYTES = 4 * 16 * 4 * 24   # two pages of eight
    ctx.reset()
    try:
        eng = PagedLMEngine(cfg, init_params(cfg, seed=3), slots=1,
                            page_size=8, chunk=24)
        with ctx.span("serving.pass", steps=0, chunks=3):
            eng.admit_start(0, np.arange(53, dtype=np.int32), 1)
            while not eng.prefill_tick():
                pass
        eng.close()
        spans = ctx.finished_spans()
        lo = min(s.start_s for s in spans)
        hi = max(s.start_s + s.dur_s for s in spans)
        value = harness.reader_for(NAME)({"trace_bounds": (lo - 1, hi + 1)})
    finally:
        paged_attention.SCORE_BYTES = old
        ctx.reset()
    # 32, 48 and 64 positions a layer of the 128 a gathered copy had
    assert value == pytest.approx(100.0 * (32 + 48 + 64) / (3 * 128))
