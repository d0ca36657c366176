"""``attn_pages_read_share`` (PR 28) over a ring built by hand: the engine
writes ``pages_read`` and ``pages_padded`` on each ``engine.step.prepare``
span, and the reader sums both over the steps of the traced passes. Every
expected number is arithmetic on this file's own table."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402

BENCH = harness.load_benchmark()
NAME = "attn_pages_read_share"
MS = 1e-3
# (pass start, pages the live slots held) of 16 slots x 128 blocks; a pass
# is 20 ms, its step's prepare span the first of them
PADDED = 16 * 128
PASSES = ((99.99, 700), (100.2, 19), (100.5, 21), (100.7, 404), (109.99, 900))


@pytest.fixture
def ring(request):
    from nnstreamer_tpu.obs import context as ctx

    ctx.reset()
    for t, read in PASSES:
        root = ctx.span("serving.pass", steps=1, chunks=0).record(
            t, t + 20 * MS)
        attrs = request.param(read)
        ctx.span("engine.step.prepare", parent=root, live=1, **attrs).record(
            t, t + 1 * MS)
        ctx.span("engine.step.dispatch", parent=root).record(
            t + 1 * MS, t + 2 * MS)
    # a pass without a step counts nothing
    ctx.span("serving.pass", steps=0, chunks=1).record(101.0, 101.02)
    yield {"trace_bounds": (100.0, 110.0), "window_s": 48.0, "config": {},
           "mix": {}, "end_to_end": {}, "trace": None, "peaks": None,
           "metric": {"name": NAME}}
    ctx.reset()


def _counted(read):
    return {"pages_read": read, "pages_padded": PADDED}


def _uncounted(read):
    return {}


@pytest.mark.parametrize("ring", [_counted], indirect=True)
def test_the_share_is_summed_over_the_traced_passes_steps(ring):
    # the first pass starts before the traced part and the last ends after
    # it: three steps count
    value = harness.reader_for(NAME)(ring)
    assert value == pytest.approx(100.0 * (19 + 21 + 404) / (3 * PADDED),
                                  rel=1e-12)


@pytest.mark.parametrize("ring", [_uncounted], indirect=True)
def test_a_program_that_counts_no_pages_leaves_the_metric_out(ring):
    # the parent's steps carry no such attributes: nothing, and no error
    assert harness.reader_for(NAME)(ring) is None


@pytest.mark.parametrize("ring", [_counted], indirect=True)
def test_no_traced_part_is_nothing_to_read(ring):
    bare = {k: v for k, v in ring.items() if k != "trace_bounds"}
    assert harness.reader_for(NAME)(bare) is None


def test_the_entry_lists_the_cells_whose_steps_it_moves():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    entry = dict(entry)
    listed = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter",
        "layer": "engine + pool (serving/lm_engine.py, kv_pool.py)",
        "moves": "tpot_p50_ms"}
    # a kernel's counter: it keeps its list (a family whose step does not
    # count its pages has nothing to read), one entry for every cell in it
    judged = {m["name"]: m for m in BENCH["end_to_end"]}["tpot_p50_ms"]
    assert set(listed) <= set(judged["workloads"])
    assert {"opt1b3_chat", "opt1b3_saturated",
            "kanana2_decode_saturated"} <= set(listed)
    assert callable(harness.reader_for(NAME))
    assert not [m for m in BENCH["per_layer"]
                if m["name"].startswith(NAME + ".")]
