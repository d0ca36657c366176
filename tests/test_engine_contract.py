"""One decode engine behind one written contract (ISSUE 29).

``serving.DecodeEngine`` is what an engine owes the ``DecodeScheduler`` that
drives it. Every engine kind the tree has, and the dozen-line fake of
``test_serving.py``, is served through the same loop here; the scheduler asks its engine nothing by
name; ``make_continuous`` builds one kind.
"""
import ast

import numpy as np
import pytest
from test_kv_paged import leakcheck  # noqa: F401 - the ledger, armed a test
from test_serving import ToyEngine

from nnstreamer_tpu.serving import (
    DecodeEngine,
    DecodeScheduler,
    PagedLMEngine,
    SpeculativeLMEngine,
)
from nnstreamer_tpu.serving import scheduler as scheduler_module

# (prompt length, steps): more requests than any engine below has slots
REQUESTS = ((11, 6), (3, 4), (19, 9), (7, 5))


def _paged_gpt():
    from nnstreamer_tpu.models.lm_serving import tiny

    return tiny.make_continuous(slots=2, page_size=8, chunk=8, pages=16)


def _paged_latent():
    from test_deepseek_v3_serving import SIZES

    from nnstreamer_tpu.models.deepseek_v3 import DeepseekV3Config
    from nnstreamer_tpu.models.lm_serving import _LMServingEntry

    entry = _LMServingEntry(DeepseekV3Config.from_published(SIZES))
    return entry.make_continuous(slots=2, page_size=4, chunk=8, pages=32)


def _speculative():
    from nnstreamer_tpu.models.lm_serving import tiny

    return tiny.make_continuous(slots=2, draft="ngram", spec_k=3,
                                page_size=8, chunk=8, pages=16)


REAL = {"paged_gpt": _paged_gpt, "paged_latent": _paged_latent,
        "speculative": _speculative}
ENGINES = {**REAL, "toy": ToyEngine}


@pytest.mark.parametrize("kind", ENGINES)
def test_scheduler_serves_and_frees(kind, leakcheck):  # noqa: F811
    leakcheck.reset_leakcheck()
    engine = ENGINES[kind]()
    assert isinstance(engine, DecodeEngine)
    rng = np.random.default_rng(29)
    sched = DecodeScheduler(engine, name=f"contract-{kind}")
    try:
        reqs = [sched.submit(rng.integers(1, 60, n).astype(np.int32), steps=s)
                for n, s in REQUESTS]
        for r, (_, s) in zip(reqs, REQUESTS):
            assert len(r.result(timeout=120)[0]) == s
        snap = sched.metrics_snapshot()
        assert snap["completed"] == len(REQUESTS)
        assert snap["active_slots"] == 0
        assert sorted(sched._free) == list(range(engine.slots))
        # a burst engine's rounds are counted, and only a burst engine's
        assert ("spec_rounds" in snap) == (engine.step_tokens is not None)
    finally:
        sched.close()
    if engine.pool is not None:
        assert engine.active_slots == 0
        assert engine.pool.used_pages == 0
        assert all(engine.pool.refcount(p) == 0
                   for p in range(1, snap["kv_pool"]["pages_total"] + 1))
    assert leakcheck.outstanding("kv_page") == []


@pytest.mark.parametrize("kind", REAL)
def test_validate_rejects_overlong(kind):
    engine = REAL[kind]()
    sched = DecodeScheduler(engine, name=f"overlong-{kind}", autostart=False)
    try:
        engine.validate(np.zeros(54, np.int32), steps=10)  # 64 positions
        with pytest.raises(ValueError, match="max_seq"):
            engine.validate(np.zeros(60, np.int32), steps=10)
        with pytest.raises(ValueError, match="max_seq"):
            sched.submit(np.zeros(60, np.int32), steps=10)  # before queueing
        assert sched.queue.depth() == 0
    finally:
        sched.close()


def test_scheduler_asks_its_engine_nothing_by_name():
    tree = ast.parse(open(scheduler_module.__file__).read())
    asked = [f"line {node.lineno}: {ast.unparse(node)}"
             for node in ast.walk(tree)
             if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Name)
             and node.func.id in ("getattr", "hasattr") and node.args
             and ast.unparse(node.args[0]) in ("engine", "self.engine")]
    assert not asked, asked


def test_make_continuous_has_one_engine():
    from nnstreamer_tpu.models.lm_serving import tiny

    with pytest.raises(ValueError, match="dense slot engine"):
        tiny.make_continuous(slots=2, paged=False)
    plain = tiny.make_continuous(slots=2, paged=True)  # accepted, inert
    assert type(plain) is PagedLMEngine and isinstance(plain, DecodeEngine)
    burst = tiny.make_continuous(slots=2, draft="ngram")
    assert type(burst) is SpeculativeLMEngine
    assert isinstance(burst, DecodeEngine) and burst.step_tokens is not None
    assert plain.step_tokens is None
    for engine in (plain, burst):
        engine.close()
