"""Paged KV-cache serving (serving/kv_pool.py + PagedLMEngine).

The properties the paged data plane exists for, each asserted directly:

* parity — the block-table gather/scatter programs are token-exact
  against batch-1 unbatched decode (``models.decoding.make_generate``),
  so paging is purely a memory-layout change;
* copy-on-write prefix sharing — a registered prefix is mapped, not
  recomputed, and a sharer's writes never corrupt the other stream;
* preemption — evict-to-host then restore is byte-exact (the request
  is paused, never dropped), both directly and through DecodeScheduler
  under a pool that cannot hold both streams;
* speculative decode — the draft/verify burst emits the target's own
  greedy stream for ANY acceptance pattern (all-reject, all-accept,
  alternating, real drafts), so speculation can change latency only;
* compile discipline — the chunk size is the only compiled prefill
  shape, so compile_count is flat across prompt lengths;
* pool in place — compiled for a described TPU v5e, the step (with its
  attention kernel in) and the prefill chunk take the donated pool in and
  hand it back in one layout: no copy and no slice of a pool's or a
  layer's size, both pools aliased input to output, and nothing of a
  gathered context's size left in the step; and on any backend a write routed to
  the null page, a COW copy and a preempt/restore touch only the rows
  they name, in every layer;
* page lifecycle — every scheduler exit path (retire, close with
  in-flight work, deadline shed, batch failure) releases through
  ``engine.release`` and page refcounts reach zero (the NNS_LEAKCHECK
  ledger asserts the same pairing at the acquire/release sites).
"""
import functools
import math
import re

import numpy as np
import pytest
from engine_util import step_now

from nnstreamer_tpu.analysis import sanitizer
from nnstreamer_tpu.ops import paged_attention
from nnstreamer_tpu.serving import (
    DecodeScheduler,
    PagedLMEngine,
    ServingError,
)


@pytest.fixture
def leakcheck():
    was = sanitizer.leakcheck_enabled()
    sanitizer.enable_leakcheck()
    yield sanitizer
    if was:
        # session-level NNS_LEAKCHECK run: re-arm with a clean ledger so
        # the autouse fixture's baseline stays truthful
        sanitizer.enable_leakcheck()
    else:
        sanitizer.disable_leakcheck()
        sanitizer.reset_leakcheck()


def _tiny():
    from nnstreamer_tpu.models.lm_serving import tiny
    from nnstreamer_tpu.models.transformer import init_params

    cfg = tiny.cfg
    return cfg, init_params(cfg, seed=0)


def _dense_baseline(cfg, params, prompt, steps):
    """Unbatched greedy decode via models/decoding — the stream every
    paged/speculative configuration must reproduce token-exact."""
    from nnstreamer_tpu.models.decoding import make_generate

    gen = make_generate(cfg)
    out = np.asarray(gen(params, np.asarray(prompt)[None, :], steps))
    return out[0, len(prompt):].tolist()


def _decode(engine, slot, prompt, steps):
    """Drive one slot of a paged engine directly: admit, step to
    completion, release. Steps the whole batch (other active slots
    advance too — callers collect their own streams)."""
    out = [engine.admit(slot, np.asarray(prompt, np.int32), steps)]
    while len(out) < steps:
        out.append(int(step_now(engine)[slot]))
    engine.release(slot)
    return out


# ---------------------------------------------------------------------------
# parity — paging is a memory-layout change, not a numerics change
# ---------------------------------------------------------------------------
class TestPagedParity:
    def test_paged_matches_dense_token_exact(self):
        cfg, params = _tiny()
        rng = np.random.default_rng(7)
        p1 = rng.integers(0, cfg.vocab, 11).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab, 5).astype(np.int32)
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=16,
                            chunk=16, share_prefixes=False)
        sched = DecodeScheduler(eng, name="parity")
        try:
            r1 = sched.submit(p1, steps=9)
            r2 = sched.submit(p2, steps=4)
            got1 = np.asarray(r1.result(120)[0]).tolist()
            got2 = np.asarray(r2.result(120)[0]).tolist()
        finally:
            sched.close()
        assert got1 == _dense_baseline(cfg, params, p1, 9)
        assert got2 == _dense_baseline(cfg, params, p2, 4)
        assert eng.pool.used_pages == 0

    def test_slot_churn_does_not_perturb_streams(self):
        # sequences join/retire mid-flight; block-table reuse across
        # admissions must not leak state between tenants of a slot
        cfg, params = _tiny()
        rng = np.random.default_rng(11)
        eng = PagedLMEngine(cfg, params, slots=1, page_size=8, pages=8,
                            chunk=16, share_prefixes=False)
        for n in (3, 17, 9):
            p = rng.integers(0, cfg.vocab, n).astype(np.int32)
            assert _decode(eng, 0, p, 6) == \
                _dense_baseline(cfg, params, p, 6)

    def test_compile_count_flat_across_prompt_lengths(self):
        # the chunk size is the ONLY compiled prefill shape: arbitrary
        # prompt lengths reuse the same executables (a prefill over the
        # whole prompt compiles once per distinct length: the NNL008 churn)
        cfg, params = _tiny()
        rng = np.random.default_rng(13)
        eng = PagedLMEngine(cfg, params, slots=1, page_size=8, pages=8,
                            chunk=16, share_prefixes=False)
        p = rng.integers(0, cfg.vocab, 4).astype(np.int32)
        _decode(eng, 0, p, 3)
        frozen = eng.compile_count
        for n in (1, 7, 16, 23, 40):
            p = rng.integers(0, cfg.vocab, n).astype(np.int32)
            _decode(eng, 0, p, 3)
        assert eng.compile_count == frozen, \
            "prompt length must not be a compiled shape"


# ---------------------------------------------------------------------------
# copy-on-write prefix sharing
# ---------------------------------------------------------------------------
class TestPrefixSharing:
    def test_shared_prefix_hits_and_streams_stay_isolated(self):
        cfg, params = _tiny()
        rng = np.random.default_rng(17)
        prefix = rng.integers(0, cfg.vocab, 16).astype(np.int32)  # 2 pages
        t1 = rng.integers(0, cfg.vocab, 4).astype(np.int32)
        t2 = rng.integers(0, cfg.vocab, 6).astype(np.int32)
        p1 = np.concatenate([prefix, t1])
        p2 = np.concatenate([prefix, t2])
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=16,
                            chunk=16, share_prefixes=True)
        # first tenant registers the prefix's full pages on prefill
        # completion; the second maps them instead of recomputing
        out1 = [eng.admit(0, p1, 8)]
        assert eng.pool.stats()["prefix_hits_total"] == 0
        out2 = [eng.admit(1, p2, 8)]
        assert eng.pool.stats()["prefix_hits_total"] >= 1
        assert eng.pool.shared_pages >= 2
        while len(out1) < 8:
            tok = step_now(eng)
            out1.append(int(tok[0]))
            out2.append(int(tok[1]))
        assert out1 == _dense_baseline(cfg, params, p1, 8)
        assert out2 == _dense_baseline(cfg, params, p2, 8)
        eng.release(0)
        eng.release(1)
        # registry still holds its refs; closing drops them
        eng.close()
        assert eng.pool.used_pages == 0

    def test_sharer_writes_never_corrupt_the_registered_pages(self):
        # page-aligned prompt: the LAST prompt page is registered and
        # shared, and the sharer's first decode write lands exactly one
        # position past it — COW must keep the registered page immutable
        cfg, params = _tiny()
        rng = np.random.default_rng(19)
        prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=16,
                            chunk=16, share_prefixes=True)
        base = _dense_baseline(cfg, params, prompt, 10)
        out1 = [eng.admit(0, prompt, 10)]
        out2 = [eng.admit(1, prompt, 10)]  # identical prompt: full hit
        assert eng.pool.stats()["prefix_hits_total"] >= 1
        while len(out1) < 10:
            tok = step_now(eng)
            out1.append(int(tok[0]))
            out2.append(int(tok[1]))
        # both streams must equal the baseline: if either slot's decode
        # writes had landed in a shared page, the OTHER stream diverges
        assert out1 == base
        assert out2 == base
        eng.release(0)
        eng.release(1)
        eng.close()


# ---------------------------------------------------------------------------
# preemption — evict to host, restore byte-exact, never drop
# ---------------------------------------------------------------------------
class TestPreemptRestore:
    def test_preempt_restore_byte_exact(self):
        cfg, params = _tiny()
        rng = np.random.default_rng(23)
        p1 = rng.integers(0, cfg.vocab, 9).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab, 6).astype(np.int32)
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=16,
                            chunk=16, share_prefixes=False)
        out1 = [eng.admit(0, p1, 12)]
        out2 = [eng.admit(1, p2, 12)]
        for _ in range(4):
            tok = step_now(eng)
            out1.append(int(tok[0]))
            out2.append(int(tok[1]))
        used_before = eng.pool.used_pages
        blob = eng.preempt(0)
        assert eng.pool.used_pages < used_before  # pages actually freed
        # the survivor keeps decoding while slot 0 sits on the host
        for _ in range(3):
            out2.append(int(step_now(eng)[1]))
        eng.restore(0, blob)
        while len(out1) < 12:
            tok = step_now(eng)
            out1.append(int(tok[0]))
            if len(out2) < 12:
                out2.append(int(tok[1]))
        assert out1 == _dense_baseline(cfg, params, p1, 12)
        assert out2 == _dense_baseline(cfg, params, p2, 12)
        eng.release(0)
        eng.release(1)
        assert eng.pool.used_pages == 0

    def test_tight_pool_preemption_through_scheduler(self):
        # pool holds ~1.2 streams: the scheduler must preempt a victim
        # on PagePoolExhausted, finish the other, restore, and finish
        # the victim — zero memory sheds, zero corrupted tokens
        cfg, params = _tiny()
        p1 = (np.arange(1, 14, dtype=np.int32) % 60)
        p2 = ((np.arange(3, 23, dtype=np.int32) * 7) % 60).astype(np.int32)
        base1 = _dense_baseline(cfg, params, p1, 20)
        base2 = _dense_baseline(cfg, params, p2, 10)
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=6,
                            chunk=16, share_prefixes=False)
        sched = DecodeScheduler(eng, name="tight")
        try:
            r1 = sched.submit(p1, steps=20)
            r2 = sched.submit(p2, steps=10)
            o1 = np.asarray(r1.result(120)[0]).tolist()
            o2 = np.asarray(r2.result(120)[0]).tolist()
            snap = sched.metrics_snapshot()
        finally:
            sched.close()
        assert o1 == base1
        assert o2 == base2
        assert snap["preempted"] >= 1, "pool pressure must preempt"
        assert snap["preempted"] < 50, \
            f"preempt/restore ping-pong: {snap['preempted']}"
        assert snap["restored"] == snap["preempted"]
        assert snap["shed_memory"] == 0, "preemption means never-drop"
        assert eng.pool.used_pages == 0


# ---------------------------------------------------------------------------
# speculative decode — output identical to target-only for ANY
# acceptance pattern
# ---------------------------------------------------------------------------
class _ScriptDraft:
    """Oracle-backed draft with a scripted accuracy pattern: proposal i
    of round r is the TRUE next token when ``correct(r, i)``, else a
    deliberately wrong one. Drives the verifier through every
    acceptance count without depending on model behavior."""

    def __init__(self, oracle, correct):
        self._oracle = oracle  # slot -> full true stream (prompt+emits)
        self._correct = correct
        self._round = 0

    def admit(self, slot, tokens, first):
        pass

    def propose(self, slot, hist, k):
        truth = self._oracle[slot]
        r, self._round = self._round, self._round + 1
        props = []
        for i in range(k):
            pos = len(hist) + i
            true_tok = truth[pos] if pos < len(truth) else 0
            props.append(true_tok if self._correct(r, i)
                         else (true_tok + 1) % 64)
        return props

    def commit(self, slot, emitted):
        pass

    def release(self, slot):
        pass

    def restore(self, slot, hist):
        pass


class TestSpeculativeParity:
    def _spec_stream(self, eng, prompt, steps):
        out = [eng.admit(0, np.asarray(prompt, np.int32), steps)]
        while len(out) < steps:
            out.extend(eng.step_tokens()[0])
        eng.release(0)
        return out[:steps]

    @pytest.mark.parametrize("pattern,expected_rate", [
        (lambda r, i: False, 0.0),        # every proposal rejected
        (lambda r, i: True, 1.0),         # every proposal accepted
        (lambda r, i: r % 2 == 0, None),  # alternating rounds
        (lambda r, i: i == 0, None),      # exactly one accept per round
    ])
    def test_scripted_acceptance_patterns_token_exact(self, pattern,
                                                      expected_rate):
        from nnstreamer_tpu.serving.speculative import SpeculativeLMEngine

        cfg, params = _tiny()
        rng = np.random.default_rng(29)
        prompt = rng.integers(0, cfg.vocab, 7).astype(np.int32)
        steps = 12
        base = _dense_baseline(cfg, params, prompt, steps)
        oracle = {0: [int(t) for t in prompt] + base}
        target = PagedLMEngine(cfg, params, slots=1, page_size=8,
                               pages=8, chunk=16, share_prefixes=False)
        eng = SpeculativeLMEngine(
            target, _ScriptDraft(oracle, pattern), k=4)
        assert self._spec_stream(eng, prompt, steps) == base
        if expected_rate is not None:
            assert eng.acceptance_rate() == pytest.approx(
                expected_rate, abs=0.05)
        eng.close()

    def test_ngram_draft_token_exact(self):
        from nnstreamer_tpu.serving.speculative import (
            NgramDraft,
            SpeculativeLMEngine,
        )

        cfg, params = _tiny()
        rng = np.random.default_rng(31)
        prompt = rng.integers(0, cfg.vocab, 8).astype(np.int32)
        base = _dense_baseline(cfg, params, prompt, 16)
        target = PagedLMEngine(cfg, params, slots=1, page_size=8,
                               pages=8, chunk=16, share_prefixes=False)
        eng = SpeculativeLMEngine(target, NgramDraft(), k=4)
        assert self._spec_stream(eng, prompt, 16) == base
        eng.close()

    def test_model_draft_token_exact_through_scheduler(self):
        # the full production stack: tiny_draft ModelDraft proposals,
        # paged target verify, DecodeScheduler burst consumption
        from nnstreamer_tpu.models.lm_serving import tiny, tiny_draft

        eng = tiny.make_continuous(
            slots=2, draft=tiny_draft, spec_k=4,
            page_size=8, pages=16, chunk=16, share_prefixes=False)
        cfg, params = eng.cfg, eng.target.params
        rng = np.random.default_rng(37)
        p1 = rng.integers(0, cfg.vocab, 9).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab, 4).astype(np.int32)
        sched = DecodeScheduler(eng, name="spec-sched")
        try:
            r1 = sched.submit(p1, steps=10)
            r2 = sched.submit(p2, steps=7)
            got1 = np.asarray(r1.result(120)[0]).tolist()
            got2 = np.asarray(r2.result(120)[0]).tolist()
        finally:
            sched.close()
        assert got1 == _dense_baseline(cfg, params, p1, 10)
        assert got2 == _dense_baseline(cfg, params, p2, 7)
        assert eng.pool.used_pages == 0

    def test_speculation_survives_preemption(self):
        # preempt/restore must round-trip the draft's history too: the
        # restored stream continues token-exact
        from nnstreamer_tpu.serving.speculative import (
            NgramDraft,
            SpeculativeLMEngine,
        )

        cfg, params = _tiny()
        rng = np.random.default_rng(41)
        prompt = rng.integers(0, cfg.vocab, 8).astype(np.int32)
        steps = 14
        base = _dense_baseline(cfg, params, prompt, steps)
        target = PagedLMEngine(cfg, params, slots=1, page_size=8,
                               pages=8, chunk=16, share_prefixes=False)
        eng = SpeculativeLMEngine(target, NgramDraft(), k=4)
        out = [eng.admit(0, prompt, steps)]
        out.extend(eng.step_tokens()[0])
        blob = eng.preempt(0)
        assert target.pool.used_pages == 0
        eng.restore(0, blob)
        while len(out) < steps:
            out.extend(eng.step_tokens()[0])
        assert out[:steps] == base
        eng.release(0)
        eng.close()


# ---------------------------------------------------------------------------
# pool in place — the layout every program writes and reads as it is stored
# ---------------------------------------------------------------------------
def _pools(eng):
    """Both pools on the host as (layers, pages+1, page, heads*head_dim):
    the folded row axis split back into layer and page."""
    L, pg = eng.cfg.layers, eng.page_size
    return [np.array(a, np.float32).reshape(L, -1, pg, eng.cfg.dim)
            for a in (eng._kpool, eng._vpool)]


class TestPoolLayout:
    def test_pool_shape_and_bytes(self):
        cfg, params = _tiny()
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=16,
                            chunk=16, share_prefixes=False)
        rows = cfg.layers * (16 + 1)  # every layer keeps its null page 0
        assert eng._kpool.shape == eng._vpool.shape == (rows, 8, cfg.dim)
        item = eng._kpool.dtype.itemsize
        assert eng.page_bytes == 2 * cfg.layers * 8 * cfg.dim * item
        assert eng.cache_bytes == 2 * rows * 8 * cfg.dim * item

    def test_null_page_write_changes_no_live_page(self):
        # slot 1 is inactive: its step write routes to page 0 of every
        # layer. Every row but the null pages and the one line slot 0
        # writes in its own page must come out bit for bit
        cfg, params = _tiny()
        rng = np.random.default_rng(43)
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=16,
                            chunk=16, share_prefixes=False)
        eng.admit(0, rng.integers(0, cfg.vocab, 11).astype(np.int32), 6)
        before = _pools(eng)
        pos = int(eng._pos[0])
        page, offs = int(eng._bt[0, pos // 8]), pos % 8
        step_now(eng)
        for b, a in zip(before, _pools(eng)):
            assert np.any(a[:, page, offs] != b[:, page, offs]), \
                "the live slot's line must have been written"
            a[:, page, offs] = b[:, page, offs]
            np.testing.assert_array_equal(a[:, 1:], b[:, 1:])
        eng.release(0)

    def test_cow_copy_leaves_the_siblings_pages_untouched(self):
        # identical page-aligned prompts: slot 1 maps slot 0's pages and
        # its first decode write COW-copies the last one. In every layer
        # the sibling's pages keep their bytes and the copy starts as the
        # page it was copied from
        cfg, params = _tiny()
        rng = np.random.default_rng(47)
        prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=16,
                            chunk=16, share_prefixes=True)
        eng.admit(0, prompt, 6)
        eng.admit(1, prompt, 6)
        shared = [int(p) for p in eng._bt[0, :2]]
        before = _pools(eng)
        cows = eng.pool.stats()["cow_copies_total"]
        # the recomputed last prompt position already copied page 2 for
        # slot 1 during its admit: the copy is what the block table names
        assert cows >= 1 and int(eng._bt[1, 1]) != shared[1]
        for pool in before:
            np.testing.assert_array_equal(
                pool[:, int(eng._bt[1, 1]), :7], pool[:, shared[1], :7])
        step_now(eng)
        for b, a in zip(before, _pools(eng)):
            np.testing.assert_array_equal(a[:, shared], b[:, shared])
        eng.release(0)
        eng.release(1)
        eng.close()

    @pytest.mark.parametrize("prompt_len,steps_before", [
        (8, 0),    # the carry sits on a page boundary: one full page held
        (9, 2),    # mid-page: a page and 3 lines of the next
        (13, 3),   # the next write crosses into a third page
    ])
    def test_preempt_blob_restores_byte_exact(self, prompt_len,
                                              steps_before):
        cfg, params = _tiny()
        rng = np.random.default_rng(53)
        prompt = rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
        eng = PagedLMEngine(cfg, params, slots=2, page_size=8, pages=16,
                            chunk=16, share_prefixes=False)
        out = [eng.admit(0, prompt, 10)]
        for _ in range(steps_before):
            out.append(int(step_now(eng)[0]))
        held = [int(p) for p in eng._bt[0] if p]
        want = [pool[:, held] for pool in _pools(eng)]
        blob = eng.preempt(0)
        NB = eng.blocks_per_slot
        kblob, vblob = blob["pages"]  # one blob per pool of the family
        assert kblob.shape == vblob.shape == \
            (cfg.layers, NB, 8, cfg.dim), "blob: (layer, block, line, dim)"
        # park another tenant on the freed pages so restore lands elsewhere
        eng.admit(1, rng.integers(0, cfg.vocab, 20).astype(np.int32), 4)
        eng.restore(0, blob)
        fresh = [int(p) for p in eng._bt[0] if p]
        assert len(fresh) == len(held)
        for w, pool in zip(want, _pools(eng)):
            np.testing.assert_array_equal(pool[:, fresh], w)
        while len(out) < 10:
            out.append(int(step_now(eng)[0]))
        assert out == _dense_baseline(cfg, params, prompt, 10)
        eng.release(0)
        eng.release(1)
        assert eng.pool.used_pages == 0


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described (not attached) TPU v5e, or skip. Made inside
    the fixture: only the worker that runs this file loads the TPU's
    compiler, and every worker collects the same tests."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _entry_results(hlo_text):
    """(name, opcode, element counts of its results) for every instruction
    of the optimised module's entry computation."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(",
                     line)
        if m:
            counts = {math.prod(int(d) for d in dims.split(","))
                      for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(2))}
            yield m.group(1), m.group(3), counts


def _no_gathered_context(compiled, count):
    """A step reads the pool's rows where they lie: the kernel is in the
    program, and nothing of a gathered context's ``(slots, max_seq,
    width)`` elements is computed."""
    hlo = compiled.as_text()
    assert "paged_line_attention" in hlo and "tpu_custom_call" in hlo, \
        "the step's attention is not the kernel"
    made = [f"{op} {name}" for name, op, counts in _entry_results(hlo)
            if count in counts and op != "parameter"]
    assert not made, f"_step still gathers a padded context: {made}"


class TestPoolInPlaceOnTpu:
    # a prefill launch at the small presets' width and at the width the
    # engine derives on a v5e (256 rows over a context of 512)
    PROGRAMS = [("_step", 32, 128), ("_prefill_chunk", 32, 128),
                ("_prefill_chunk", 256, 512)]

    @pytest.mark.parametrize("program, C, max_seq", PROGRAMS)
    def test_pool_goes_in_and_comes_out_in_one_layout(self, v5e_chip,
                                                      program, C, max_seq,
                                                      monkeypatch):
        import functools

        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models.transformer import (
            TransformerConfig,
            init_params,
        )

        # OPT's head size and page size at a small depth and width. 2049
        # rows a layer: no gathered context has a pool's or a layer's
        # element count by accident, and at 17 MB a layer the pool is past
        # what the compiler would prefetch whole into faster memory
        cfg = TransformerConfig(vocab=512, dim=256, heads=4, layers=2,
                                mlp_mult=4, max_seq=max_seq)
        S, pg, pages = 4, 16, 2048
        # the step as a TPU runs it, with the kernel in (here the op would
        # take its plain form: this process's backend is the CPU)
        monkeypatch.setattr(paged_attention, "paged_line_attention",
                            paged_attention.kernel_line_attention)
        eng = PagedLMEngine(cfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
                            slots=S, page_size=pg, pages=pages, chunk=C)

        def shape(s, dt):
            return jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)

        params = jax.tree_util.tree_map(
            lambda a: shape(a.shape, jnp.bfloat16),
            jax.eval_shape(functools.partial(init_params, cfg)))
        NB = cfg.max_seq // pg
        pool = shape(eng._kpool.shape, jnp.bfloat16)
        if program == "_step":
            args = (shape((S, 1), jnp.int32), shape((S,), jnp.int32),
                    shape((S,), jnp.bool_), shape((S, NB), jnp.int32))
        else:
            args = (shape((C,), jnp.int32), shape((), jnp.int32),
                    shape((), jnp.int32), shape((NB,), jnp.int32))
        compiled = getattr(eng, program).func.lower(
            params, *args, pool, pool).compile()

        pool_count = int(np.prod(eng._kpool.shape))
        sizes = {pool_count, pool_count // cfg.layers}
        moved = [f"{op} {name}"
                 for name, op, counts in _entry_results(compiled.as_text())
                 if counts & sizes
                 and (op == "copy" or "slice" in name or "copy" in name)]
        assert not moved, \
            f"{program} copies or slices a pool or a layer of it: {moved}"
        aliased = compiled.memory_analysis().alias_size_in_bytes
        assert aliased >= 2 * pool_count * 2, \
            f"{program} must alias both donated pools to its outputs"
        if program == "_step":
            _no_gathered_context(compiled, S * cfg.max_seq * cfg.dim)

    @pytest.mark.parametrize("program, C, max_seq", PROGRAMS)
    def test_latent_pool_goes_in_and_comes_out_in_one_layout(self, v5e_chip,
                                                             program, C,
                                                             max_seq,
                                                             monkeypatch):
        # the same rules for the DeepSeek-V3 family's one pool: a line of
        # 512 latent + 64 rotary values (Kanana-2's widths) at a small
        # depth, hidden size and expert count, 2049 rows a layer
        import functools

        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models.deepseek_v3 import (
            DeepseekV3Config,
            init_params,
        )

        cfg = DeepseekV3Config(
            vocab_size=512, hidden_size=256, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=512,
            moe_intermediate_size=128, n_routed_experts=8,
            num_experts_per_tok=2, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128,
            max_position_embeddings=max_seq)
        S, pg, pages = 4, 16, 2048
        monkeypatch.setattr(paged_attention, "paged_line_attention",
                            paged_attention.kernel_line_attention)
        eng = PagedLMEngine(cfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
                            slots=S, page_size=pg, pages=pages, chunk=C)
        assert len(eng._pools) == 1 and eng.line_widths == (640,)  # 576 padded

        def shape(s, dt):
            return jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)

        params = jax.tree_util.tree_map(
            lambda a: shape(a.shape, jnp.bfloat16),
            jax.eval_shape(functools.partial(init_params, cfg)))
        NB = cfg.max_position_embeddings // pg
        pool = shape(eng._pools[0].shape, jnp.bfloat16)
        if program == "_step":
            args = (shape((S, 1), jnp.int32), shape((S,), jnp.int32),
                    shape((S,), jnp.bool_), shape((S, NB), jnp.int32))
        else:
            args = (shape((C,), jnp.int32), shape((), jnp.int32),
                    shape((), jnp.int32), shape((NB,), jnp.int32))
        compiled = getattr(eng, program).func.lower(
            params, *args, pool).compile()

        pool_count = int(np.prod(eng._pools[0].shape))
        sizes = {pool_count, pool_count // cfg.num_hidden_layers}
        moved = [f"{op} {name}"
                 for name, op, counts in _entry_results(compiled.as_text())
                 if counts & sizes
                 and (op == "copy" or "slice" in name or "copy" in name)]
        assert not moved, \
            f"{program} copies or slices the pool or a layer of it: {moved}"
        aliased = compiled.memory_analysis().alias_size_in_bytes
        assert aliased >= pool_count * 2, \
            f"{program} must alias the donated pool to its output"
        # a decode step never expands a context's keys and values per head:
        # nothing of (slots, ctx, heads, head values) elements exists
        # (weights of these toy sizes have such counts by accident: only
        # what an operation computes is looked at)
        ctx = NB * pg
        expanded = {S * ctx * 4 * d for d in (128, 192, 256, 320)}
        if program == "_step":
            made = [f"{op} {name}" for name, op, counts in
                    _entry_results(compiled.as_text())
                    if counts & expanded and op in (
                        "fusion", "dot", "convolution", "custom-call",
                        "reshape", "transpose", "broadcast", "copy")]
            assert not made, f"_step expands keys or values per head: {made}"
            _no_gathered_context(compiled, S * ctx * 640)

    @pytest.mark.parametrize("program, C, max_seq", PROGRAMS)
    def test_pools_by_layer_kind_go_in_and_come_out_in_one_layout(
            self, v5e_chip, program, C, max_seq, monkeypatch):
        # the same rules for a family with two kinds of layer (Mellum2's
        # line of 4 x 128 keys and as many values, window 1024, at a small
        # depth, hidden size and expert count): four pool arrays, the full
        # kind's of 2049 rows a layer and the window kind's of 513, each
        # aliased to its output, none copied or sliced by layer, and the
        # step's kernel taking both tables
        import functools

        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models.mellum import MellumConfig, init_params

        max_seq = max_seq * 8  # past the window: 1024 or 4096 positions
        cfg = MellumConfig(
            vocab_size=512, hidden_size=256, num_hidden_layers=4,
            num_attention_heads=32, num_key_value_heads=4, head_dim=128,
            moe_intermediate_size=128, num_experts=8, num_experts_per_tok=2,
            sliding_window=1024, max_position_embeddings=max_seq,
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                    "original_max_position_embeddings": 8192,
                    "beta_fast": 32, "beta_slow": 1,
                    "attention_factor": 1.2772588722239782},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 500000}})
        S, pg = 4, 16
        monkeypatch.setattr(paged_attention, "paged_line_attention",
                            paged_attention.kernel_line_attention)
        eng = PagedLMEngine(cfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
                            slots=S, page_size=pg, chunk=C,
                            pages={"full": 2048, "window": 512},
                            share_prefixes=False)
        assert eng.kinds == ("full", "window") and len(eng._pools) == 4
        assert eng.line_widths == (512, 512)  # four whole lane rows
        assert [p.shape[0] for p in eng._pools] == [2049, 2049,
                                                    3 * 513, 3 * 513]

        def shape(s, dt):
            return jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)

        params = jax.tree_util.tree_map(
            lambda a: shape(a.shape, jnp.bfloat16),
            jax.eval_shape(functools.partial(init_params, cfg)))
        NB = max_seq // pg
        pools = [shape(p.shape, jnp.bfloat16) for p in eng._pools]
        if program == "_step":
            args = (shape((S, 1), jnp.int32), shape((S,), jnp.int32),
                    shape((S,), jnp.bool_), shape((S, NB), jnp.int32),
                    shape((S, NB), jnp.int32))
        else:
            args = (shape((C,), jnp.int32), shape((), jnp.int32),
                    shape((), jnp.int32), shape((NB,), jnp.int32),
                    shape((NB,), jnp.int32))
        compiled = getattr(eng, program).func.lower(
            params, *args, *pools).compile()

        counts = {int(np.prod(p.shape)) for p in eng._pools}
        sizes = counts | {int(np.prod(eng._pools[0].shape)),
                          int(np.prod(eng._pools[2].shape)) // 3}
        moved = [f"{op} {name}"
                 for name, op, seen in _entry_results(compiled.as_text())
                 if seen & sizes
                 and (op == "copy" or "slice" in name or "copy" in name)]
        assert not moved, \
            f"{program} copies or slices a pool or a layer of it: {moved}"
        aliased = compiled.memory_analysis().alias_size_in_bytes
        assert aliased >= sum(int(np.prod(p.shape)) for p in eng._pools) * 2, \
            f"{program} must alias all four donated pools to its outputs"
        if program == "_step":
            assert compiled.as_text().count("paged_line_attention") >= 4
            _no_gathered_context(compiled, S * max_seq * 512)


    @staticmethod
    def _cells_launch(v5e_chip):
        """``_prefill_chunk`` of the ``opt_1.3b`` cell's engine, at the
        cell's widths, limit and pool and the width a v5e derives, two
        layers deep (24 compile for a quarter of a minute on every core,
        under the other workers' tests): ``(engine, configuration, pool's
        shape, arguments)`` to lower it by."""
        import json
        import os

        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models.transformer import (
            TransformerConfig,
            init_params,
        )

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               "opt_1.3b.json")) as fh:
            config = json.load(fh)
        cfg = TransformerConfig(
            vocab=config["vocab_size"], dim=config["hidden_size"],
            heads=config["num_attention_heads"],
            layers=2,
            mlp_mult=config["ffn_dim"] // config["hidden_size"],
            max_seq=config["max_position_embeddings"])
        geo = config["engine"]
        C, pg, ctx = 256, geo["page_size"], cfg.max_seq
        # the programs close over the sizes only: a two-page pool to build
        eng = PagedLMEngine(cfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
                            slots=1, page_size=pg, pages=2, chunk=C)
        assert eng.chunk_block_pages * pg == 256

        def shape(s, dt):
            return jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)

        params = jax.tree_util.tree_map(
            lambda a: shape(a.shape, jnp.bfloat16),
            jax.eval_shape(functools.partial(init_params, cfg)))
        pool = shape((cfg.layers * (geo["pages"] + 1), pg, cfg.dim),
                     jnp.bfloat16)
        return eng, cfg, pool, (
            params, shape((C,), jnp.int32), shape((), jnp.int32),
            shape((), jnp.int32), shape((ctx // pg,), jnp.int32), pool, pool)

    def test_the_cells_launch_walks_and_gathers_no_padded_context(
            self, v5e_chip):
        """The cell's launch (PR 42): its attention is a loop a layer over
        blocks of 256 positions, nothing of a gathered context's shape is
        made anywhere in the program (the loops' bodies included), and
        nothing of a pool's size is copied."""
        eng, cfg, pool, args = self._cells_launch(v5e_chip)
        C, pg, ctx = eng.chunk, eng.page_size, cfg.max_seq
        hlo = eng._prefill_chunk.func.lower(*args).compile().as_text()
        assert len(re.findall(r" while\(", hlo)) == cfg.layers
        # the family's launch multiplies at jax's default, as its
        # configuration states and as its gathered form did
        assert eng.family.chunk_precision is None and "highest" not in hlo
        H, Dh = cfg.heads, cfg.head_dim
        # the limit's lines split by head, a table's worth of gathered
        # lines, a launch's scores over the limit (the hidden size is the
        # limit here: (rows, 2048) and (2048, 2048) are activations and
        # weights, and say nothing)
        gathered = {f"[{ctx},{H},{Dh}]", f"[{H},{ctx},{Dh}]",
                    f"[{H},{Dh},{ctx}]", f"[1,{ctx},{cfg.dim}]",
                    f"[{ctx // pg},{pg},{cfg.dim}]", f"[{H},{C},{ctx}]",
                    f"[1,{H},{C},{ctx}]", f"[{H},1,{C},{ctx}]"}
        pool_dims = f"[{pool.shape[0]},{pg},{cfg.dim}]"
        made, copied = [], []
        for line in hlo.splitlines():
            m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(",
                         line)
            if not m or m.group(3) in ("parameter", "get-tuple-element",
                                       "tuple", "while", "bitcast"):
                continue
            name, result, op = m.groups()
            shapes = set(re.findall(r"\w+(\[[\d,]+\])", result))
            if shapes & gathered:
                made.append(f"{op} {name} {sorted(shapes & gathered)}")
            if pool_dims in shapes and (op == "copy" or "copy" in name):
                copied.append(f"{op} {name}")
        assert not made, f"the launch still makes a padded context: {made}"
        assert not copied, f"the launch copies a pool: {copied}"

    def test_the_cells_launch_writes_its_lines_a_page_at_a_time(
            self, v5e_chip):
        """The cell's launch (PR 44): each of its four writes (two layers,
        keys and values) is one scatter of 16 whole pages where it was one
        of 256 lines, both pools are aliased and neither is copied, and
        the program is no larger: at most 3% over the instructions this
        launch has had (1,161 at PR 43; a program's load follows its
        instructions, and ``setup_s`` the load)."""
        import jax

        eng, cfg, pool, args = self._cells_launch(v5e_chip)
        C, pg = eng.chunk, eng.page_size
        assert eng.chunk_pages == C // pg == 16

        def scatters(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "scatter":
                    yield tuple(v.aval.shape for v in eqn.invars)
                for inner in jax.core.jaxprs_in_params(eqn.params):
                    yield from scatters(inner)

        over_a_pool = [shapes for shapes in scatters(
            jax.make_jaxpr(eng._prefill_chunk.func)(*args).jaxpr)
            if shapes[0] == pool.shape]
        assert over_a_pool == [
            (pool.shape, (16, 1), (16, pg, cfg.dim))] * (2 * cfg.layers)
        compiled = eng._prefill_chunk.func.lower(*args).compile()
        hlo = compiled.as_text()
        # every instruction whose result is a pool: (name, opcode, line)
        pool_dims = f"bf16[{pool.shape[0]},{pg},{cfg.dim}]"
        makes_a_pool = [
            (*m.groups(), line) for line in hlo.splitlines()
            for m in [re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = " + re.escape(
                pool_dims) + r"\S* ([a-z][\w\-]*)\(", line)] if m]
        written = [line for _, op, line in makes_a_pool if op == "scatter"]
        assert len(written) == 2 * cfg.layers
        assert all("update_window_dims={1,2}" in line for line in written)
        copied = [name for name, op, _ in makes_a_pool if "copy" in op]
        assert not copied, f"the launch copies a pool: {copied}"
        assert compiled.memory_analysis().alias_size_in_bytes \
            >= 2 * int(np.prod(pool.shape)) * 2
        instructions = len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", hlo,
                                      re.M))
        # 1,158 until PR 49, which took the head out of the launch
        assert instructions <= 1161 * 1.03, instructions

    def test_the_cells_launch_runs_no_head_and_the_seed_scores_one_row(
            self, v5e_chip):
        """The cell's launch (PR 49) runs no head: it hands back its rows
        as the stack left them, 2 MB, and no ``(256, vocab)`` scores are
        made anywhere in it (51 MB of float32 that one row was read of,
        behind a prompt's last launch alone), nor any of one row.
        ``_seed``, the program behind a prompt's last launch, scores the
        last real row, takes the best token and puts it into the decode
        carry: its answer is the carry and four bytes."""
        import jax
        import jax.numpy as jnp

        eng, cfg, pool, args = self._cells_launch(v5e_chip)
        C, V = eng.chunk, cfg.vocab
        assert (C, V) == (256, 50272)
        lowered = eng._prefill_chunk.func.lower(*args)
        hlo = lowered.compile().as_text()
        assert not re.findall(rf"\[(?:1,)?(?:{C}|1),{V}\]", hlo), \
            "the launch still scores its rows"
        assert " conditional(" not in hlo
        out = lowered.out_info[0]
        assert (out.shape, out.dtype) == ((C, cfg.dim), jnp.float32)

        def shape(s, dt):
            return jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)

        seed = eng._seed.func.lower(
            args[0], shape((eng.slots, 1), jnp.int32), shape((), jnp.int32),
            shape((C, cfg.dim), jnp.float32), shape((), jnp.int32))
        assert [(o.shape, o.dtype) for o in seed.out_info] == [
            ((eng.slots, 1), jnp.int32), ((1,), jnp.int32)]
        text = seed.compile().as_text()
        assert re.search(rf"f32\[(?:1,)?{V}\]", text), "one row is scored"
        assert not re.findall(rf"\[{C},{V}\]", text)

    @pytest.mark.parametrize("program", ["_step", "_prefill_chunk"])
    def test_the_looped_familys_programs_copy_no_weight_through_hbm(
            self, v5e_chip, program, monkeypatch):
        """``ouro_2.6b``'s programs at the published widths and the cell's
        engine geometry, two layers deep, the parameters as the family
        stores them (PR 45): no ``copy`` whose result has a weight matrix's
        shape lies outside memory space 1 (as given, the compiler
        transposed ``W_q`` and ``W_k`` of every layer through HBM ahead of
        the loop over passes, in every call: four such copies at this
        depth), and the temporaries stay under a sixth of one layer's
        weights (the four copies alone are a third)."""
        import json
        import os

        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models.ouro import (
            OuroConfig,
            OuroFamily,
            init_params,
        )

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               "ouro_2.6b.json")) as fh:
            config = json.load(fh)
        cfg = OuroConfig.from_published(
            {**config, "num_hidden_layers": 2,
             "layer_types": config["layer_types"][:2]})
        geo = config["engine"]
        S, pg, C = geo["slots"], geo["page_size"], 256
        monkeypatch.setattr(paged_attention, "paged_line_attention",
                            paged_attention.kernel_line_attention)
        # the programs close over the sizes only: a two-page pool to build
        eng = PagedLMEngine(cfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
                            slots=S, page_size=pg, pages=2, chunk=C,
                            share_prefixes=False)
        assert eng.passes == 4 and eng.kind_layers == {"full": 8}

        def shape(s, dt):
            return jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)

        stored = jax.eval_shape(
            lambda: OuroFamily(cfg).stored(init_params(cfg)))
        blk = stored["blocks"][0]
        assert blk["wq"].shape == blk["wk"].shape == (2048, 2048)
        params = jax.tree_util.tree_map(
            lambda a: shape(a.shape, jnp.bfloat16), stored)
        NB = cfg.max_position_embeddings // pg
        pool = shape((eng.kind_layers["full"] * (geo["pages"] + 1), pg,
                      cfg.line_width), jnp.bfloat16)
        if program == "_step":
            args = (shape((S, 1), jnp.int32), shape((S,), jnp.int32),
                    shape((S,), jnp.bool_), shape((S, NB), jnp.int32))
        else:
            args = (shape((C,), jnp.int32), shape((), jnp.int32),
                    shape((), jnp.int32), shape((NB,), jnp.int32))
        compiled = getattr(eng, program).func.lower(
            params, *args, pool, pool).compile()

        matrices = {f"bf16[{','.join(map(str, dims))}]"
                    for a in jax.tree_util.tree_leaves(stored) if a.ndim == 2
                    for dims in (a.shape, a.shape[::-1])}
        copied = [
            line.strip()[:120] for line in compiled.as_text().splitlines()
            for m in [re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (\w+\[[\d,]*\])"
                               r"(\S*) copy\(", line)]
            if m and m.group(1) in matrices and "S(1)" not in m.group(2)]
        assert not copied, \
            f"{program} copies a weight matrix through HBM: {copied}"
        layer = 2 * sum(int(np.prod(a.shape))
                        for a in jax.tree_util.tree_leaves(blk)
                        if a.ndim == 2)
        assert layer == 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
        assert compiled.memory_analysis().temp_size_in_bytes < layer // 6


class TestAttentionByHeadOnTpu:
    """The step's attention kernel with head-wide operands (PR 48), at the
    widths of the cell that takes them (64 slots, 64 heads over 8 key
    heads of 128, pages of ``(16, 1024)``, blocks of 32 pages), compiled
    for a described v5e: lane slices of 128 a key head, 48 stacked rows a
    product, and what the interpreter cannot refuse (a slice off the
    tiling, more fast memory than a kernel may use)."""

    # queries a slot, a window layer's starts
    CALLS = [(2, False), (2, True), (1, False)]

    @pytest.mark.parametrize("K, window", CALLS)
    def test_the_kernel_compiles_at_the_cells_widths(self, v5e_chip, K,
                                                     window):
        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.ops import paged_attention as pa

        S, H, KV, Dh, pg, NB, rows = 64, 64, 8, 128, 16, 256, 2 * 2049
        assert pa.contracts_by_head(3 * K * H, KV, Dh)

        def shape(dims, dt):
            return jax.ShapeDtypeStruct(dims, dt, sharding=v5e_chip)

        def call(q, kpool, vpool, table, lengths, starts):
            return pa.kernel_line_attention(
                q, kpool, vpool, table, lengths, Dh ** -0.5,
                starts if window else None, queries=K)

        pool = shape((rows, pg, KV * Dh), jnp.bfloat16)
        compiled = jax.jit(call).lower(
            shape((S, K * H, Dh), jnp.float32), pool, pool,
            shape((S, NB), jnp.int32), shape((S,), jnp.int32),
            shape((S, K) if K > 1 else (S,), jnp.int32)).compile()
        hlo = compiled.as_text()
        assert "paged_line_attention" in hlo and "tpu_custom_call" in hlo
        # head-wide in, head-wide out: nothing of a whole line a row
        assert f"f32[{S},{K * H},{KV * Dh}]" not in hlo


class TestStateInAttentionLayersOnTpu:
    """``tools/serving_programs_ops.py`` on the configuration whose
    attention layers keep a state a slot (``zaya1_8b_pp2_l20``), at its
    rehearsal sizes and in the forms a TPU runs, compiled for a described
    v5e: it builds the family's state arrays for every slot beside the
    pools, both programs compile with them donated and handed back, and
    their bytes are printed beside ``weight_copies``."""

    def test_the_tool_builds_and_prints_the_state_a_slot_keeps(
            self, v5e_chip, tmp_path, monkeypatch):
        import json
        import os

        from nnstreamer_tpu.ops import moe_grouped, paged_attention

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.syspath_prepend(os.path.join(root, "tools"))
        monkeypatch.syspath_prepend(root)
        # the tool binds the TPU's forms where the engine looks them up
        monkeypatch.setattr(paged_attention, "paged_line_attention",
                            paged_attention.paged_line_attention)
        monkeypatch.setattr(moe_grouped, "grouped_experts",
                            moe_grouped.grouped_experts)
        import serving_programs_ops

        name = "zaya1_8b_pp2_l20"
        printed = serving_programs_ops.main(root, str(tmp_path), only=[name],
                                            rehearse=True)
        assert [(p["config"], p["program"]) for p in printed] == [
            (name, "_step"), (name, "_prefill_chunk")]
        with open(os.path.join(root, "benchmark", "configs",
                               f"{name}.json")) as fh:
            small = json.load(fh)["rehearsal"]
        # one float32 line of [p ; a ; shifted value] a slot and layer
        width = (2 * (small["num_attention_heads"]
                      + small["num_key_value_heads"]) + 1) * small["head_dim"]
        state = (small["engine"]["slots"] * small["num_hidden_layers"]
                 * width * 4)
        for p in printed:
            assert p["state_bytes"] == state
            assert p["weight_copies"] == 0
            # the pools and the state go in and come out where they lie
            assert p["alias_bytes"] >= state
            assert os.path.getsize(os.path.join(
                tmp_path, f"{name}.{p['program']}.ops")) > 0
        ops = open(os.path.join(tmp_path, f"{name}._step.ops")).read()
        assert "custom-call" in ops, "the step holds the TPU's kernels"


class TestExpertsStreamOnTpu:
    """What the compiled programs of the two expert families hold on a
    TPU (PR 32): the record of where ``ops/moe_grouped.py``'s kernel
    engages. The benchmark configurations' widths, experts and slots at
    two layers (four: one period of Mellum's) and a short serving limit,
    compiled for a described v5e with both kernels in (this process's
    backend is the CPU, where either op would take its plain form)."""

    # family, program, rows of the launch, the form its expert layers hold
    PROGRAMS = [
        ("mellum2_12b_a2.5b_l12", "_step", 32, "kernel"),
        ("mellum2_12b_a2.5b_l12", "_prefill_chunk", 256, "kernel"),
        ("mellum2_12b_a2.5b_l12", "_prefill_chunk", 512, "ragged-dot"),
        ("kanana2_30b_a3b_l8", "_step", 32, "kernel"),
        ("kanana2_30b_a3b_l8", "_prefill_chunk", 256, "kernel"),
    ]

    @pytest.mark.parametrize("name, program, rows, form", PROGRAMS)
    def test_the_expert_layers_stream_the_stacks_as_they_lie(
            self, v5e_chip, name, program, rows, form, monkeypatch):
        import functools
        import json
        import os

        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.models import deepseek_v3, mellum
        from nnstreamer_tpu.ops import moe_grouped

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               f"{name}.json")) as f:
            conf = json.load(f)
        conf.update(max_position_embeddings=2048)
        if name.startswith("mellum"):  # one period: three window, one full
            conf.update(num_hidden_layers=4)
            cfg = mellum.MellumConfig.from_published(conf)
            init, pages = mellum.init_params, {"full": 256, "window": 256}
            E, D, F, layers = (cfg.num_experts, cfg.hidden_size,
                               cfg.moe_intermediate_size, 4)
        else:  # layer 0 is the dense one
            conf.update(num_hidden_layers=2)
            cfg = deepseek_v3.DeepseekV3Config.from_published(conf)
            init, pages = deepseek_v3.init_params, 256
            E, D, F, layers = (cfg.n_routed_experts, cfg.hidden_size,
                               cfg.moe_intermediate_size, 1)
        monkeypatch.setattr(paged_attention, "paged_line_attention",
                            paged_attention.kernel_line_attention)
        monkeypatch.setattr(moe_grouped, "grouped_experts",
                            moe_grouped.tpu_grouped_experts)
        S, pg = conf["engine"]["slots"], conf["engine"]["page_size"]
        eng = PagedLMEngine(cfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
                            slots=S, page_size=pg, chunk=rows, pages=pages,
                            share_prefixes=False)
        assert S == 32 and eng.chunk == rows

        def shape(s, dt):
            return jax.ShapeDtypeStruct(s, dt, sharding=v5e_chip)

        params = jax.tree_util.tree_map(
            lambda a: shape(a.shape, jnp.bfloat16),
            jax.eval_shape(functools.partial(init, cfg)))
        NB, K = eng.blocks_per_slot, len(eng.kinds)
        pools = [shape(p.shape, jnp.bfloat16) for p in eng._pools]
        if program == "_step":
            args = (shape((S, 1), jnp.int32), shape((S,), jnp.int32),
                    shape((S,), jnp.bool_),
                    *[shape((S, NB), jnp.int32)] * K)
        else:
            args = (shape((rows,), jnp.int32), shape((), jnp.int32),
                    shape((), jnp.int32), *[shape((NB,), jnp.int32)] * K)
        compiled = getattr(eng, program).func.lower(
            params, *args, *pools).compile()
        text = compiled.as_text()

        kernels = [line for line in text.splitlines()
                   if "custom-call(" in line and "grouped_experts" in line]
        if form == "kernel":
            assert len(kernels) == layers and "ragged-dot" not in text
            for line in kernels:  # the drivers charge device time by this
                assert re.search(r'op_name="[^"]*/moe\.experts/[^"]*'
                                 r'grouped_experts', line), line
        else:
            assert not kernels and text.count("ragged-dot") >= 3 * layers
        # no copy, re-layout or conversion of an expert stack: nothing the
        # program makes has a stack's element count, and its temporaries
        # are smaller than one stack
        made = [f"{op} {name_}"
                for name_, op, counts in _entry_results(text)
                if E * D * F in counts and op != "parameter"]
        assert not made, f"{program} makes a copy of an expert stack: {made}"
        assert compiled.memory_analysis().temp_size_in_bytes < E * D * F * 2


# ---------------------------------------------------------------------------
# page lifecycle — refcounts reach zero on EVERY scheduler exit path
# ---------------------------------------------------------------------------
class TestPageLifecycle:
    def _engine(self, slots=2, pages=16):
        cfg, params = _tiny()
        return cfg, PagedLMEngine(cfg, params, slots=slots, page_size=8,
                                  pages=pages, chunk=16,
                                  share_prefixes=False)

    def test_release_on_close_with_inflight_work(self):
        cfg, eng = self._engine()
        sched = DecodeScheduler(eng, name="close-leak")
        p = np.arange(1, 10, dtype=np.int32)
        reqs = [sched.submit(p, steps=50) for _ in range(2)]
        # close while decoding: in-flight slots MUST release through
        # the engine — anything else leaks every page they held
        sched.close()
        for r in reqs:
            with pytest.raises(Exception):
                r.result(timeout=5.0)
        assert eng.pool.used_pages == 0

    def test_release_on_deadline_shed(self):
        cfg, eng = self._engine(slots=1)
        sched = DecodeScheduler(eng, name="deadline-leak")
        p = np.arange(1, 8, dtype=np.int32)
        try:
            blocker = sched.submit(p, steps=40)
            # expires while queued behind the blocker (slots=1): shed at
            # pop time, before any pages were mapped for it
            late = sched.submit(p, steps=40, deadline_s=0.01)
            with pytest.raises(Exception):
                late.result(timeout=30.0)
            blocker.result(timeout=120.0)
            assert sched.metrics_snapshot()["shed_deadline"] >= 1
        finally:
            sched.close()
        assert eng.pool.used_pages == 0

    def test_release_on_batch_failure(self):
        cfg, eng = self._engine(slots=1)
        sched = DecodeScheduler(eng, name="fail-leak")
        orig_step = eng.step

        def boom():
            raise ServingError("injected device fault")

        p = np.arange(1, 8, dtype=np.int32)
        try:
            eng.step = boom
            req = sched.submit(p, steps=10)
            with pytest.raises(Exception):
                req.result(timeout=30.0)
        finally:
            eng.step = orig_step
            sched.close()
        assert eng.pool.used_pages == 0, \
            "batch failure must still release the slot's pages"

    def test_leak_ledger_pairs_pool_acquire_release(self, leakcheck):
        # runtime twin of the `# pairs-with:` comments in kv_pool.py:
        # a full admit/decode/release cycle leaves zero outstanding
        # kv_page acquisitions in the sanitizer ledger
        cfg, eng = self._engine(slots=1, pages=8)
        sanitizer.reset_leakcheck()
        p = np.arange(1, 12, dtype=np.int32)
        _decode(eng, 0, p, 6)
        assert eng.pool.used_pages == 0
        assert sanitizer.outstanding("kv_page") == []
        rep = sanitizer.leak_report()
        assert rep["enabled"] and rep["outstanding_units"] == 0

    def test_leak_ledger_flags_held_pages(self, leakcheck):
        # negative control: a slot still active IS an outstanding
        # acquisition — the ledger must see it (otherwise the positive
        # test above proves nothing)
        cfg, eng = self._engine(slots=1, pages=8)
        sanitizer.reset_leakcheck()
        p = np.arange(1, 12, dtype=np.int32)
        eng.admit(0, p, 6)
        assert sanitizer.outstanding("kv_page"), \
            "active slot's pages must show in the ledger"
        eng.release(0)
        assert sanitizer.outstanding("kv_page") == []
