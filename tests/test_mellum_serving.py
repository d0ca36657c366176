"""The Mellum family (grouped-query attention, window layers beside full
ones, YaRN on the full ones, dropless softmax top-k experts) through the
paged serving engine, against the benchmark's plain reference
(``benchmark/references/mellum_lm.py``: a full forward with no cache,
attention a block of queries at a time, a loop over the held experts,
float32 at ``highest``). CPU, small sizes, seeded weights; logits are
compared, never sampled tokens.

Sizes: a window of 12 positions under a limit of 96 and YaRN's original
length 32, so that a prompt of 37 crosses the window inside prefill (chunks
of 8), one of 7 crosses it while decoding, and positions past 32 use the
scaled frequencies. Three window layers and one full one, the published
period.

Tolerances. Everything here is float32 on the CPU, so program and reference
differ only by the order of float32 sums: logits of size 0.1-1 agree to a
few 1e-6; the limits (2e-5 on logits, 1e-4 on the gap of a served token
under the reference's best) leave a factor of ten above what is seen.
"""
import os
import sys

import numpy as np
import pytest
from engine_util import spy_launches, step_now

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib.weights import seed_key  # noqa: E402
from benchmark.references import mellum_lm as ref  # noqa: E402
from nnstreamer_tpu.models.families import family_of  # noqa: E402
from nnstreamer_tpu.models.lm_serving import _LMServingEntry  # noqa: E402
from nnstreamer_tpu.models.mellum import (  # noqa: E402
    MellumConfig,
    MellumFamily,
)
from nnstreamer_tpu.parallel import moe_dropless  # noqa: E402
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine  # noqa: E402
from nnstreamer_tpu.serving.kv_pool import PagePoolExhausted  # noqa: E402

WINDOW, LIMIT, ORIGINAL = 12, 96, 32
SIZES = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=64, moe_intermediate_size=16, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, sliding_window=WINDOW,
    use_sliding_window=True, max_window_layers=0, rms_norm_eps=1e-6,
    max_position_embeddings=LIMIT,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
            "original_max_position_embeddings": ORIGINAL, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}})
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4
ENGINE = dict(slots=3, page_size=4, chunk=8, share_prefixes=False,
              pages={"full": 72, "window": 24})
# ceil((window + chunk) / page) + 1: the most window pages a slot holds
HELD = -(-(WINDOW + 8) // 4) + 1


def _model(seed=5, dtype=jnp.float32, **over):
    conf = {**SIZES, **over}
    cfg = MellumConfig.from_published(conf)
    sz = ref.sizes(conf)
    key = seed_key(seed)
    return cfg, sz, key, ref.program_params(key, sz, dtype)


def _entry(cfg, params):
    class Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    return Seeded(cfg)


def _engine(**over):
    cfg, sz, key, params = _model()
    return cfg, sz, key, _entry(cfg, params).make_continuous(
        **{**ENGINE, **over})


def _reference_logits(key, sz, prompt, served, width=LIMIT):
    """Teacher-forced reference logits at the rows that produced each
    served token: (len(served), V)."""
    n = len(served)
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :prompt.size] = prompt
    tokens[0, prompt.size:prompt.size + n - 1] = served[:-1]
    rows = (prompt.size - 1 + np.arange(n))[None].astype(np.int32)
    return ref.logits_for(key, sz, tokens, rows)["none"][0]


def _gaps(key, sz, prompt, served):
    exact = _reference_logits(key, sz, prompt, np.asarray(served))
    return exact.max(-1) - np.take_along_axis(
        exact, np.asarray(served)[:, None], 1)[:, 0]


def _window_pages(eng, slot):
    return int((eng._bts["window"][slot] != 0).sum())


# -- the family ----------------------------------------------------------------

def test_the_family_is_chosen_by_the_configurations_type_and_says_its_kinds():
    cfg, _, _, _ = _model()
    fam = family_of(cfg)
    assert isinstance(fam, MellumFamily) and fam.name == "mellum"
    assert fam.layer_kinds == ("window", "window", "window", "full")
    assert fam.window == WINDOW and fam.cache_lines == (32, 32)
    assert fam.counters == moe_dropless.COUNTERS and not fam.serves_verify
    with pytest.raises(TypeError, match="MellumConfig"):
        family_of(object())


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("hidden_act", "gelu"), ("mlp_layer_types", ["dense"] * 4),
    ("layer_types", ["chunked_attention"] * 4),
    ("use_sliding_window", False), ("num_key_value_heads", 3),
    ("rope_parameters", {"full_attention": {"rope_type": "llama3"}}),
])
def test_a_key_the_block_does_not_implement_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        MellumConfig.from_published({**SIZES, key: value})


def test_the_published_list_of_layers_is_cut_to_the_layers_held():
    cfg = MellumConfig.from_published(
        {**SIZES, "num_hidden_layers": 2,
         "layer_types": ["full_attention", "sliding_attention"] * 14})
    assert MellumFamily(cfg).layer_kinds == ("full", "window")


def test_speculative_decoding_is_refused_for_the_family_by_name():
    cfg, _, _, params = _model()
    with pytest.raises(NotImplementedError, match="mellum"):
        _entry(cfg, params).make_continuous(draft="ngram", **ENGINE)


def test_prefix_sharing_is_refused_for_a_family_with_window_layers():
    cfg, _, _, params = _model()
    with pytest.raises(NotImplementedError, match="mellum.*share_prefixes"):
        PagedLMEngine(cfg, params, slots=2, page_size=4, chunk=8)
    with pytest.raises(ValueError, match="pages of each"):
        PagedLMEngine(cfg, params, slots=2, page_size=4, chunk=8, pages=16,
                      share_prefixes=False)


def test_yarn_scales_the_slow_pairs_and_keeps_the_fast_ones():
    from nnstreamer_tpu.models.mellum import rope_frequencies

    published = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                 "original_max_position_embeddings": 8192, "beta_fast": 32,
                 "beta_slow": 1, "attention_factor": 1.2772588722239782}
    freq, factor = rope_frequencies(128, published)
    plain, one = rope_frequencies(128, {"rope_theta": 500000})
    assert one == 1.0 and factor == 1.2772588722239782
    np.testing.assert_allclose(freq[:8], plain[:8], rtol=1e-12)
    np.testing.assert_allclose(freq[-8:], plain[-8:] / 16, rtol=1e-12)
    assert np.all(np.diff(freq) < 0)
    # the reference writes the same table on its own
    table, ref_factor = ref.rope_table(128, published)
    np.testing.assert_allclose(freq, table, rtol=1e-12)
    assert ref_factor == factor


# -- the served path against the reference's full forward ----------------------

def test_chunked_prefill_then_decode_matches_the_reference_forward():
    cfg, sz, key, eng = _engine()
    assert isinstance(eng, PagedLMEngine) and eng.family.name == "mellum"
    assert eng.kinds == ("full", "window")
    assert eng.held_blocks == {"full": LIMIT // 4, "window": HELD}
    chunk_scores = spy_launches(eng)
    sched = DecodeScheduler(eng, name="mellum-a")
    rng = np.random.default_rng(0)
    # 37 crosses the window inside prefill and ends past YaRN's original
    # 32; 7 crosses the window while it decodes; 45 + 40 ends at 85
    lengths = [(37, 30), (7, 24), (45, 40), (12, 9)]
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n, _ in lengths]
    try:
        reqs = [sched.submit(p, steps=s)
                for p, (_, s) in zip(prompts, lengths)]
        outs = [np.asarray(r.result(timeout=300)[0]) for r in reqs]
    finally:
        sched.close()
    for pool in eng.pools_by_kind.values():
        assert pool.used_pages == 0, "every page released at close"
    for prompt, served in zip(prompts, outs):
        assert _gaps(key, sz, prompt, served).max() <= GAP_TOL, \
            "a served token is not the reference's"
    # the launches of the first prompt, and its last row's scores (alone in the
    # lane first: its chunks are the first five calls)
    prompt = prompts[0]
    full = ref.logits_for(
        key, sz, np.pad(prompt, (0, LIMIT - prompt.size))[None],
        np.arange(prompt.size, dtype=np.int32)[None])["none"][0]
    seen = 0
    for start, n_valid, scores in chunk_scores[:5]:
        assert start == seen
        seen += n_valid
        if seen < prompt.size:
            assert scores is None, "only a prompt's last launch runs the head"
        else:
            np.testing.assert_allclose(scores, full[seen - 1],
                                       atol=LOGIT_TOL, rtol=0)
    assert seen == prompt.size
    assert eng.compile_count == 2, "one step and one chunk program"
    assert eng.window_pages_released > 0
    for call in ("step", "chunk"):
        c = eng.layer_counts[call]
        assert 0 < c["moe_experts_touched"] <= c["moe_assignments"]
    assert eng.layer_counts["chunk"]["moe_assignments"] == \
        sum(p.size for p in prompts) * 2 * 4


def test_served_through_the_experts_kernel_matches_the_reference(monkeypatch):
    """Both programs with the expert layers in the form a TPU runs
    (``ops/moe_grouped.py``'s kernel, interpreted here), at widths that
    tile by 128 lanes: the served tokens are the reference's, past the
    window too."""
    import functools

    from nnstreamer_tpu.ops import moe_grouped

    rows, real = [], moe_grouped.kernel_grouped_experts

    def kernel(h, *args, **kw):
        rows.append(h.shape[0])
        return real(h, *args, **kw)

    monkeypatch.setattr(moe_grouped, "kernel_grouped_experts", kernel)
    monkeypatch.setattr(moe_grouped, "grouped_experts", functools.partial(
        moe_grouped.tpu_grouped_experts, interpret=True))
    cfg, sz, key, params = _model(hidden_size=128, moe_intermediate_size=128)
    eng = _entry(cfg, params).make_continuous(**ENGINE)
    sched = DecodeScheduler(eng, name="mellum-kernel")
    rng = np.random.default_rng(1)
    lengths = [(21, 20), (7, 12), (30, 9)]
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n, _ in lengths]
    try:
        reqs = [sched.submit(p, steps=s)
                for p, (_, s) in zip(prompts, lengths)]
        outs = [np.asarray(r.result(timeout=300)[0]) for r in reqs]
    finally:
        sched.close()
    # traced once a program and layer: a step of 3 rows, a launch of 8
    assert sorted(rows) == [3] * 4 + [8] * 4
    for prompt, served in zip(prompts, outs):
        assert _gaps(key, sz, prompt, served).max() <= GAP_TOL, \
            "a served token is not the reference's"
    assert eng.layer_counts["chunk"]["moe_assignments"] == \
        sum(p.size for p in prompts) * 2 * 4


def test_decode_steps_logits_match_the_reference_past_the_window():
    """The step program's own logits (not only its argmax) at positions
    one to six windows deep, past YaRN's original length."""
    cfg, sz, key, params = _model()
    fam = MellumFamily(cfg)
    eng = PagedLMEngine(cfg, params, **ENGINE)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 96, 21).astype(np.int32)
    served = [eng.admit(0, prompt, 60)]
    for _ in range(59):
        served.append(int(step_now(eng)[0]))
    served = np.asarray(served)
    exact = _reference_logits(key, sz, prompt, served)
    assert (exact.argmax(-1) == served).mean() > 0.9
    assert _gaps(key, sz, prompt, served).max() <= GAP_TOL
    assert eng._pos[0] == 80 and fam.window == WINDOW


def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    cfg, sz, key, params = _model()
    blk = params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 11, 32), jnp.float32)
    live = jnp.ones((1, 11), bool)
    whole, counts = MellumFamily(cfg).ffn(blk, x, live)
    parts = []
    for first in (0, 4):
        part_cfg = MellumConfig.from_published(
            {**SIZES, "experts_held": [first, 4]})
        held = {**blk, "experts": {k: v[first:first + 4]
                                   for k, v in blk["experts"].items()}}
        y, c = MellumFamily(part_cfg).ffn(held, x, live)
        assert int(c[3]) == 4
        parts.append(y)
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=2e-6)
    assert int(counts[1]) == 11 * 2 and int(counts[3]) == 8
    # and the reference's layer, given one share, gives that share
    h = ref._rms(x[0], jnp.ones((32,)), 1e-6)
    with jax.default_matmul_precision("highest"):
        share = ref.sizes({**SIZES, "experts_held": [4, 4]})
        combine = ref.combine_weights(h, blk["router"], share)[:, 4:]
        want = ref.experts_sum(h, combine, {k: v[4:] for k, v
                                            in blk["experts"].items()})
    np.testing.assert_allclose(parts[1][0], want, atol=2e-6)


def test_the_router_scores_by_softmax_without_a_bias():
    cfg, sz, key, params = _model()
    blk = params["blocks"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (9, 32), jnp.float32)
    experts, weights = moe_dropless.route(
        blk["router"], None, h, 2, 1.0, True, scoring="softmax")
    with jax.default_matmul_precision("highest"):
        p = np.asarray(jax.nn.softmax(h @ blk["router"], axis=-1))
    order = np.argsort(-p, axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(order, -1))
    chosen = np.take_along_axis(p, np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="softmax"):
        moe_dropless.route(blk["router"], jnp.zeros((8,)), h, 2, 1.0,
                           scoring="softmax")
    with pytest.raises(ValueError, match="sigmoid"):
        moe_dropless.route(blk["router"], None, h, 2, 1.0)


# -- pages by kind --------------------------------------------------------------

def test_a_slot_gives_window_pages_back_and_never_holds_more_than_the_bound():
    cfg, sz, key, eng = _engine()
    start = {kind: pool.free_pages
             for kind, pool in eng.pools_by_kind.items()}
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 96, 50).astype(np.int32)
    eng.admit_start(0, prompt, 40)
    most = 0
    while not eng.prefill_tick():
        most = max(most, _window_pages(eng, 0))
    for _ in range(39):
        step_now(eng)
        most = max(most, _window_pages(eng, 0))
        # what is held covers what the next query still sees, no more
        pos = int(eng._pos[0])
        held = np.flatnonzero(eng._bts["window"][0])
        assert held.min() >= max(pos - 1 - WINDOW + 1, 0) // 4
        assert held.max() == (pos - 1) // 4
    assert most <= HELD, "more window pages than ceil((window+chunk)/page)+1"
    assert _window_pages(eng, 0) <= WINDOW // 4 + 1
    full_pages = int((eng._bts["full"][0] != 0).sum())
    assert full_pages == -(-89 // 4), "a full layer keeps every page"
    assert eng.pools_by_kind["window"].used_pages == _window_pages(eng, 0)
    assert eng.window_pages_released == full_pages - _window_pages(eng, 0)
    eng.release(0)
    for kind, pool in eng.pools_by_kind.items():
        assert pool.free_pages == start[kind], f"{kind} pages leaked"


def test_pages_of_the_window_kind_return_when_every_slot_is_released():
    cfg, sz, key, eng = _engine()
    sched = DecodeScheduler(eng, name="mellum-leak")
    rng = np.random.default_rng(9)
    try:
        reqs = [sched.submit(rng.integers(0, 96, n).astype(np.int32),
                             steps=s)
                for n, s in ((30, 20), (9, 40), (41, 12), (17, 30), (5, 50))]
        for r in reqs:
            r.result(timeout=300)
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
    assert snap["kv_pool"]["kind"] == "full"
    assert snap["kv_pools"]["window"]["pages_total"] == 24
    for kind, pool in eng.pools_by_kind.items():
        assert pool.free_pages == pool.pages and pool.used_pages == 0


@pytest.mark.parametrize("prompt_len,steps_before", [(9, 2), (30, 17)])
def test_preempt_and_restore_are_byte_exact_after_pages_were_given_back(
        prompt_len, steps_before):
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(53)
    prompt = rng.integers(0, 96, prompt_len).astype(np.int32)
    out = [eng.admit(0, prompt, 40)]
    for _ in range(steps_before):
        out.append(int(step_now(eng)[0]))
    held = {kind: [int(p) for p in eng._bts[kind][0] if p]
            for kind in eng.kinds}
    if prompt_len + steps_before > WINDOW + 4:
        assert eng.window_pages_released > 0

    def lines(kind, pages):  # (pool, layer, page, line, width) on the host
        n = eng.kind_layers[kind]
        return [np.asarray(p).reshape(n, -1, 4, 32)[:, pages]
                for p in eng._kind_pools(kind)]

    want = {kind: lines(kind, held[kind]) for kind in eng.kinds}
    blob = eng.preempt(0)
    assert all(p.used_pages == 0 for p in eng.pools_by_kind.values()), \
        "preemption frees the victim's pages of both kinds"
    assert blob["pages"][0].shape == (1, LIMIT // 4, 4, 32)   # full: keys
    assert blob["pages"][2].shape == (3, HELD, 4, 32)         # window: keys
    # park another tenant on the freed pages so restore lands elsewhere
    eng.admit(1, rng.integers(0, 96, 10).astype(np.int32), 4)
    eng.restore(0, blob)
    for kind in eng.kinds:
        fresh = [int(p) for p in eng._bts[kind][0] if p]
        assert len(fresh) == len(held[kind])
        for got, w in zip(lines(kind, fresh), want[kind]):
            np.testing.assert_array_equal(got, w)
    while len(out) < 40:
        out.append(int(step_now(eng)[0]))
    _, _, _, alone = _engine()
    straight = [alone.admit(0, prompt, 40)]
    while len(straight) < 40:
        straight.append(int(step_now(alone)[0]))
    assert out == straight, "the restored stream is the uninterrupted one"
    assert _gaps(key, sz, prompt, out).max() <= GAP_TOL


def test_a_restore_that_one_kind_cannot_hold_takes_no_page_of_the_other():
    cfg, sz, key, eng = _engine(pages={"full": 72, "window": HELD + 2})
    rng = np.random.default_rng(3)
    eng.admit(0, rng.integers(0, 96, 30).astype(np.int32), 8)
    blob = eng.preempt(0)
    eng.admit(1, rng.integers(0, 96, 30).astype(np.int32), 8)
    free = eng.pools_by_kind["full"].free_pages
    with pytest.raises(PagePoolExhausted):
        eng.restore(0, blob)
    assert eng.pools_by_kind["full"].free_pages == free
    eng.release(1)
    eng.restore(0, blob)
    eng.release(0)
    assert all(p.used_pages == 0 for p in eng.pools_by_kind.values())


def test_the_scheduler_preempts_a_victim_of_both_kinds_and_stays_exact():
    """A window pool too small for every slot's launch at once: the
    scheduler evicts a victim (both kinds' pages) and restores it; tokens
    are those of an engine with room."""
    cfg, sz, key, eng = _engine(pages={"full": 72, "window": HELD + 6})
    sched = DecodeScheduler(eng, name="mellum-tight")
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (33, 29, 31)]
    try:
        reqs = [sched.submit(p, steps=20) for p in prompts]
        outs = [np.asarray(r.result(timeout=300)[0]) for r in reqs]
    finally:
        sched.close()
    assert eng.pool.preemptions >= 1
    for prompt, served in zip(prompts, outs):
        assert _gaps(key, sz, prompt, served).max() <= GAP_TOL
    assert all(p.used_pages == 0 for p in eng.pools_by_kind.values())


# -- what the engine reports by kind ---------------------------------------------

def test_bytes_and_pages_are_reported_by_kind():
    cfg, sz, key, eng = _engine()
    line = 2 * 32 * 4  # keys and values, 32 float32 values each
    assert eng.token_bytes == 4 * line
    full, window = (eng.pools_by_kind[k] for k in ("full", "window"))
    assert eng.pool is full and full.kind == "full"
    assert window.kind == "window" and window.name == full.name + ".window"
    assert full.page_bytes == 4 * 1 * line and window.page_bytes == 4 * 3 * line
    # 9 + 4 tokens: 4 pages of each kind; 200 tokens: a window layer never
    # holds more than its bound
    assert eng.projected_page_bytes(9, 4) == 4 * (full.page_bytes
                                                  + window.page_bytes)
    assert eng.projected_page_bytes(60, 30) == (
        23 * full.page_bytes + HELD * window.page_bytes)
    mem = eng.memory_bytes()
    assert mem["bytes"] == sum(int(p.nbytes) for p in eng._pools)
    assert mem["bytes"] == sum(k["bytes"] for k in mem["kinds"].values())
    assert mem["kinds"]["window"] == {
        "layers": 3, "pages_total": 24, "pages_used": 0,
        "page_bytes": window.page_bytes,
        "bytes": 25 * window.page_bytes}
    assert window.stats()["kind"] == "window"
    assert window.stats()["bytes_total"] == 24 * window.page_bytes


def test_a_step_counts_the_pages_it_reads_by_kind():
    from nnstreamer_tpu.obs import context

    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(1)
    eng.admit(0, rng.integers(0, 96, 41).astype(np.int32), 8)
    eng.admit(1, rng.integers(0, 96, 6).astype(np.int32), 8)
    before = dict(eng.counters())
    step_now(eng)
    span = [s for s in context.finished_spans()
            if s.name == "engine.step.prepare"][-1]
    # slot 0 sees 42 positions (11 pages; 12 of them in a window layer: the
    # pages of positions 30..41 are 7..10, 4 pages), slot 1 sees 7 (2 pages)
    assert span.attrs["pages_read_full"] == 11 + 2
    assert span.attrs["pages_read_window"] == 4 + 2
    assert span.attrs["pages_read"] == round((13 * 1 + 6 * 3) / 4)
    # the kernel copies the pages that hold a visible position and no
    # other, in a layer of either kind (ops.paged_attention.pages_fetched)
    assert span.attrs["pages_fetched_full"] == 13
    assert span.attrs["pages_fetched_window"] == 6
    assert span.attrs["pages_fetched"] == span.attrs["pages_read"]
    assert span.attrs["pages_padded"] == 3 * (LIMIT // 4)
    assert span.attrs["window_pages_released"] == eng.window_pages_released
    after = eng.counters()
    assert after["attn_pages_read_full"] - before["attn_pages_read_full"] == 13
    assert after["attn_pages_read_window"] \
        - before["attn_pages_read_window"] == 6
    assert after["attn_pages_fetched_window"] \
        - before["attn_pages_fetched_window"] == 6
    assert after["window_pages_released"] == eng.window_pages_released > 0


def test_the_programs_name_their_regions_by_layer_kind():
    cfg, sz, key, eng = _engine()
    S, NB = eng.slots, eng.blocks_per_slot
    i32 = jnp.int32
    text = eng._step.func.lower(
        eng.params, jnp.zeros((S, 1), i32), jnp.zeros((S,), i32),
        jnp.zeros((S,), bool), jnp.zeros((S, NB), i32),
        jnp.zeros((S, NB), i32), *eng._pools).as_text(debug_info=True)
    for scope in ("attn.window", "attn.full", "moe.route", "moe.experts",
                  "head"):
        assert scope in text, scope
