"""The Ouro family (ONE stack of layers that every token runs
``total_ut_steps`` times, a cache line for every pass of every layer, an exit
gate that says which pass's hidden state the head reads) through the paged
serving engine, against the benchmark's plain reference
(``benchmark/references/ouro_lm.py``: a full forward with no cache, float32
at ``highest``). CPU, small sizes, seeded weights; logits are compared, never
sampled tokens.

Sizes: four layers run four times (16 pass-layers a token), four heads of 8
over as many key/value heads, prompts that take one launch, several, and
several with a ragged last one (chunks of 8), a limit of 96. The weights'
std is 0.15 and not the benchmark's 0.02: at a hundredth of the published
widths the gate and the head would say the same of every token.

Tolerances. Everything here is float32 on the CPU, so program and reference
differ only by the order of float32 sums: logits of size 1 agree to a few
1e-6; the limits (2e-5 on logits, 1e-4 on the gap of a served token under
the reference's best) are the other families'.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest
from engine_util import spy_launches, step_now

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import harness  # noqa: E402
from benchmark.lib.weights import seed_key  # noqa: E402
from benchmark.references import ouro_lm as ref  # noqa: E402
from nnstreamer_tpu.models.families import family_of  # noqa: E402
from nnstreamer_tpu.models.lm_serving import _LMServingEntry  # noqa: E402
from nnstreamer_tpu.models.ouro import OuroConfig, OuroFamily  # noqa: E402
from nnstreamer_tpu.obs import context as obs_context  # noqa: E402
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine  # noqa: E402
from nnstreamer_tpu.serving.kv_pool import KVPagePool  # noqa: E402

LIMIT = 96
LAYERS, PASSES = 4, 4
SIZES = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=LAYERS,
    num_attention_heads=4, num_key_value_heads=4, head_dim=8,
    intermediate_size=64, total_ut_steps=PASSES, early_exit_threshold=1,
    layer_types=["full_attention"] * LAYERS, max_window_layers=LAYERS,
    sliding_window=None, use_sliding_window=False, rope_theta=1000000,
    rope_scaling=None, rms_norm_eps=1e-6, max_position_embeddings=LIMIT,
    tie_word_embeddings=False, hidden_act="silu", model_type="ouro",
    weight_std=0.15)
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4
ENGINE = dict(slots=3, page_size=4, chunk=8, share_prefixes=False)
GOLDEN = os.path.join(ROOT, "tests", "golden", "serving_programs.json")


def _model(seed=5, dtype=jnp.float32, **over):
    conf = {**SIZES, **over}
    cfg = OuroConfig.from_published(conf)
    sz = ref.sizes(conf)
    key = seed_key(seed)
    return cfg, sz, key, ref.program_params(key, sz, dtype)


def _entry(cfg, params):
    class Seeded(_LMServingEntry):
        def _shard_params(self, mesh):
            return params, False

    return Seeded(cfg)


def _engine(engine=None, **over):
    cfg, sz, key, params = _model(**over)
    return cfg, sz, key, _entry(cfg, params).make_continuous(
        **{**ENGINE, **(engine or {})})


def _sequence(prompt, served, width=LIMIT):
    """The tokens the served path saw, padded: the prompt, then every
    served token but the last."""
    n = len(served)
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :prompt.size] = prompt
    tokens[0, prompt.size:prompt.size + n - 1] = served[:-1]
    return tokens


def _reference_logits(key, sz, prompt, served):
    """Teacher-forced reference logits at the rows that produced each
    served token: (len(served), V)."""
    rows = (prompt.size - 1 + np.arange(len(served)))[None].astype(np.int32)
    return ref.logits_for(key, sz, _sequence(prompt, served), rows)["none"][0]


def _gaps(key, sz, prompt, served):
    served = np.asarray(served)
    exact = _reference_logits(key, sz, prompt, served)
    return exact.max(-1) - np.take_along_axis(exact, served[:, None], 1)[:, 0]


def _prompt(rng, n):
    return rng.integers(0, 96, n).astype(np.int32)


def _pools(eng):
    """The pools by pass-layer: ``(pass-layers, rows a layer, page, W)``."""
    n = PASSES * LAYERS
    return [np.asarray(p).reshape(n, -1, *p.shape[1:]).copy()
            for p in eng._pools]


# -- the family ----------------------------------------------------------------

def test_the_family_is_chosen_by_the_configurations_type_and_says_its_passes():
    cfg, _, _, _ = _model()
    fam = family_of(cfg)
    assert isinstance(fam, OuroFamily) and fam.name == "ouro"
    assert fam.passes == PASSES and fam.layer_kinds == ("full",) * LAYERS
    assert fam.window is None and fam.cache_lines == (32, 32)
    assert fam.state_lines == () and not fam.serves_verify
    assert fam.counters == tuple(f"exit_pass_{t}" for t in (1, 2, 3, 4))
    assert fam.attention_scopes == {"full": "attn.full"}
    # the error names the types of the table it dispatches on
    with pytest.raises(TypeError, match="TransformerConfig, DeepseekV3Config,"
                       " MellumConfig, JambaConfig, OuroConfig"):
        family_of(object())


def test_every_other_family_says_one_pass():
    from nnstreamer_tpu.models.families import _families

    others = [family for _, family in _families() if family is not OuroFamily]
    assert len(others) >= 4  # every family there is but this one
    for family in others:
        assert family.passes == 1, family
        assert not hasattr(family, "close_pass"), family


def test_the_published_keys_give_the_published_model():
    _, config = harness.find_cell(harness.load_benchmark(),
                                  "ouro_reasoning_saturated")
    cfg = OuroConfig.from_published(config)
    fam = OuroFamily(cfg)
    assert fam.passes == 4 and fam.layers == 48
    assert fam.cache_lines == (2048, 2048) and fam.chunk_heads == (16, 1)
    assert cfg.rope_theta == 1e6 and cfg.early_exit_threshold == 1
    sz = ref.sizes({**config, "max_position_embeddings": 65536})
    assert ref.parameters(sz) == 2_667_974_657
    # the program's own parameter tree holds that many
    shapes = jax.eval_shape(lambda k: ref.program_params(k, sz, jnp.bfloat16),
                            jax.random.key(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes)) == 2_667_974_657
    # a token keeps 4 x 48 x 2 lines of 2048 bfloat16 values; the engine's
    # geometry at two pages (the count does not depend on the pool's size)
    eng = PagedLMEngine(cfg, {"embed": jnp.zeros((1, 1), jnp.bfloat16)},
                        slots=1, page_size=16, pages=2, chunk=16,
                        share_prefixes=False)
    assert eng.token_bytes == 1_572_864 == 192 * 2 * 2048 * 2
    assert eng.kind_layers == {"full": 192} and eng.passes == 4
    assert eng._pools[0].shape == (192 * 3, 16, 2048)


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("use_sliding_window", True),
    ("layer_types", ["full_attention", "sliding_attention"] * 2),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("num_key_value_heads", 3), ("total_ut_steps", 0),
    ("early_exit_threshold", 1.5),
])
def test_a_key_the_block_does_not_implement_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=f"ouro.*{key}"):
        OuroConfig.from_published({**SIZES, key: value})


def test_speculative_decoding_is_refused_for_the_family_by_name():
    cfg, _, _, params = _model()
    with pytest.raises(NotImplementedError, match="_verify.*ouro"):
        _entry(cfg, params).make_continuous(draft="ngram", **ENGINE)


# -- the served path against the reference's full forward ----------------------

@pytest.mark.parametrize("threshold", [1, 0.5])
def test_chunked_prefill_then_decode_matches_the_reference_forward(threshold):
    cfg, sz, key, eng = _engine(early_exit_threshold=threshold)
    assert isinstance(eng, PagedLMEngine) and eng.family.name == "ouro"
    assert eng.kinds == ("full",) and eng.passes == PASSES
    assert eng.kind_layers == {"full": PASSES * LAYERS}
    chunks = spy_launches(eng)
    sched = DecodeScheduler(eng, name=f"ouro-{threshold}")
    rng = np.random.default_rng(0)
    # five launches with a ragged last one; one launch; six; two; one
    lengths = [(37, 30), (7, 24), (45, 40), (12, 9), (3, 50)]
    prompts = [_prompt(rng, n) for n, _ in lengths]
    try:
        reqs = [sched.submit(p, steps=s)
                for p, (_, s) in zip(prompts, lengths)]
        outs = [np.asarray(r.result(timeout=300)[0]) for r in reqs]
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
    assert eng.pool.used_pages == 0, "every page released at close"
    left = np.zeros((PASSES + 1,), np.int64)
    for prompt, served in zip(prompts, outs):
        assert _gaps(key, sz, prompt, served).max() <= GAP_TOL, \
            "a served token is not the reference's"
        # the pass at which the reference lets each position's logits go
        at = ref.forward(key, sz, _sequence(prompt, served))["none"][1]
        left += np.bincount(np.asarray(at)[0, :prompt.size + len(served) - 1],
                            minlength=PASSES + 1)
    # prefill logits, chunk by chunk, for the first prompt (alone in the
    # lane first: its chunks are the first five calls)
    prompt = prompts[0]
    full = ref.logits_for(
        key, sz, np.pad(prompt, (0, LIMIT - prompt.size))[None],
        np.arange(prompt.size, dtype=np.int32)[None])["none"][0]
    seen = 0
    for start, n_valid, scores in chunks[:5]:
        assert start == seen
        seen += n_valid
        if seen < prompt.size:
            assert scores is None, "only a prompt's last launch runs the head"
        else:
            np.testing.assert_allclose(scores, full[seen - 1],
                                       atol=LOGIT_TOL, rtol=0)
    assert seen == prompt.size
    assert eng.compile_count == 2, "one step and one chunk program"
    # the counts: every position the served path computed left at the
    # reference's pass (launches count their real rows, steps their live
    # slots; nothing here ends early, so no step's token is dropped)
    assert left[0] == 0
    got = [eng.layer_counts["step"][f"exit_pass_{t}"]
           + eng.layer_counts["chunk"][f"exit_pass_{t}"]
           for t in range(1, PASSES + 1)]
    assert got == list(left[1:])
    assert [snap[f"exit_pass_{t}"] for t in range(1, PASSES + 1)] == got
    if threshold == 1:
        assert got[:3] == [0, 0, 0], "at 1 every token leaves at the last"
    else:
        assert sum(n > 0 for n in got) >= 2, \
            "at 0.5 the toy's tokens leave at different passes"


def test_decode_steps_logits_match_the_reference_far_into_the_sequence():
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(2)
    prompt = _prompt(rng, 11)
    served = [eng.admit(0, prompt, 84)]
    for _ in range(83):
        served.append(int(step_now(eng)[0]))
    exact = _reference_logits(key, sz, prompt, np.asarray(served))
    assert (exact.argmax(-1) == np.asarray(served)).all()
    assert len(set(served)) > 4, "the toy model does not say one token"
    assert eng._pos[0] == 94


def test_grouped_query_heads_serve_too():
    cfg, sz, key, eng = _engine(num_key_value_heads=2)
    assert eng.family.cache_lines == (16, 16)
    assert eng.family.chunk_heads == (2, 2)
    rng = np.random.default_rng(3)
    prompt = _prompt(rng, 19)
    served = [eng.admit(1, prompt, 30)]
    for _ in range(29):
        served.append(int(step_now(eng)[1]))
    assert _gaps(key, sz, prompt, served).max() <= GAP_TOL


# -- a line for every pass of every layer --------------------------------------

@pytest.mark.parametrize("chunk", [4, 8, 12])
def test_a_prompt_in_one_launch_and_in_several_leaves_the_same_lines(chunk):
    """29 tokens in one launch of 32, and in launches of 4 (ragged last: 1),
    8 (5) and 12 (5): the same logits and, in every pass-layer, the same
    lines to the order of float32 sums."""
    rng = np.random.default_rng(4)
    prompt = _prompt(rng, 29)
    got = []
    for width in (32, chunk):
        cfg, sz, key, eng = _engine(engine={"chunk": width})
        chunks = spy_launches(eng)
        first = eng.admit(0, prompt, 4)
        assert len(chunks) == -(-29 // width)
        pages = [int(p) for p in eng._bt[0] if p]
        assert len(pages) == 8
        lines = [pool[:, pages].reshape(PASSES * LAYERS, 32, -1)[:, :29]
                 for pool in _pools(eng)]
        assert [c[2] is None for c in chunks] == [True] * (len(chunks) - 1) \
            + [False]
        got.append((first, chunks[-1][2], lines))
    (a_first, a_logits, a_lines), (b_first, b_logits, b_lines) = got
    assert a_first == b_first
    np.testing.assert_allclose(a_logits, b_logits, atol=LOGIT_TOL, rtol=0)
    for a, b in zip(a_lines, b_lines):
        assert (np.abs(a).max(axis=(1, 2)) > 0).all(), \
            "a pass-layer's rows were never written"
        np.testing.assert_allclose(a, b, atol=LOGIT_TOL, rtol=0)
    # no two passes of a layer hold the same lines: each wrote its own
    keys = a_lines[0].reshape(PASSES, LAYERS, 29, -1)
    for t in range(1, PASSES):
        assert np.abs(keys[t] - keys[t - 1]).max() > 1e-3


@pytest.mark.parametrize("leaves_at", [1, 2, 3])
def test_a_pass_reads_its_own_lines_and_no_later_passs(leaves_at):
    """With the gate's weight zero every ``lambda`` is a half, ``c_t`` is
    0.5, 0.75, 0.875, 1, and the threshold picks the pass every token
    leaves at. The logits of a launch over an earlier launch's lines then
    hang on passes ``1 .. leaves_at`` alone: poison the rows of every later
    pass and nothing changes; poison the rows of pass ``leaves_at`` and
    they do."""
    threshold = {1: 0.4, 2: 0.7, 3: 0.8}[leaves_at]
    rng = np.random.default_rng(6)
    prompt = _prompt(rng, 16)

    def second_launch(poisoned):
        cfg, sz, key, params = _model(early_exit_threshold=threshold)
        params = {**params, "gate_w": jnp.zeros_like(params["gate_w"])}
        eng = _entry(cfg, params).make_continuous(**ENGINE)
        chunks = spy_launches(eng)
        eng.admit_start(0, prompt, 4)
        eng.prefill_tick()                      # positions 0..7
        rows = eng._pools[0].shape[0] // (PASSES * LAYERS)
        for t in poisoned:                      # 0-based passes
            lo, hi = t * LAYERS * rows, (t + 1) * LAYERS * rows
            eng._pools = tuple(p.at[lo:hi].set(7.0) for p in eng._pools)
        eng.prefill_tick()                      # positions 8..15 read them
        counts = dict(eng.layer_counts["chunk"])
        return chunks[1][2], counts

    clean, counts = second_launch(())
    assert counts[f"exit_pass_{leaves_at}"] == 16 == sum(counts.values())
    later, _ = second_launch(range(leaves_at, PASSES))
    np.testing.assert_array_equal(later, clean)
    own, _ = second_launch((leaves_at - 1,))
    assert np.abs(own - clean).max() > 1e-3


def test_a_token_keeps_a_line_for_every_pass_of_every_layer():
    cfg, sz, key, eng = _engine()
    per_token = PASSES * LAYERS * 2 * 32 * 4     # float32 here
    assert eng.token_bytes == per_token
    assert eng.page_bytes == 4 * per_token == eng.pool.page_bytes
    assert eng.projected_page_bytes(10, 7) == 5 * 4 * per_token
    assert [p.shape for p in eng._pools] == [
        (PASSES * LAYERS * (3 * 24 + 1), 4, 32)] * 2
    mem = eng.memory_bytes()
    assert mem["token_bytes"] == per_token
    assert mem["kinds"]["full"]["layers"] == PASSES * LAYERS
    assert mem["kinds"]["full"]["page_bytes"] == 4 * per_token
    assert mem["bytes"] == eng.cache_bytes == sum(
        int(p.nbytes) for p in eng._pools) == (3 * 24 + 1) * 4 * per_token
    stats = eng.pool.stats()
    assert stats["token_bytes"] == per_token
    assert stats["page_bytes"] == 4 * per_token
    assert stats["bytes_total"] == 72 * 4 * per_token
    # the allocator counts pages in the bytes it is given and knows nothing
    # of passes or layers
    pool = KVPagePool(5, 4, token_bytes=per_token)
    try:
        assert pool.page_bytes == 4 * per_token
        assert pool.stats()["bytes_total"] == 5 * 4 * per_token
    finally:
        pool.close()
    rng = np.random.default_rng(7)
    eng.admit(0, _prompt(rng, 10), 4)
    assert eng.pool.stats()["bytes_used"] == 3 * 4 * per_token


def test_preempt_then_other_traffic_in_the_slot_then_restore_is_exact():
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(8)
    prompt = _prompt(rng, 13)
    served = [eng.admit(0, prompt, 24)]
    for _ in range(5):
        served.append(int(step_now(eng)[0]))
    held = [int(p) for p in eng._bt[0] if p]
    want = [pool[:, held] for pool in _pools(eng)]
    blob = eng.preempt(0)
    assert [b.shape for b in blob["pages"]] == [
        (PASSES * LAYERS, 24, 4, 32)] * 2, "a blob holds every pass-layer"
    assert eng.pool.used_pages == 0
    # another sequence in the slot, over the freed pages
    other = _prompt(rng, 21)
    eng.admit(0, other, 6)
    for _ in range(3):
        step_now(eng)
    eng.release(0)
    # a third holds the first pages while the first comes back elsewhere
    eng.admit(1, _prompt(rng, 9), 4)
    eng.restore(0, blob)
    fresh = [int(p) for p in eng._bt[0] if p]
    assert len(fresh) == len(held) and fresh != held
    for w, pool in zip(want, _pools(eng)):
        np.testing.assert_array_equal(pool[:, fresh], w)
    while len(served) < 24:
        served.append(int(step_now(eng)[0]))
    assert _gaps(key, sz, prompt, served).max() <= GAP_TOL
    exact = _reference_logits(key, sz, prompt, np.asarray(served))
    assert (exact.argmax(-1) == np.asarray(served)).all()


def test_a_copy_on_write_copies_every_pass_layers_rows():
    # identical page-aligned prompts: slot 1 maps slot 0's pages, and the
    # recomputed last prompt position copies the last of them before its
    # write lands: in every pass-layer the copy starts as the page it was
    # copied from, and the sibling's pages keep their bytes
    cfg, sz, key, eng = _engine(engine={"share_prefixes": True})
    rng = np.random.default_rng(9)
    prompt = _prompt(rng, 16)
    first = eng.admit(0, prompt, 6)
    shared = [int(p) for p in eng._bt[0, :4]]
    before = _pools(eng)
    assert eng.admit(1, prompt, 6) == first
    assert eng.pool.stats()["cow_copies_total"] >= 1
    assert [int(p) for p in eng._bt[1, :3]] == shared[:3]
    copy = int(eng._bt[1, 3])
    assert copy != shared[3]
    for b, a in zip(before, _pools(eng)):
        assert (np.abs(a[:, copy, :3]).max(axis=(1, 2)) > 0).all()
        np.testing.assert_array_equal(a[:, copy, :3], b[:, shared[3], :3])
        np.testing.assert_array_equal(a[:, shared], b[:, shared])
    a, b = [first], [first]
    for _ in range(5):
        tok = step_now(eng)
        a.append(int(tok[0]))
        b.append(int(tok[1]))
    assert a == b
    assert _gaps(key, sz, prompt, a).max() <= GAP_TOL


def test_several_slots_at_different_depths_share_a_step():
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(10)
    prompts = [_prompt(rng, n) for n in (5, 33, 18)]
    served = [[eng.admit(0, prompts[0], 20)], [], []]
    for _ in range(4):
        served[0].append(int(step_now(eng)[0]))
    served[1].append(eng.admit(1, prompts[1], 20))
    for _ in range(3):
        tok = step_now(eng)
        served[0].append(int(tok[0]))
        served[1].append(int(tok[1]))
    served[2].append(eng.admit(2, prompts[2], 20))
    for _ in range(6):
        tok = step_now(eng)
        for s in range(3):
            served[s].append(int(tok[s]))
    assert [len(s) for s in served] == [14, 10, 7]
    for prompt, got in zip(prompts, served):
        assert _gaps(key, sz, prompt, got).max() <= GAP_TOL
    # a step counts its live slots once, at the pass they left
    assert sum(eng.layer_counts["step"].values()) == 4 + 2 * 3 + 3 * 6


# -- tracing ------------------------------------------------------------------

def test_spans_and_counters_carry_the_passes():
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(11)
    eng.admit(0, _prompt(rng, 13), 8)
    for _ in range(3):
        step_now(eng)
    spans = obs_context.finished_spans()
    for name in ("engine.step.prepare", "engine.chunk.prepare"):
        span = [s for s in spans if s.name == name][-1]
        assert span.attrs["passes"] == PASSES
        assert span.attrs["pass_layers"] == PASSES * LAYERS
    chunk = [s for s in spans if s.name == "engine.chunk.prepare"][-1]
    # a launch's positions read count every pass-layer's
    assert chunk.attrs["ctx_read"] % (PASSES * LAYERS) == 0
    assert chunk.attrs["ctx_padded"] == PASSES * LAYERS * LIMIT
    pull = [s for s in spans if s.name == "engine.step.pull"
            and "exit_pass_4" in s.attrs][-1]
    assert pull.attrs["exit_pass_4"] == 1 and pull.attrs["exit_pass_1"] == 0
    assert eng.layer_counts["step"]["exit_pass_4"] == 3
    assert eng.layer_counts["chunk"]["exit_pass_4"] == 13
    assert eng.counters()["exit_pass_4"] == 16
    S, NB = eng.slots, eng.blocks_per_slot
    i32 = jnp.int32
    text = eng._step.func.lower(
        eng.params, jnp.zeros((S, 1), i32), jnp.zeros((S,), i32),
        jnp.zeros((S,), bool), jnp.zeros((S, NB), i32),
        *eng._pools).as_text(debug_info=True)
    for scope in ("attn.full", "mlp", "loop.exit", "head"):
        assert scope in text, scope
    # the passes are a loop of the program, not four copies of the stack
    assert text.count("stablehlo.while") >= 1
    assert text.count("stablehlo.dot_general") < 2 * LAYERS * 8


# -- the stored form -----------------------------------------------------------

def _as_they_come(monkeypatch):
    """The family as it served before it kept a stored form: the tree as
    given, the two products over ``(hidden, heads * head_dim)``. The oracle
    of the cases below, kept here and nowhere in the program."""
    from nnstreamer_tpu.models.mellum import rotate_half
    from nnstreamer_tpu.models.ouro import rms_norm

    def project(self, blk, x, pos, kind="full"):
        cfg = self.cfg
        H, KV, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        freq, factor = self._rope
        h = rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
        q = (h @ blk["wq"]).reshape(*x.shape[:2], H, Dh)
        k = (h @ blk["wk"]).reshape(*x.shape[:2], KV, Dh)
        q = rotate_half(q, pos[..., None], freq, factor)
        k = rotate_half(k, pos[..., None], freq, factor)
        return q, (k.reshape(*x.shape[:2], KV * Dh), h @ blk["wv"])

    monkeypatch.setattr(OuroFamily, "project", project)
    monkeypatch.setattr(OuroFamily, "stored", lambda self, params: params)


def _serve(eng, prompts, steps):
    """Every prompt through a scheduler: the tokens, and each launch's
    ``(start, n_valid, its last row's scores or None)``."""
    chunks = spy_launches(eng)
    sched = DecodeScheduler(eng, name="ouro-stored")
    try:
        reqs = [sched.submit(p, steps=steps) for p in prompts]
        outs = [np.asarray(r.result(timeout=300)[0]) for r in reqs]
    finally:
        sched.close()
    return outs, chunks


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_the_stored_form_serves_what_the_given_form_served(kv_heads,
                                                           monkeypatch):
    cfg = OuroConfig.from_published(
        {**SIZES, "num_key_value_heads": kv_heads})
    fam = OuroFamily(cfg)
    # the matrices at the file's std of 0.15, so that tokens differ
    params = jax.tree_util.tree_map(
        lambda a: a * 7.5 if a.ndim == 2 else a, fam.init_params(3))
    given = jax.tree_util.tree_leaves_with_path(params)
    rng = np.random.default_rng(4)
    prompts = [_prompt(rng, n) for n in (37, 7, 21, 12)]

    eng = _entry(cfg, params).make_continuous(**ENGINE)
    span = [s for s in obs_context.startup_spans()
            if s.name == "setup.engine"][-1]
    outs, chunks = _serve(eng, prompts, 24)

    # the tree the engine keeps: the two matrices a layer transposed, every
    # other leaf the caller's own object; the caller's tree as it was
    stored = jax.tree_util.tree_leaves_with_path(eng.params)
    assert [path for path, _ in stored] == [path for path, _ in given]
    relaid = 0
    for (path, was), (_, now) in zip(given, stored):
        if jax.tree_util.keystr(path)[-6:] in ("['wq']", "['wk']"):
            assert now.shape == was.shape[::-1], path
            assert now.shape[1] == cfg.hidden_size
            np.testing.assert_array_equal(np.asarray(now),
                                          np.asarray(was).T)
            relaid += now.nbytes
        else:
            assert now is was, path
        assert not was.is_deleted(), path
    assert eng.relaid == {"matrices": 2 * LAYERS, "bytes": relaid}
    assert span.attrs["relaid_matrices"] == 2 * LAYERS
    assert span.attrs["relaid_bytes"] == relaid
    assert eng.param_bytes == sum(a.nbytes for _, a in given)

    _as_they_come(monkeypatch)
    old = _entry(cfg, params).make_continuous(**ENGINE)
    assert old.relaid == {"matrices": 0, "bytes": 0}
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(old.params),
        jax.tree_util.tree_leaves(params)))
    old_outs, old_chunks = _serve(old, prompts, 24)
    for served, before in zip(outs, old_outs):
        assert served.tolist() == before.tolist()
    assert len({int(t) for o in outs for t in o}) > 4, \
        "the toy model does not say one token"
    assert len(chunks) == len(old_chunks)
    for (start, n, scores), (start0, n0, scores0) in zip(chunks, old_chunks):
        assert (start, n) == (start0, n0)
        assert (scores is None) == (scores0 is None)
        if scores is not None:
            np.testing.assert_allclose(scores, scores0, atol=LOGIT_TOL,
                                       rtol=0)
    assert sum(scores is not None for _, _, scores in chunks) == len(prompts)


def _single_pass_families():
    from nnstreamer_tpu.models.families import _families

    return [pytest.param(config_type, family, id=family.name)
            for config_type, family in _families()
            if family is not OuroFamily]


@pytest.mark.parametrize("config_type, family", _single_pass_families())
def test_a_family_with_nothing_to_re_lay_keeps_the_tree_it_was_given(
        config_type, family):
    cfg = config_type()
    fam = family(cfg)
    params = fam.init_params(0)
    assert fam.stored(params) is params
    entry = _entry(cfg, params)
    eng = entry.make_continuous(slots=2, page_size=4, chunk=8,
                                share_prefixes=False)
    span = [s for s in obs_context.startup_spans()
            if s.name == "setup.engine"][-1]
    try:
        assert eng.params is params
        assert eng.relaid == {"matrices": 0, "bytes": 0}
        assert span.attrs["relaid_matrices"] == 0
        assert span.attrs["relaid_bytes"] == 0
    finally:
        eng.close()


def test_a_probe_without_layers_has_nothing_to_re_lay():
    cfg, _, _, _ = _model()
    stub = {"embed": jnp.zeros((1, 1), jnp.float32)}
    assert OuroFamily(cfg).stored(stub) is stub


# -- a family with one pass takes no loop ----------------------------------------

def _rehearsal_programs():
    """``{"<config>.<program>": lowered text}`` of ``_step`` and
    ``_prefill_chunk`` for the four configurations whose families run
    their stack once, at their files' rehearsal sizes, lowered here (the
    CPU, the plain attention form) from shapes alone."""
    from nnstreamer_tpu.models.deepseek_v3 import DeepseekV3Config
    from nnstreamer_tpu.models.jamba import JambaConfig
    from nnstreamer_tpu.models.mellum import MellumConfig
    from nnstreamer_tpu.models.transformer import TransformerConfig

    def gpt(c):
        return TransformerConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            heads=c["num_attention_heads"], layers=c["num_hidden_layers"],
            mlp_mult=c["ffn_dim"] // c["hidden_size"],
            max_seq=c["max_position_embeddings"])

    models = {"lm_serving": gpt,
              "lm_serving_moe_mla": DeepseekV3Config.from_published,
              "lm_serving_moe_window": MellumConfig.from_published,
              "lm_serving_ssm": JambaConfig.from_published}
    out = {}
    for entry in harness.load_benchmark()["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as fh:
            config = json.load(fh)
        config = {**config, **config["rehearsal"]}
        if config["kind"] not in models:
            continue
        dtype = jnp.dtype(config["serve_dtype"])
        reference = harness.reference_for(config)
        eng = PagedLMEngine(models[config["kind"]](config),
                            {"embed": jnp.zeros((1, 1), dtype)},
                            **config["engine"])
        assert eng.passes == 1

        def like(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        params = jax.eval_shape(lambda k: reference.program_params(
            k, reference.sizes(config), dtype), jax.random.key(0))
        S, NB, C, K = (eng.slots, eng.blocks_per_slot, eng.chunk,
                       len(eng.kinds))
        i32 = jnp.int32
        pools = [like(p) for p in eng._pools]
        states = [like(s) for s in eng._states]
        scalar = jax.ShapeDtypeStruct((), i32)
        programs = {
            "_step": (jax.ShapeDtypeStruct((S, 1), i32),
                      jax.ShapeDtypeStruct((S,), i32),
                      jax.ShapeDtypeStruct((S,), jnp.bool_),
                      *[jax.ShapeDtypeStruct((S, NB), i32)] * K, *pools,
                      *states),
            "_prefill_chunk": (jax.ShapeDtypeStruct((C,), i32), scalar,
                               scalar,
                               *[jax.ShapeDtypeStruct((NB,), i32)] * K,
                               *pools, *([scalar] if states else []),
                               *states)}
        for name, args in programs.items():
            out[f"{entry['name']}.{name}"] = getattr(eng, name).func.lower(
                params, *args).as_text()
        eng.close()
    return out


def test_a_family_with_one_pass_lowers_to_the_parents_program_text():
    """The four families that run their stack once take no loop: their two
    programs lower, at the rehearsal sizes, to the text they lowered to at
    the commit before the loop existed (PR 42's tree; the SHA-256 of each
    text is in ``tests/golden/serving_programs.json``, written there by
    ``python tests/test_ouro_serving.py <tree>`` run on that tree). The same
    input to the same compiler: no program of theirs changed. A PR that
    means to change one of these programs writes the file anew and says so:
    PR 44 did for the four ``_prefill_chunk`` (a launch writes its lines a
    page at a time) and PR 49 again (a launch runs no head: it hands back
    its rows as the stack left them, of which ``_seed`` behind a prompt's
    last launch makes the first token on the device; no ``f32[C, vocab]``
    is made and no argument joined the program); the four
    ``_step`` hashes are still PR 42's.
    """
    with open(GOLDEN) as fh:
        golden = json.load(fh)
    texts = _rehearsal_programs()
    assert sorted(texts) == sorted(golden["sha256"]) and len(texts) == 8
    changed = [name for name, text in texts.items()
               if hashlib.sha256(text.encode()).hexdigest()
               != golden["sha256"][name]]
    assert not changed, f"programs that no longer lower to {golden['tree']}'s"


if __name__ == "__main__":
    # python tests/test_ouro_serving.py "<what tree this is>": the golden file
    found = {name: hashlib.sha256(text.encode()).hexdigest()
             for name, text in _rehearsal_programs().items()}
    json.dump({"tree": sys.argv[1], "jax": jax.__version__,
               "sha256": found}, sys.stdout, indent=1, sort_keys=True)
    print()
