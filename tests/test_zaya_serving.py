"""The ZAYA family (attention in a compressed latent whose projection keeps
a state a *slot* beside the lines a *token* keeps, a top-1 expert layer
behind an MLP router whose activations go down the stack, a residual merge
that scales and shifts both operands) through the paged serving engine,
against the benchmark's plain reference (``benchmark/references/zaya_lm.py``:
a full forward with no cache and no state, the convolutions shifted copies of
the whole sequence, float32 at ``highest``). CPU, small sizes, seeded
weights; logits are compared, never sampled tokens.

Sizes: four layers (layer 0 without ``(b_x, s_x)`` and ``gamma``, three with
them), four query heads over two key heads of 16, four experts behind a
router of width 8, a limit of 96; prompts that take one launch, several, and
several with a ragged last one (chunks of 8). The weights' std is 0.5 and not
the benchmark's 0.02: at a hundredth of the published widths the layers
would add nothing to the embedding and the tied head would repeat the last
token whatever they did.

Tolerances. Everything here is float32 on the CPU, so program and reference
differ only by the order of float32 sums; at this std the logits are of size
4 and agree to about 1e-5 (the other families' are of size 1 and agree to a
few 1e-6): 5e-5 on logits and 2e-4 on the gap of a served token under the
reference's best leave a factor of four and more above what is seen, and lie
a hundred times under what a dropped mechanism moves (the tests at the end).
A slot's kept line (values up to 8 at this std) agrees to 2e-5 + 1e-5
relative.
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
from benchmark.references import zaya_lm as ref  # noqa: E402
from nnstreamer_tpu.models.families import PlainStack, family_of  # noqa: E402
from nnstreamer_tpu.models.lm_serving import _LMServingEntry  # noqa: E402
from nnstreamer_tpu.models.zaya import ZayaConfig, ZayaFamily  # noqa: E402
from nnstreamer_tpu.obs import context as obs_context  # noqa: E402
from nnstreamer_tpu.parallel import moe_dropless  # noqa: E402
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine  # noqa: E402

LIMIT = 96
SIZES = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=16, num_experts=4, num_experts_per_tok=1,
    router_hidden_size=8, cca_time0=2, cca_time1=2,
    partial_rotary_factor=0.5,
    rope_parameters={"hybrid": {"partial_rotary_factor": 0.5,
                                "rope_theta": 10000.0,
                                "rope_type": "default"}},
    layer_types=["hybrid"] * 8, sliding_window=None, rms_norm_eps=1e-5,
    max_position_embeddings=LIMIT, tie_word_embeddings=True,
    attention_bias=False, lm_head_bias=False, hidden_act="silu",
    weight_std=0.5)
LINE = 2 * (4 + 2) * 16 + 16   # [p ; a ; shifted value] a slot and layer
LOGIT_TOL = 5e-5
GAP_TOL = 2e-4
ENGINE = dict(slots=3, page_size=4, chunk=8, share_prefixes=False)


def _model(seed=5, dtype=jnp.float32, **over):
    conf = {**SIZES, **over}
    cfg = ZayaConfig.from_published(conf)
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


def _reference_logits(key, sz, prompt, served, edit=None):
    """Teacher-forced reference logits at the rows that produced each
    served token: (len(served), V)."""
    n = len(served)
    tokens = np.zeros((1, LIMIT), np.int32)
    tokens[0, :prompt.size] = prompt
    tokens[0, prompt.size:prompt.size + n - 1] = served[:-1]
    rows = (prompt.size - 1 + np.arange(n))[None].astype(np.int32)
    return ref.logits_for(key, sz, tokens, rows, edit=edit)["none"][0]


def _gaps(key, sz, prompt, served, edit=None):
    served = np.asarray(served)
    exact = _reference_logits(key, sz, prompt, served, edit)
    return exact.max(-1) - np.take_along_axis(exact, served[:, None], 1)[:, 0]


def _lines(eng, slot):
    return [np.asarray(s[:, slot]).copy() for s in eng._states]


def _prompt(rng, n):
    return rng.integers(0, 96, n).astype(np.int32)


def _serve(eng, slot, prompt, steps):
    served = [eng.admit(slot, prompt, steps)]
    for _ in range(steps - 1):
        served.append(int(step_now(eng)[slot]))
    return served


# -- the family ----------------------------------------------------------------

def test_the_family_is_chosen_by_the_configurations_type_and_says_what_it_keeps():
    cfg, _, _, _ = _model()
    fam = family_of(cfg)
    assert isinstance(fam, ZayaFamily) and fam.name == "zaya"
    assert fam.layer_kinds == ("full",) * 4 and fam.window is None
    assert fam.cache_lines == (32, 32) and fam.chunk_heads == (2, 2)
    assert fam.state_lines == () and fam.slot_lines == (((LINE,), "float32"),)
    assert fam.passes == 1 and fam.drafts == 0
    assert fam.counters == moe_dropless.COUNTERS and not fam.serves_verify
    assert fam.expert_slots == 4 * 4
    # 12 stacked rows a slot fit one tile of lines: whole lines, by the rule
    assert not fam.step_by_head(1)


def test_the_six_elder_families_keep_the_contracts_defaults():
    from nnstreamer_tpu.models.families import _families

    elder = [family for _, family in _families() if family is not ZayaFamily]
    assert len(elder) == 6
    for family in elder:
        assert issubclass(family, PlainStack)
        assert family.slot_lines == ()
        for default in ("merge", "open_stack", "project_slot", "ffn_carry"):
            assert getattr(family, default) is getattr(PlainStack, default)
    x, y = jnp.arange(3.0), jnp.ones(3)
    np.testing.assert_array_equal(PlainStack().merge(None, x, y, "ffn"),
                                  x + y)

    class Plain(PlainStack):  # the defaults hand on what they are given
        def project(self, blk, x, pos, kind):
            return "q", ("k", "v")

        def ffn(self, blk, x, live):
            return "y", None

    plain = Plain()
    assert plain.open_stack(None, x) is None
    assert plain.project_slot(None, x, None, "full", (), None) == (
        "q", ("k", "v"), ())
    assert plain.ffn_carry(None, x, None, "carry") == ("y", None, "carry")


def test_the_published_keys_give_the_published_shapes():
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "zaya1_8b_pp2_l20.json")) as fh:
        conf = json.load(fh)
    cfg = ZayaConfig.from_published(conf)
    fam = family_of(cfg)
    assert fam.layers == 20 and len(cfg.layer_types) == 40
    assert fam.cache_lines == (256, 256) and cfg.packed_width == 1280
    assert cfg.rotary_dim == 64 and cfg.rope()["rope_theta"] == 5000000
    assert fam.slot_lines == (((2688,), "float32"),)
    assert fam.chunk_heads == (2, 4) and fam.expert_slots == 20 * 16


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("attention_bias", True),
    ("lm_head_bias", True), ("hidden_act", "gelu"),
    ("layer_types", ["hybrid", "hybrid_sliding"] * 2),
    ("sliding_window", 4096), ("cca_time0", 4), ("num_experts_per_tok", 2),
    ("num_key_value_heads", 4), ("partial_rotary_factor", 0.45)])
def test_a_key_the_block_does_not_implement_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError,
                       match=key.replace("cca_time0", "cca_time0/cca_time1")):
        ZayaConfig.from_published({**SIZES, key: value})


# -- what the engine refuses, each by the kind of state it is about ------------

def test_prefix_sharing_is_refused_for_a_state_in_the_attention_layers():
    cfg, _, _, params = _model()
    with pytest.raises(NotImplementedError,
                       match="prefix sharing.*zaya.*the state a slot keeps "
                             "in its attention layers.*share_prefixes"):
        PagedLMEngine(cfg, params, slots=2, page_size=4, chunk=8)


def test_prefix_sharing_is_refused_for_state_layers_by_their_own_name():
    from benchmark.references import jamba_lm
    from nnstreamer_tpu.models.jamba import JambaConfig

    conf = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=1, intermediate_size=64,
        attn_layer_period=4, attn_layer_offset=2, expert_layer_period=2,
        expert_layer_offset=1, num_experts=1, num_experts_per_tok=1,
        mamba_expand=2, mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=4,
        mamba_conv_bias=True, mamba_proj_bias=False, rms_norm_eps=1e-6,
        max_position_embeddings=LIMIT, tie_word_embeddings=True,
        sliding_window=None, hidden_act="silu")
    params = jamba_lm.program_params(seed_key(1), jamba_lm.sizes(conf),
                                     jnp.float32)
    with pytest.raises(NotImplementedError) as refused:
        PagedLMEngine(JambaConfig.from_published(conf), params, slots=2,
                      page_size=4, chunk=8)
    assert "its state layers' state as it was" in str(refused.value)
    assert "attention layers" not in str(refused.value)


def test_several_passes_are_refused_for_a_family_that_keeps_a_state(
        monkeypatch):
    cfg, _, _, params = _model()
    monkeypatch.setattr(ZayaFamily, "passes", 2)
    with pytest.raises(NotImplementedError,
                       match="zaya.*2 times a token and keeps the state a "
                             "slot keeps in its attention layers"):
        PagedLMEngine(cfg, params, **ENGINE)


def test_a_drafting_family_that_keeps_a_state_is_refused(
        monkeypatch):
    cfg, _, _, params = _model()
    monkeypatch.setattr(ZayaFamily, "drafts", 1)
    with pytest.raises(NotImplementedError) as refused:
        PagedLMEngine(cfg, params, **ENGINE)
    said = str(refused.value)
    assert "zaya family drafts 1 tokens a pass" in said
    assert "keeps the state a slot keeps in its attention layers" in said


def test_speculative_decoding_is_refused_for_the_family_by_name():
    cfg, _, _, params = _model()
    with pytest.raises(NotImplementedError,
                       match="zaya.*roll.*the state a slot keeps in its "
                             "attention layers"):
        _entry(cfg, params).make_continuous(draft="ngram", **ENGINE)


# -- the served path against the reference's full forward ----------------------

def test_chunked_prefill_then_decode_matches_the_reference_forward():
    cfg, sz, key, eng = _engine()
    assert isinstance(eng, PagedLMEngine) and eng.family.name == "zaya"
    assert eng.kinds == ("full",) and eng.state_layers == 0
    assert eng.slot_layers == 4 and eng.kind_layers == {"full": 4}
    assert [s.shape for s in eng._states] == [(4, 3, LINE)]
    assert eng._states[0].dtype == jnp.float32
    chunk_scores = spy_launches(eng)
    sched = DecodeScheduler(eng, name="zaya-a")
    rng = np.random.default_rng(0)
    # five launches with a ragged last one; one launch; six; two; one row
    lengths = [(37, 30), (7, 24), (45, 40), (12, 9), (1, 50)]
    prompts = [_prompt(rng, n) for n, _ in lengths]
    try:
        reqs = [sched.submit(p, steps=s)
                for p, (_, s) in zip(prompts, lengths)]
        outs = [np.asarray(r.result(timeout=300)[0]) for r in reqs]
        snap = sched.metrics_snapshot()
    finally:
        sched.close()
    assert eng.pool.used_pages == 0, "every page released at close"
    for prompt, served in zip(prompts, outs):
        assert _gaps(key, sz, prompt, served).max() <= GAP_TOL, \
            "a served token is not the reference's"
    assert len({int(t) for out in outs for t in out}) > 20, \
        "the toy model does not repeat itself"
    # the launches of the first prompt, and its last row's scores (alone in
    # the lane first: its chunks are the first five calls)
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
    # the state is a kind of cache of its own in the snapshot
    assert snap["state"]["layers"] == snap["state"]["attention_layers"] == 4
    assert snap["state"]["bytes"] == 3 * snap["state"]["slot_bytes"] \
        == 3 * 4 * LINE * 4
    assert 0 < snap["state_slots_live"] <= snap["state_slots"]
    # the expert layers counted: one assignment a live row and layer
    assert snap["moe_assignments"] > 0
    assert snap["moe_experts_touched"] <= snap["moe_expert_slots"]


def test_decode_steps_logits_match_the_reference_far_into_the_sequence():
    """Ninety positions through the kept line: a projection that drifted
    from the sequence's own rows would show in the later tokens."""
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(2)
    prompt = _prompt(rng, 11)
    served = _serve(eng, 0, prompt, 84)
    assert _gaps(key, sz, prompt, served).max() <= GAP_TOL
    assert eng._pos[0] == 94


# -- the state a slot keeps in the attention layers ---------------------------

@pytest.mark.parametrize("chunk", [4, 7, 8, 16])
def test_a_prompt_cut_at_every_offset_leaves_what_one_launch_leaves(chunk):
    """29 tokens in one launch of 32, and in launches of 4 (cuts at 4, 8,
    12, ...: every offset mod 3, the convolutions' reach; ragged last: one
    row, shorter than the reach), 7 (rows written one at a time; cuts at 7,
    14, 21, 28), 8 and 16: the same scores of the last row and the same
    line a layer to the order of float32 sums."""
    cfg, sz, key, params = _model()
    rng = np.random.default_rng(4)
    prompt = _prompt(rng, 29)

    def ingest(chunk):
        eng = _entry(cfg, params).make_continuous(**{**ENGINE,
                                                     "chunk": chunk})
        launches = spy_launches(eng)
        first = eng.admit(2, prompt, 8)
        assert [scores is None for _, _, scores in launches] == \
            [True] * (len(launches) - 1) + [False]
        return launches, _lines(eng, 2), first, eng

    whole, whole_line, first, _ = ingest(32)
    launches, line, again, eng = ingest(chunk)
    cuts = {start % 3 for start, _, _ in launches[1:]}
    assert cuts == ({0, 1, 2} if chunk in (4, 7, 8) else {1})  # 16: one cut
    assert eng._lane[2][1] == -(-29 // chunk) and again == first
    np.testing.assert_allclose(launches[-1][2], whole[-1][2],
                               atol=LOGIT_TOL, rtol=0)
    for got, want in zip(line, whole_line):
        assert np.abs(want).max() > 0.01
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # and it is the state the reference's rows say: the last packed row of
    # layer 0 is the last token's latent
    w = jax.tree_util.tree_map(np.asarray, params["blocks"][0])
    x = np.asarray(params["embed"])[prompt[-1]]
    h = x / np.sqrt((x * x).mean() + 1e-5)
    np.testing.assert_allclose(
        line[0][0, :96], np.concatenate([h @ w["wq"], h @ w["wk"]]),
        atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(line[0][0, 192:], h @ w["wv2"],
                               atol=2e-5, rtol=1e-5)


def test_a_slot_reused_after_release_starts_from_zero():
    """Serve A, release, serve B in the same slot = B alone: the launch
    that starts a sequence zeroes the rows, whatever the slot held."""
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(6)
    a, b = _prompt(rng, 23), _prompt(rng, 13)
    _serve(eng, 1, a, 12)
    held = _lines(eng, 1)
    eng.release(1)
    for got, was in zip(_lines(eng, 1), held):
        np.testing.assert_array_equal(got, was)  # release moves no state
        assert np.abs(was).max() > 0.01
    served = _serve(eng, 1, b, 20)
    _, _, _, fresh = _engine()
    alone = _serve(fresh, 1, b, 20)
    assert served == alone
    for got, want in zip(_lines(eng, 1), _lines(fresh, 1)):
        np.testing.assert_array_equal(got, want)
    assert _gaps(key, sz, b, served).max() <= GAP_TOL


def test_a_slot_that_sits_out_a_step_and_a_padded_row_move_no_byte():
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(7)
    ragged = _prompt(rng, 21)
    eng.admit(0, _prompt(rng, 10), 30)
    eng.admit(2, _prompt(rng, 5), 30)
    step_now(eng)
    eng.release(2)                       # slot 2 is dead and holds a state
    eng.admit_start(1, ragged, 9)        # slot 1 is mid-prefill
    assert eng.prefill_tick() == []
    before = [np.asarray(s).copy() for s in eng._states]
    step_now(eng)                        # only slot 0 is live
    after = [np.asarray(s) for s in eng._states]
    for was, now in zip(before, after):
        for slot in (1, 2):
            np.testing.assert_array_equal(now[:, slot], was[:, slot])
        assert (now[:, 0] != was[:, 0]).any()
    # a launch moves its own slot's rows and no other's; its padded rows
    # (21 = 8 + 8 + 5: three of the last launch's eight) move nothing: the
    # state after it is the state after the 21 real tokens alone
    before = after
    assert eng.prefill_tick() == []
    done = eng.prefill_tick()
    assert [slot for slot, _ in done] == [1]
    after = [np.asarray(s) for s in eng._states]
    for was, now in zip(before, after):
        for slot in (0, 2):
            np.testing.assert_array_equal(now[:, slot], was[:, slot])
    _, _, _, whole = _engine(chunk=32)   # the same prompt in one launch
    whole.admit(1, ragged, 9)
    for got, want in zip(_lines(eng, 1), _lines(whole, 1)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_preempt_then_other_traffic_in_the_slot_then_restore_is_exact():
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(8)
    a, b = _prompt(rng, 17), _prompt(rng, 9)
    served = [eng.admit(0, a, 40)]
    for _ in range(9):
        served.append(int(step_now(eng)[0]))
    held = _lines(eng, 0)
    with obs_context.span("test.root"):
        blob = eng.preempt(0)
    assert [b_.shape for b_ in blob["state"]] == [(4, LINE)]
    np.testing.assert_array_equal(blob["state"][0], held[0])
    assert eng.pool.used_pages == 0 and not eng._mask[0]
    # another sequence lives in the slot meanwhile
    _serve(eng, 0, b, 8)
    eng.release(0)
    assert (_lines(eng, 0)[0] != held[0]).any()
    eng.restore(0, blob)
    np.testing.assert_array_equal(_lines(eng, 0)[0], held[0])
    for _ in range(30):
        served.append(int(step_now(eng)[0]))
    _, _, _, straight = _engine()
    assert served == _serve(straight, 0, a, 40), \
        "the resumed stream is the unbroken one, token for token"
    assert _gaps(key, sz, a, served).max() <= GAP_TOL
    spans = {s.name: s.attrs for s in obs_context.finished_spans()
             if s.name in ("engine.preempt", "engine.restore")}
    assert spans["engine.preempt"]["state_bytes"] == eng.state_slot_bytes
    assert spans["engine.restore"]["state_bytes"] == 4 * LINE * 4


def test_resumed_logits_are_the_unbroken_streams():
    """The scores behind every token after a preempt and a restore, not
    their best token alone: the head's rows of the resumed engine against
    those of one that never stopped."""
    from engine_util import spy_head

    rng = np.random.default_rng(18)
    a = _prompt(rng, 14)

    def run(pause):
        _, _, _, eng = _engine()
        heads = spy_head(eng)
        eng.admit(1, a, 30)
        for i in range(20):
            if i == pause:
                eng.restore(1, eng.preempt(1))
            step_now(eng)
        jax.effects_barrier()
        return [h[1] for h in heads[1:]]     # slot 1's row of every step

    for got, want in zip(run(7), run(-1)):
        np.testing.assert_array_equal(got, want)


def test_slots_at_different_positions_do_not_see_each_others_state():
    """One decodes while another's prompt rides in three launches between
    its steps; the first leaves, a third starts in its slot: every stream
    is the reference's of its own sequence alone."""
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(9)
    pa, pb, pc = _prompt(rng, 9), _prompt(rng, 21), _prompt(rng, 5)
    outs = {"a": [eng.admit(0, pa, 60)], "b": [], "c": []}
    for _ in range(3):
        outs["a"].append(int(step_now(eng)[0]))
    eng.admit_start(1, pb, 40)
    while True:
        done = eng.prefill_tick()
        if done:
            break
        outs["a"].append(int(step_now(eng)[0]))
    outs["b"].append(done[0][1])
    for _ in range(10):
        tok = step_now(eng)
        outs["a"].append(int(tok[0]))
        outs["b"].append(int(tok[1]))
    eng.release(0)
    eng.admit_start(0, pc, 30)
    while True:
        done = eng.prefill_tick()
        if done:
            break
        outs["b"].append(int(step_now(eng)[1]))
    outs["c"].append(done[0][1])
    for _ in range(12):
        tok = step_now(eng)
        outs["b"].append(int(tok[1]))
        outs["c"].append(int(tok[0]))
    for prompt, name in ((pa, "a"), (pb, "b"), (pc, "c")):
        assert _gaps(key, sz, prompt, outs[name]).max() <= GAP_TOL, name
    # and a stream does not depend on who shares its steps
    _, _, _, alone = _engine()
    assert _serve(alone, 1, pb, len(outs["b"])) == outs["b"]


def test_the_state_is_a_fixed_cost_a_slot_and_not_a_cost_a_token():
    cfg, sz, key, eng = _engine()
    slot_bytes = 4 * LINE * 4
    assert eng.state_slot_bytes == slot_bytes
    assert eng.cache_bytes == sum(p.nbytes for p in eng._pools) \
        + 3 * slot_bytes
    # four attention layers' two lines of 32 a token, and no slot's line
    assert eng.token_bytes == 4 * (32 + 32) * 4
    assert eng.projected_page_bytes(10, 6) == 4 * eng.pool.page_bytes
    mem = eng.memory_bytes()
    assert mem["bytes"] == eng.cache_bytes
    assert mem["state"] == eng.state_stats() == {
        "layers": 4, "attention_layers": 4, "slots": 3, "slots_live": 0,
        "slot_bytes": slot_bytes, "bytes": 3 * slot_bytes,
        "shapes": [[LINE]]}
    assert mem["kinds"]["full"]["layers"] == 4


# -- the carry down the stack and the merge ------------------------------------

def _without(name):
    """An edit of the layers' parameters that takes a mechanism out."""
    def edit(li, w):
        if name == "gamma" and li:
            w = {**w, "router": {**w["router"], "gamma": jnp.zeros_like(
                w["router"]["gamma"])}}
        if name == "shift":
            w = {**w, "wv2": jnp.zeros_like(w["wv2"])}
        return w
    return edit


@pytest.mark.parametrize("name", ["gamma", "shift"])
def test_the_depth_average_and_the_value_shift_reach_the_logits(name):
    """Layer ``l``'s router adds ``gamma`` times layer ``l - 1``'s
    activations: with ``gamma`` zeroed the served logits change, by the same
    amount in the program as in the reference. Likewise the shifted half of
    the values (``wv2`` zeroed: what the kept line's last part feeds)."""
    cfg, sz, key, params = _model()
    edit = _without(name)
    edited = {**params, "blocks": [edit(li, blk) for li, blk
                                   in enumerate(params["blocks"])]}
    rng = np.random.default_rng(11)
    prompt = _prompt(rng, 19)

    def last_scores(p):
        eng = _entry(cfg, p).make_continuous(**ENGINE)
        launches = spy_launches(eng)
        eng.admit(0, prompt, 4)
        return launches[-1][2]

    rows = np.asarray([[prompt.size - 1]], np.int32)
    tokens = np.pad(prompt, (0, LIMIT - prompt.size))[None]
    with_it = ref.logits_for(key, sz, tokens, rows)["none"][0, 0]
    without = ref.logits_for(key, sz, tokens, rows, edit=edit)["none"][0, 0]
    assert np.abs(with_it - without).max() > 100 * LOGIT_TOL
    np.testing.assert_allclose(last_scores(params), with_it,
                               atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(last_scores(edited), without,
                               atol=LOGIT_TOL, rtol=0)


def test_the_carry_opens_empty_and_goes_from_each_feed_forward_to_the_next():
    cfg, _, _, params = _model()
    fam = family_of(cfg)
    x = jnp.ones((2, 3, 32), jnp.float32)
    live = jnp.ones((2, 3), bool)
    assert fam.open_stack(params, x) is None
    y0, counts, act0 = fam.ffn_carry(params["blocks"][0], x, live, None)
    assert y0.shape == x.shape and act0.shape == (2, 3, 8)
    assert int(counts[1]) == 6, "one assignment a live row"
    y1, _, act1 = fam.ffn_carry(params["blocks"][1], x, live, act0)
    _, _, cold = fam.ffn_carry(params["blocks"][1], x, live, jnp.zeros_like(act0))
    gamma = params["blocks"][1]["router"]["gamma"]
    np.testing.assert_allclose(act1 - cold, gamma * act0, atol=1e-5)


def test_layer_0_keeps_the_residual_and_the_others_scale_and_shift_it():
    cfg, _, _, params = _model()
    fam = family_of(cfg)
    x = jnp.full((1, 2, 32), 2.0)
    y = jnp.full((1, 2, 32), 3.0)
    first, later = params["blocks"][0], params["blocks"][1]
    assert set(first["res_attn"]) == {"by", "sy"}
    assert set(later["res_ffn"]) == {"bx", "sx", "by", "sy"}
    r = first["res_attn"]
    np.testing.assert_allclose(fam.merge(first, x, y, "attention"),
                               x + (y + r["by"]) * r["sy"], rtol=1e-6)
    r = {k: v * 1.5 for k, v in later["res_ffn"].items()}
    scaled = {**later, "res_ffn": r}
    np.testing.assert_allclose(
        fam.merge(scaled, x, y, "ffn"),
        (x + r["bx"]) * r["sx"] + (y + r["by"]) * r["sy"], rtol=1e-6)


def test_one_token_runs_one_expert_under_the_softmaxs_own_weight():
    cfg, _, _, params = _model()
    fam = family_of(cfg)
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    experts, weights, _ = fam.route(params["blocks"][0]["router"], h, None)
    assert experts.shape == weights.shape == (64, 1)
    assert len(set(np.asarray(experts)[:, 0].tolist())) > 1
    # not renormalised over the chosen: a weight of one would be
    assert float(weights.max()) < 1.0 and float(weights.min()) > 0.0


# -- spans, counters, and the kernels' forms ----------------------------------

def test_spans_and_counters_carry_the_state(monkeypatch):
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(12)
    with obs_context.span("test.root"):
        eng.admit(0, _prompt(rng, 11), 6)
        step_now(eng)
    spans = [s for s in obs_context.finished_spans()
             if s.name in ("engine.chunk.prepare", "engine.step.prepare")]
    chunk = [s.attrs for s in spans if s.name == "engine.chunk.prepare"]
    assert [a["state_reset"] for a in chunk[-2:]] == [1, 0]
    step = [s.attrs for s in spans if s.name == "engine.step.prepare"][-1]
    assert step["state_slots_live"] == 1 and step["state_slots"] == 3
    assert step["attn_by_head"] == 0
    counters = eng.counters()
    assert counters["state_slots"] == 3 and counters["moe_assignments"] > 0


def test_the_programs_carry_the_scopes_the_benchmark_reads():
    cfg, sz, key, eng = _engine()
    S, NB = eng.slots, eng.blocks_per_slot
    text = eng._step.func.lower(
        eng.params, jnp.zeros((S, 1), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.zeros((S,), bool), jnp.zeros((S, NB), jnp.int32), *eng._pools,
        *eng._states, jnp.zeros((S,), jnp.int32)).as_text(debug_info=True)
    for scope in ("cca.in", "cca.mix", "attn.full", "cca.out", "moe.router",
                  "moe.experts", "merge", "head"):
        assert f"/{scope}/" in text, scope


def test_served_through_both_kernels_matches_the_reference(monkeypatch):
    """Widths of whole lanes, the step's attention through the paged
    kernel and the experts through the streaming kernel, both interpreted:
    what a TPU runs."""
    import functools

    from nnstreamer_tpu.ops import moe_grouped, paged_attention

    monkeypatch.setattr(
        paged_attention, "paged_line_attention",
        functools.partial(paged_attention.kernel_line_attention,
                          interpret=True))
    monkeypatch.setattr(
        moe_grouped, "grouped_experts",
        functools.partial(moe_grouped.tpu_grouped_experts, interpret=True))
    over = dict(hidden_size=128, head_dim=128, moe_intermediate_size=128,
                num_hidden_layers=2, weight_std=0.1)
    cfg, sz, key, params = _model(**over)
    eng = _entry(cfg, params).make_continuous(**{**ENGINE, "slots": 2})
    assert eng.family.cache_lines == (256, 256)
    rng = np.random.default_rng(13)
    prompt = _prompt(rng, 13)
    served = _serve(eng, 1, prompt, 8)
    assert _gaps(key, sz, prompt, served).max() <= 5 * GAP_TOL
