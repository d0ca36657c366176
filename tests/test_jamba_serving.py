"""The Jamba family (state-space layers that keep a state a *slot* beside
multi-query attention layers that keep lines a *token*) through the paged
serving engine, against the benchmark's plain reference
(``benchmark/references/jamba_lm.py``: a full forward with no cache, the
recurrence a ``lax.scan`` over positions, float32 at ``highest``). CPU,
small sizes, seeded weights; logits are compared, never sampled tokens.

Sizes: eight layers with an attention layer every fourth from the third
(state, state, attention, state, state, state, attention, state: two state
layers before and after an attention layer), prompts that take one launch,
several, and several with a ragged last one (chunks of 8), a limit of 96.
The weights' std is 0.15 and not the benchmark's 0.02: at a hundredth of the
published widths the layers would add nothing to the embedding and the tied
head would repeat the last token whatever they did.

Tolerances. Everything here is float32 on the CPU, so program and reference
differ only by the order of float32 sums: logits of size 1 agree to a few
1e-6; the limits (2e-5 on logits, 1e-4 on the gap of a served token under
the reference's best) are the other families'.
"""
import functools
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
from benchmark.references import jamba_lm as ref  # noqa: E402
from nnstreamer_tpu.models.families import family_of  # noqa: E402
from nnstreamer_tpu.models.jamba import JambaConfig, JambaFamily  # noqa: E402
from nnstreamer_tpu.models.lm_serving import _LMServingEntry  # noqa: E402
from nnstreamer_tpu.obs import context as obs_context  # noqa: E402
from nnstreamer_tpu.ops import selective_scan  # noqa: E402
from nnstreamer_tpu.serving import DecodeScheduler, PagedLMEngine  # noqa: E402

LIMIT = 96
SIZES = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=1, intermediate_size=64,
    attn_layer_period=4, attn_layer_offset=2, expert_layer_period=2,
    expert_layer_offset=1, num_experts=1, num_experts_per_tok=1,
    mamba_expand=2, mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=4,
    mamba_conv_bias=True, mamba_proj_bias=False, rms_norm_eps=1e-6,
    max_position_embeddings=LIMIT, tie_word_embeddings=True,
    sliding_window=None, hidden_act="silu", weight_std=0.15)
KINDS = ("state", "state", "full", "state", "state", "state", "full", "state")
LOGIT_TOL = 2e-5
GAP_TOL = 1e-4
ENGINE = dict(slots=3, page_size=4, chunk=8, share_prefixes=False)


def _model(seed=5, dtype=jnp.float32, **over):
    conf = {**SIZES, **over}
    cfg = JambaConfig.from_published(conf)
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
    served = np.asarray(served)
    exact = _reference_logits(key, sz, prompt, served)
    return exact.max(-1) - np.take_along_axis(exact, served[:, None], 1)[:, 0]


def _states(eng, slot):
    return [np.asarray(s[:, slot]).copy() for s in eng._states]


def _prompt(rng, n):
    return rng.integers(0, 96, n).astype(np.int32)


# -- the family ----------------------------------------------------------------

def test_the_family_is_chosen_by_the_configurations_type_and_says_its_kinds():
    cfg, _, _, _ = _model()
    fam = family_of(cfg)
    assert isinstance(fam, JambaFamily) and fam.name == "jamba"
    assert fam.layer_kinds == KINDS
    assert fam.window is None and fam.cache_lines == (8, 8)
    assert fam.counters == () and not fam.serves_verify
    # the conv's last three inputs flat, the scan state channels last
    assert fam.state_lines == (((3 * 64,), None), ((16, 64), "float32"))
    with pytest.raises(TypeError, match="JambaConfig"):
        family_of(object())


def test_the_published_keys_put_attention_at_layers_7_and_21():
    cfg = JambaConfig.from_published({
        "attn_layer_offset": 7, "attn_layer_period": 14, "hidden_size": 2560,
        "mamba_expand": 2, "num_attention_heads": 20,
        "num_hidden_layers": 28, "num_key_value_heads": 1,
        "mamba_d_state": 16, "mamba_d_conv": 4, "use_mamba_kernels": True})
    fam = JambaFamily(cfg)
    assert [i for i, k in enumerate(fam.layer_kinds) if k == "full"] == [7, 21]
    assert fam.layer_kinds.count("state") == 26
    assert cfg.head_dim == 128 and fam.cache_lines == (128, 128)
    assert fam.state_lines == (((15360,), None), ((16, 5120), "float32"))


@pytest.mark.parametrize("key,value", [
    ("num_experts", 2), ("sliding_window", 16), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("tie_word_embeddings", False),
    ("hidden_act", "gelu"), ("num_key_value_heads", 3),
    ("attn_layer_offset", 4),
])
def test_a_key_the_block_does_not_implement_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        JambaConfig.from_published({**SIZES, key: value})


def test_speculative_decoding_is_refused_for_the_family_by_name():
    cfg, _, _, params = _model()
    with pytest.raises(NotImplementedError, match="jamba.*roll.*state"):
        _entry(cfg, params).make_continuous(draft="ngram", **ENGINE)


def test_prefix_sharing_is_refused_for_a_family_with_state_layers():
    cfg, _, _, params = _model()
    with pytest.raises(NotImplementedError,
                       match="jamba.*state.*share_prefixes"):
        PagedLMEngine(cfg, params, slots=2, page_size=4, chunk=8)


# -- the served path against the reference's full forward ----------------------

def test_chunked_prefill_then_decode_matches_the_reference_forward():
    cfg, sz, key, eng = _engine()
    assert isinstance(eng, PagedLMEngine) and eng.family.name == "jamba"
    assert eng.kinds == ("full",) and eng.state_layers == 6
    assert eng.kind_layers == {"full": 2}
    assert [s.shape for s in eng._states] == [(6, 3, 192), (6, 3, 16, 64)]
    assert eng._states[1].dtype == jnp.float32
    chunk_scores = spy_launches(eng)
    dispatched, real_step = [], eng._step

    def count(*args):
        dispatched.append(1)
        return real_step(*args)

    eng._step = count
    sched = DecodeScheduler(eng, name="jamba-a")
    rng = np.random.default_rng(0)
    # five launches with a ragged last one; one launch; six; two
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
    # the state is a kind of cache of its own in the snapshot
    assert snap["state"]["layers"] == 6 and snap["state"]["slots"] == 3
    assert snap["state"]["bytes"] == 3 * snap["state"]["slot_bytes"] \
        == 3 * 6 * (192 * 4 + 16 * 64 * 4)
    assert 0 < snap["state_slots_live"] <= snap["state_slots"]
    # every step dispatched reads and writes every slot's state; a pass
    # whose slots all wait for their last token dispatches none
    assert snap["state_slots"] == len(dispatched) * 3
    assert len(dispatched) <= snap["decode_steps"]


def test_decode_steps_logits_match_the_reference_far_into_the_sequence():
    """Ninety positions through the state: a recurrence that drifted would
    show in the later tokens' gaps."""
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(2)
    prompt = _prompt(rng, 11)
    served = [eng.admit(0, prompt, 84)]
    for _ in range(83):
        served.append(int(step_now(eng)[0]))
    exact = _reference_logits(key, sz, prompt, np.asarray(served))
    assert (exact.argmax(-1) == np.asarray(served)).all()
    assert len(set(served)) > 20, "the toy model does not repeat itself"
    assert eng._pos[0] == 94


def test_grouped_query_heads_serve_too():
    cfg, sz, key, params = _model(num_key_value_heads=2)
    eng = _entry(cfg, params).make_continuous(**ENGINE)
    assert eng.family.cache_lines == (16, 16)
    rng = np.random.default_rng(3)
    prompt = _prompt(rng, 19)
    served = [eng.admit(1, prompt, 30)]
    for _ in range(29):
        served.append(int(step_now(eng)[1]))
    assert _gaps(key, sz, prompt, served).max() <= GAP_TOL


# -- the state a slot ---------------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 8, 12])
def test_a_prompt_in_one_launch_and_in_several_leaves_the_same_state(chunk):
    """29 tokens in one launch of 32, and in launches of 4 (ragged last: 1),
    8 (5) and 12 (5): the same scores of the last row and the same state
    to the order of float32 sums."""
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
        return launches[-1][2], _states(eng, 2), first, eng

    whole_logits, whole_state, first, _ = ingest(32)
    logits, state, again, eng = ingest(chunk)
    assert eng._lane[2][1] == -(-29 // chunk)
    assert again == first
    np.testing.assert_allclose(logits, whole_logits, atol=LOGIT_TOL, rtol=0)
    for got, want in zip(state, whole_state):
        assert np.abs(want).max() > 0.01
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)


def test_a_slot_reused_after_release_starts_from_zero():
    """Serve A, release, serve B in the same slot = B alone: the launch
    that starts a sequence zeroes the rows, whatever the slot held."""
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(6)
    a, b = _prompt(rng, 23), _prompt(rng, 13)
    eng.admit(1, a, 12)
    for _ in range(11):
        step_now(eng)
    held = _states(eng, 1)
    eng.release(1)
    for got, was in zip(_states(eng, 1), held):
        np.testing.assert_array_equal(got, was)  # release moves no state
        assert np.abs(was).max() > 0.01
    served = [eng.admit(1, b, 20)]
    for _ in range(19):
        served.append(int(step_now(eng)[1]))
    _, _, _, fresh = _engine()
    alone = [fresh.admit(1, b, 20)]
    for _ in range(19):
        alone.append(int(step_now(fresh)[1]))
    assert served == alone
    for got, want in zip(_states(eng, 1), _states(fresh, 1)):
        np.testing.assert_array_equal(got, want)
    assert _gaps(key, sz, b, served).max() <= GAP_TOL


def test_a_dead_slot_and_a_padded_row_change_no_byte_of_any_state():
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
    step_now(eng)                           # only slot 0 is live
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
    for got, want in zip(_states(eng, 1), _states(whole, 1)):
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)


def test_preempt_then_other_traffic_in_the_slot_then_restore_is_exact():
    cfg, sz, key, eng = _engine()
    rng = np.random.default_rng(8)
    a, b = _prompt(rng, 17), _prompt(rng, 9)
    served = [eng.admit(0, a, 40)]
    for _ in range(9):
        served.append(int(step_now(eng)[0]))
    held = _states(eng, 0)
    with obs_context.span("test.root"):
        blob = eng.preempt(0)
    assert [b_.shape for b_ in blob["state"]] == [(6, 192), (6, 16, 64)]
    for got, want in zip(blob["state"], held):
        np.testing.assert_array_equal(got, want)
    assert eng.pool.used_pages == 0 and not eng._mask[0]
    # another sequence lives in the slot meanwhile
    eng.admit(0, b, 12)
    for _ in range(7):
        step_now(eng)
    eng.release(0)
    assert any((now != was).any()
               for now, was in zip(_states(eng, 0), held))
    eng.restore(0, blob)
    for got, want in zip(_states(eng, 0), held):
        np.testing.assert_array_equal(got, want)
    for _ in range(30):
        served.append(int(step_now(eng)[0]))
    _, _, _, straight = _engine()
    want = [straight.admit(0, a, 40)]
    for _ in range(39):
        want.append(int(step_now(straight)[0]))
    assert served == want
    assert _gaps(key, sz, a, served).max() <= GAP_TOL
    spans = {s.name: s.attrs for s in obs_context.finished_spans()
             if s.name in ("engine.preempt", "engine.restore")}
    assert spans["engine.preempt"]["state_bytes"] == eng.state_slot_bytes
    assert spans["engine.restore"]["state_bytes"] == 6 * (192 + 1024) * 4


def test_several_slots_at_different_depths_share_a_step():
    """One decodes while another's prompt rides in three launches between
    its steps; the first leaves, a third starts in its slot: every stream
    is the reference's."""
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


def test_the_state_is_a_fixed_cost_a_slot_and_not_a_cost_a_token():
    cfg, sz, key, eng = _engine()
    slot_bytes = 6 * (192 * 4 + 16 * 64 * 4)
    assert eng.state_slot_bytes == slot_bytes
    assert eng.cache_bytes == sum(p.nbytes for p in eng._pools) \
        + 3 * slot_bytes
    # two attention layers' lines a token, and no state layer's
    assert eng.token_bytes == 2 * (8 + 8) * 4
    # the memory guard charges pages and nothing a slot holds anyway
    assert eng.projected_page_bytes(10, 6) == 4 * eng.pool.page_bytes
    assert eng.projected_page_bytes(10, 6) < slot_bytes
    mem = eng.memory_bytes()
    assert mem["bytes"] == eng.cache_bytes
    assert mem["state"] == eng.state_stats() == {
        "layers": 6, "slots": 3, "slots_live": 0, "slot_bytes": slot_bytes,
        "bytes": 3 * slot_bytes, "shapes": [[192], [16, 64]]}
    assert mem["kinds"]["full"]["layers"] == 2
    # an engine of a family with no state layer says so by None
    from nnstreamer_tpu.models.lm_serving import tiny
    plain = tiny.make_continuous(slots=2, page_size=4, chunk=8)
    assert plain.state_stats() is None and "state" not in plain.memory_bytes()


def test_spans_counters_and_gauges_carry_the_state(monkeypatch):
    from nnstreamer_tpu.obs import metrics as obs_metrics

    cfg, sz, key, eng = _engine()
    sched = DecodeScheduler(eng, name="jamba-obs")
    rng = np.random.default_rng(10)
    try:
        reqs = [sched.submit(_prompt(rng, n), steps=s)
                for n, s in ((13, 10), (6, 14))]
        for r in reqs:
            r.result(timeout=300)
        text = obs_metrics.default_registry.render()
    finally:
        sched.close()
    spans = obs_context.finished_spans()
    steps = [s.attrs for s in spans if s.name == "engine.step.prepare"
             and "state_slots" in s.attrs]
    assert steps and all(a["state_slots"] == 3 for a in steps)
    assert all(a["state_slots_live"] == a["live"] for a in [
        dict(s.attrs) for s in spans if s.name == "engine.step.prepare"
        and "state_slots" in s.attrs])
    launches = [s.attrs for s in spans if s.name == "engine.chunk.prepare"
                and "state_reset" in s.attrs]
    # 13 tokens: a launch that starts the sequence and one that does not
    assert {(a["start"], a["state_reset"]) for a in launches} >= {
        (0, 1), (8, 0)}
    assert 'nns_serving_state_bytes{scheduler="jamba-obs"}' in text
    assert 'nns_serving_state_slots_live{scheduler="jamba-obs"}' in text
    assert 'nns_serving_state_slots_total{scheduler="jamba-obs"}' in text


# -- the kernels, interpreted, against the plain forms -----------------------------

def _scan_args(rows, n, width, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return (jax.random.normal(ks[0], (n, width)),
            jax.nn.softplus(jax.random.normal(ks[1], (rows, width)) - 3),
            jax.random.normal(ks[2], (rows, width)),
            -jnp.exp(jax.random.normal(ks[3], (n, width))),
            jax.random.normal(ks[4], (rows, n)),
            jax.random.normal(ks[5], (rows, n)),
            jax.random.normal(ks[6], (width,)))


@pytest.mark.parametrize("rows,n,width,n_valid", [
    (16, 16, 256, 11), (24, 8, 640, 24), (16, 16, 128, 3), (32, 16, 1024, 9)])
def test_the_launchs_scan_kernel_is_the_plain_scan(rows, n, width, n_valid):
    args = _scan_args(rows, n, width)
    y0, h0 = selective_scan.plain_chunk_scan(*args, n_valid)
    y1, h1 = selective_scan.kernel_chunk_scan(*args, n_valid, interpret=True)
    np.testing.assert_allclose(y1[:n_valid], y0[:n_valid], atol=1e-5)
    np.testing.assert_allclose(h1, h0, atol=1e-5, rtol=1e-5)
    # whole groups of eight past the last real row are skipped: zeros
    skipped = -(-n_valid // 8) * 8
    assert not np.asarray(y1[skipped:]).any()
    assert np.isfinite(np.asarray(y1)).all()
    # a launch with no real row at all leaves the state bit for bit
    _, same = selective_scan.kernel_chunk_scan(*args, 0, interpret=True)
    np.testing.assert_array_equal(same, args[0])


@pytest.mark.parametrize("slots,n,width,layers", [(16, 16, 256, 3),
                                                  (8, 8, 640, 2)])
def test_the_steps_kernel_updates_live_slots_in_place(slots, n, width, layers):
    _, dt, u, a, b, c, d = _scan_args(slots, n, width, seed=1)
    h_all = jax.random.normal(jax.random.PRNGKey(9), (layers, slots, n, width))
    live = jnp.arange(slots) % 3 != 0
    y0, g0 = selective_scan.plain_slots_update(h_all, 1, live, dt, u, a, b,
                                               c, d)
    y1, g1 = selective_scan.kernel_slots_update(h_all, 1, live, dt, u, a, b,
                                                c, d, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(g1, g0, atol=1e-6, rtol=1e-6)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(g1[1])[dead],
                                  np.asarray(h_all[1])[dead])
    for other in set(range(layers)) - {1}:
        np.testing.assert_array_equal(g1[other], h_all[other])


def test_shapes_the_kernels_cannot_tile_take_the_plain_form():
    assert selective_scan.tiles(256, 5120) == 512
    assert selective_scan.tiles(128, 5120, selective_scan.STEP_TILE) == 2560
    assert selective_scan.tiles(8, 640) == 128  # 640 = 5 x 128
    assert selective_scan.tiles(8, 64) is None   # not whole lanes
    assert selective_scan.tiles(3, 128) is None  # not whole groups of eight
    args = _scan_args(8, 16, 64)
    y0, h0 = selective_scan.plain_chunk_scan(*args, 5)
    y1, h1 = selective_scan.tpu_chunk_scan(*args, 5)
    np.testing.assert_array_equal(y1, y0)
    np.testing.assert_array_equal(h1, h0)


def test_served_through_both_kernels_matches_the_reference(monkeypatch):
    """Both programs with the state layers in the forms a TPU runs (the
    kernels, interpreted here), at an inner width of whole lanes and eight
    slots: the served tokens are the reference's."""
    calls = {"chunk": 0, "step": 0}
    chunk_real = selective_scan.kernel_chunk_scan
    step_real = selective_scan.kernel_slots_update

    def chunk(*args, **kw):
        calls["chunk"] += 1
        return chunk_real(*args, **kw)

    def step(*args, **kw):
        calls["step"] += 1
        return step_real(*args, **kw)

    monkeypatch.setattr(selective_scan, "kernel_chunk_scan", chunk)
    monkeypatch.setattr(selective_scan, "kernel_slots_update", step)
    monkeypatch.setattr(selective_scan, "chunk_scan", functools.partial(
        selective_scan.tpu_chunk_scan, interpret=True))
    monkeypatch.setattr(selective_scan, "slots_update", functools.partial(
        selective_scan.tpu_slots_update, interpret=True))
    cfg, sz, key, params = _model(hidden_size=64, num_hidden_layers=4,
                                  attn_layer_period=4, attn_layer_offset=1)
    eng = _entry(cfg, params).make_continuous(**{**ENGINE, "slots": 8})
    sched = DecodeScheduler(eng, name="jamba-kernel")
    rng = np.random.default_rng(11)
    lengths = [(21, 20), (7, 12), (30, 9)]
    prompts = [_prompt(rng, n) for n, _ in lengths]
    try:
        reqs = [sched.submit(p, steps=s)
                for p, (_, s) in zip(prompts, lengths)]
        outs = [np.asarray(r.result(timeout=600)[0]) for r in reqs]
    finally:
        sched.close()
    # traced once a program and state layer
    assert calls == {"chunk": 3, "step": 3}
    for prompt, served in zip(prompts, outs):
        assert _gaps(key, sz, prompt, served).max() <= GAP_TOL
